"""The trace reduction on a constructed trace."""
import pytest

import tracereduce as tr
from tracereduce import Event


def _ops():
    # device ops over a window [0, 10]: overlapping 1-3 and 2-4, nested 5-6
    # inside 5-7, one op hanging past the window's end
    return [Event("fusion.1", 1.0, 3.0), Event("fusion.2", 2.0, 4.0),
            Event("ssd_chunk_kernel", 5.0, 7.0),
            Event("ssd_chunk_bwd_kernel", 5.0, 6.0),
            Event("fusion.1", 9.0, 11.0)]


def test_busy_union_counts_overlaps_once_and_clips_to_window():
    assert tr.merged([(1, 3), (2, 4), (5, 7), (5, 6)], 0, 10) == \
        [(1, 4), (5, 7)]
    assert tr.busy_seconds(_ops(), 0.0, 10.0) == pytest.approx(3 + 2 + 1)


def test_idle_gaps_longest_first():
    gaps = tr.idle_gaps(_ops(), 0.0, 10.0)
    assert gaps == [(7.0, 9.0), (0.0, 1.0), (4.0, 5.0)]
    assert sum(e - s for s, e in gaps) + 6.0 == pytest.approx(10.0)


def test_gap_named_by_overlapping_host_activity():
    host = [Event("bench.window", 0.0, 10.0), Event("bench.feed", 6.5, 8.8),
            Event("bench.wait", 9.5, 10.0)]
    assert tr.host_activity((7.0, 9.0), host) == "bench.feed"
    assert tr.host_activity((4.0, 5.0), host) == "bench.window"
    assert tr.host_activity((4.0, 5.0), []) == "idle"


def test_kernel_time_and_summary():
    ops = _ops()
    assert tr.kernel_time(ops, "ssd_chunk_kernel", 0, 10) == (2.0, 1)
    assert tr.kernel_time(ops, "ssd_chunk_bwd_kernel", 0, 10) == (1.0, 1)
    s = tr.summarize([ops, ops[:2]], [Event("bench.feed", 6.5, 8.8)],
                     0.0, 10.0, top=2)
    assert s["busy_s"] == pytest.approx((6.0 + 3.0) / 2)
    assert s["window_s"] == 10.0
    assert s["device_ops"] == [["fusion.1", 3.0], ["fusion.2", 2.0]]
    assert s["idle_gaps"] == [["bench.feed", 2.0], ["idle", 1.0]]


def test_tpu_op_names_are_cut_to_the_instruction_name():
    line = ("%ssd_scan.32 = (f32[2,512,256,64]{3,2,1,0:T(8,128)}, f32[2]{0}) "
            "custom-call(%bitcast.718, %custom-call.58), "
            "custom_call_target=\"tpu_custom_call\"")
    assert tr.op_name(line) == "ssd_scan.32"
    assert tr.op_name("fusion.7") == "fusion.7"


def test_op_totals_count_self_time_under_a_loop_op():
    # a while op spanning its body: two ops inside it, one nested deeper
    ops = [Event("while.1", 0.0, 8.0), Event("fusion.1", 1.0, 4.0),
           Event("ssd_scan.32", 2.0, 3.0), Event("fusion.2", 5.0, 7.0)]
    got = tr.op_totals(ops, 0.0, 10.0)
    assert got == pytest.approx({"while.1": 3.0, "fusion.1": 2.0,
                                 "ssd_scan.32": 1.0, "fusion.2": 2.0})
    assert sum(got.values()) == pytest.approx(tr.busy_seconds(ops, 0, 10))
