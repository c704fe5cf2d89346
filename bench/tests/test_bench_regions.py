"""The region reduction (``regions.py``) and its readers, on a constructed
trace over an excerpt of the ``train-2k`` step as the v5e compiled it: one
instruction of most regions and phases, the forward SSD call (its Mosaic
body left out), a layout copy and a weight cast that carry no metadata of
their own, and the layer loop."""
from pathlib import Path

import pytest

import regions
import spec
import tracereduce
from tracereduce import Event

EXCERPT = (Path(__file__).parent / "data" / "regions_v5e.hlo").read_text()
NEW = ("region_ms.proj", "region_ms.ssd_state", "region_ms.elementwise",
       "region_ms.vocab", "region_ms.optimizer", "phase_ms.recompute",
       "sim_region_err")

# (instruction, seconds) over a window of two steps; the layer loop spans
# the ops of its body, and one op is not in the excerpt
TRACE = [
    ("fusion", 0.010), ("convert.242", 0.020), ("while.136", 0.500),
    ("multiply_reduce_fusion.56", 0.040), ("multiply_reduce_fusion.57",
                                           0.030),
    ("divide_multiply_fusion.6", 0.025), ("copy.510", 0.005),
    ("ssd_scan.32", 0.060), ("copy.516", 0.015), ("fusion.399", 0.002),
    ("multiply_reduce_fusion.59", 0.035), ("fusion.457", 0.045),
    ("multiply_add_fusion.84", 0.012), ("fusion.256", 0.050),
    ("multiply_reduce_fusion.28", 0.070), ("fusion.189", 0.080),
    ("copy-done.999", 0.004),
]
IN_LOOP = {"multiply_reduce_fusion.56", "multiply_reduce_fusion.57",
           "divide_multiply_fusion.6", "copy.510", "ssd_scan.32",
           "copy.516", "fusion.399", "multiply_reduce_fusion.59",
           "fusion.457", "multiply_add_fusion.84"}
STEPS = 2


def _events():
    """Back to back, the loop's body ops nested inside it; the first op
    starts before the window and is clipped to it."""
    out, t = [], 0.0
    loop = dict(TRACE)["while.136"]
    body = sum(s for n, s in TRACE if n in IN_LOOP)
    for name, s in TRACE:
        if name in IN_LOOP:
            continue
        if name == "while.136":
            start, u = t, t + (loop - body) / 2
            for n, d in TRACE:
                if n in IN_LOOP:
                    out.append(Event(n, u, u + d))
                    u += d
            out.append(Event(name, start, start + loop))
        else:
            out.append(Event(name, t, t + s))
        t += s
    return out, t


def _ctx(hlo=EXCERPT):
    ops, end = _events()
    return {"hlo_text": hlo, "devices": [ops], "lo": 0.005, "hi": end,
            "steps": STEPS}


def _ms(*names):
    return 1e3 * sum(s for n, s in TRACE if n in names) / STEPS


@pytest.fixture
def no_sim(monkeypatch):
    monkeypatch.setattr(regions, "simulated", lambda text: None)


def test_regions_and_other_add_up_to_busy_time(no_sim):
    ctx = _ctx()
    red = regions.reduce(ctx)
    busy = tracereduce.busy_seconds(ctx["devices"][0], ctx["lo"], ctx["hi"])
    assert sum(red["ms"].values()) == pytest.approx(1e3 * busy / STEPS)
    by_region = {}
    for (r, _), v in red["ms"].items():
        by_region[r] = by_region.get(r, 0.0) + v
    assert by_region["other"] == pytest.approx(_ms("copy-done.999"))
    # the layer loop's own time is its span less its body's ops
    body = sum(s for n, s in TRACE if n in IN_LOOP)
    assert by_region["layers"] == pytest.approx(
        1e3 * (dict(TRACE)["while.136"] - body + 0.020) / STEPS)
    # the copy with no metadata counts to its user's region
    assert by_region["mixer.ssd_chunk"] == pytest.approx(
        _ms("copy.510", "ssd_scan.32"))
    assert by_region["embed"] == pytest.approx(_ms("fusion") - 1e3 * 0.005
                                               / STEPS)


def test_readers_on_the_constructed_trace(no_sim):
    ctx = _ctx()
    read = {m: spec.reader(m).read(ctx) for m in NEW}
    assert read["region_ms.proj"] == pytest.approx(_ms(
        "multiply_reduce_fusion.56", "multiply_reduce_fusion.57",
        "multiply_reduce_fusion.59", "fusion.457"))
    assert read["region_ms.ssd_state"] == pytest.approx(_ms("copy.516"))
    assert read["region_ms.elementwise"] == pytest.approx(_ms(
        "divide_multiply_fusion.6", "fusion.399", "multiply_add_fusion.84"))
    assert read["region_ms.vocab"] == pytest.approx(
        _ms("fusion", "fusion.256", "multiply_reduce_fusion.28")
        - 1e3 * 0.005 / STEPS)
    assert read["region_ms.optimizer"] == pytest.approx(_ms("fusion.189"))
    assert read["phase_ms.recompute"] == pytest.approx(
        _ms("multiply_reduce_fusion.59"))
    assert read["sim_region_err"] is None      # the simulator gave nothing


def test_sim_region_err_adds_errors_of_either_sign(monkeypatch):
    ctx = _ctx()
    monkeypatch.setattr(regions, "simulated", lambda text: None)
    measured = {}
    for (r, _), v in regions.reduce(ctx)["ms"].items():
        measured[r] = measured.get(r, 0.0) + v
    sim = dict(measured, head=measured["head"] + 10.0,
               optimizer=measured["optimizer"] - 10.0)
    ctx["regions"]["sim_ms"] = sim
    total = sum(measured.values())
    assert regions.sim_region_err(ctx) == pytest.approx(100 * 20.0 / total)


def test_table_names_every_region_and_the_busy_total(no_sim):
    red = regions.reduce(_ctx())
    text = regions.table(dict(red, sim_ms={"head": 1.0}))
    assert "mixer.ssd_chunk" in text and "other" in text
    assert text.splitlines()[-1].split()[0] == "busy"


def test_readers_read_nothing_without_region_metadata(monkeypatch):
    called = []
    monkeypatch.setattr(regions, "simulated", called.append)
    hlo = "\n".join(ln for ln in EXCERPT.splitlines()
                    if "metadata=" not in ln)
    ctx = _ctx(hlo)
    assert all(spec.reader(m).read(ctx) is None for m in NEW)
    assert called == []
