"""Each per-layer reader on a constructed trace context."""
import base64
from pathlib import Path

import pytest

import spec
import tracereduce
import workcount
from tracereduce import Event

# The SSD kernel's forward and backward custom calls as the v5e compiler
# writes them (compiled for a described v5e), a Pallas call of another kernel
# with as many outputs as the forward, and a fusion.
SSD_CALLS = (Path(__file__).parent / "data" / "ssd_calls_v5e.hlo").read_text()
OTHER = base64.b64encode(b"\x00func.func\x00_flash_fwd_kernel\x00").decode()
HLO = SSD_CALLS + f"""\
  %other.3 = (f32[8]{{0}}, f32[8]{{0}}) custom-call(%p), custom_call_target="tpu_custom_call", backend_config={{"custom_call_config":{{"body":"{OTHER}"}}}}, metadata={{op_name="jit(f)/pallas_call"}}
  %fusion.7 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%fused
"""


PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _ctx(benchmark_json, ops, sim_t_est=None):
    cfg = spec.config(benchmark_json, "mamba2-1.3b")
    traffic = spec.traffic("train-2k")
    lo, hi = 0.0, 2.0
    return {"cfg": cfg, "traffic": traffic, "devices": [ops], "lo": lo,
            "hi": hi, "steps": 4, "peak": PEAK, "sim_t_est": sim_t_est,
            "hlo_text": HLO,
            "summary": tracereduce.summarize([ops], [], lo, hi)}


def _read(name, ctx):
    return spec.reader(name).read(ctx)


def test_readers_on_a_window_of_four_steps(benchmark_json):
    ops = [Event("fusion.1", 0.0, 1.5), Event("jvp__.1", 1.5, 1.6),
           Event("jvp__.1", 1.6, 1.7), Event("transpose_jvp___.1", 1.7, 1.9)]
    ctx = _ctx(benchmark_json, ops, sim_t_est=0.55)
    assert _read("device_idle.train", ctx) == pytest.approx(5.0)
    assert _read("ssd_kernel_ms", ctx) == pytest.approx(1e3 * 0.4 / 4)
    work = workcount.ssd_kernel_work(ctx["cfg"], 2, 2048)
    least = 2 * work["fwd"][1] / 819e9 + work["bwd"][1] / 819e9
    assert _read("ssd_kernel_roofline", ctx) == pytest.approx(
        100 * least / 0.4)
    flops = workcount.train_flops_per_token(ctx["cfg"]) * 4096 * 4
    assert _read("train_mfu", ctx) == pytest.approx(
        100 * flops / (2.0 * 197e12))
    assert _read("sim_est_err", ctx) == pytest.approx(10.0)


def test_ssd_calls_are_found_by_their_compiled_names(benchmark_json):
    import ssdcalls

    assert ssdcalls.kernel_ops(HLO) == {"jvp__.1": "fwd",
                                        "transpose_jvp___.1": "bwd"}
    ops = [Event("fusion.7", 0.0, 1.0), Event("jvp__.1", 1.0, 1.1),
           Event("jvp__.1", 1.1, 1.2), Event("transpose_jvp___.1", 1.2, 1.5),
           Event("other.3", 1.5, 1.9)]
    ctx = _ctx(benchmark_json, ops)
    assert ssdcalls.calls(ctx)["fwd"] == (pytest.approx(0.2), 2)
    assert ssdcalls.calls(ctx)["bwd"] == (pytest.approx(0.3), 1)
    assert _read("ssd_kernel_ms", ctx) == pytest.approx(1e3 * 0.5 / 4)


def test_readers_with_nothing_to_read_return_none(benchmark_json):
    ctx = _ctx(benchmark_json, [Event("fusion.1", 0.0, 1.0)])
    assert _read("ssd_kernel_ms", ctx) is None
    assert _read("ssd_kernel_roofline", ctx) is None
    assert _read("sim_est_err", ctx) is None


def test_a_reported_metric_that_reads_nothing_is_an_error(benchmark_json,
                                                         capsys):
    import run

    ctx = _ctx(benchmark_json, [Event("fusion.1", 0.0, 1.0)])
    got = run.per_layer(benchmark_json, "mamba2-1.3b.train-2k", ctx)
    assert "ssd_kernel_roofline" not in got and "ssd_kernel_ms" not in got
    assert {"train_mfu", "device_idle.train"} <= set(got)
    err = capsys.readouterr().err
    assert "error: ssd_kernel_roofline found nothing" in err
    assert "error: ssd_kernel_ms found nothing" in err
