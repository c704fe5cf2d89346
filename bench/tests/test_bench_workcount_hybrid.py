"""The shared attention core's work counts against values worked out by
hand, and the hybrid cell's three readers on a constructed trace."""
from pathlib import Path

import pytest

import regions
import spec
import workcount
import workcount_hybrid
from tracereduce import Event

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("region_ms.shared_block", "region_ms.shared_attn",
       "shared_attn_roofline")


def _cfg(n_heads=2, head_dim=4, n_layers=19, every=6):
    return {"n_layers": n_layers,
            "shared": {"n_heads": n_heads, "head_dim": head_dim,
                       "every": every}}


def test_shared_attn_work_by_hand():
    # B=1, S=8, H=2, D=4: a causal product is 2 x 2 heads x 32 x 4 = 512
    w = workcount_hybrid.shared_attn_work(_cfg(), batch=1, seq=8)
    assert w["fwd"] == (2 * 512, 4 * 256)       # QK^T, PV; q, k, v, o
    assert w["bwd"] == (4 * 512, 8 * 256)       # four products; 8 tensors
    assert workcount_hybrid.invocations(_cfg()) == 3          # 6, 12, 18
    assert workcount_hybrid.invocations(_cfg(n_layers=38)) == 6


def test_shared_attn_work_at_the_cells_shape_is_bound_by_operations():
    w = workcount_hybrid.shared_attn_work(_cfg(32, 128), batch=1, seq=4096)
    assert w["fwd"][0] == 2 * 2 * 32 * 4096 ** 2 / 2 * 128 == 137438953472
    assert w["fwd"][1] == 4 * 4096 * 32 * 128 * 4
    t_f, bound = workcount.least_time(*w["fwd"], PEAK)
    assert bound == "flops" and t_f == pytest.approx(137438953472 / 197e12)
    assert workcount.least_time(*w["bwd"], PEAK)[1] == "flops"


SHARED_HLO = """\
ENTRY %main.1 () -> () {
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(train_step)/jvp()/checkpoint/shared.attn/while/body/exp"}
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f2, metadata={op_name="jit(train_step)/transpose(jvp())/checkpoint/shared.attn/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f3, metadata={op_name="jit(train_step)/transpose(jvp())/checkpoint/rematted_computation/shared.qkv/dot_general"}
  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f4, metadata={op_name="jit(train_step)/jvp()/checkpoint/shared.mlp/dot_general"}
  %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f5, metadata={op_name="jit(train_step)/jvp()/checkpoint/shared.link/add"}
  %fusion.6 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f6, metadata={op_name="jit(train_step)/jvp(layers)/while/body/closed_call/mixer.in_proj/dot_general"}
}
"""
# seconds of each op over a window of two steps
TRACE = {"fusion.1": 0.030, "fusion.2": 0.050, "fusion.3": 0.010,
         "fusion.4": 0.020, "fusion.5": 0.004, "fusion.6": 0.100}


def _ctx(hlo=SHARED_HLO, cfg=None):
    ops, t = [], 0.0
    for name, s in TRACE.items():
        ops.append(Event(name, t, t + s))
        t += s
    traffic = {"batch": 1, "seq": 4096}
    return {"hlo_text": hlo, "devices": [ops], "lo": 0.0, "hi": t,
            "steps": 2, "peak": PEAK, "traffic": traffic,
            "cfg": cfg or _cfg(32, 128)}


@pytest.fixture
def no_sim(monkeypatch):
    monkeypatch.setattr(regions, "simulated", lambda text: None)


def test_hybrid_readers_on_a_constructed_trace(no_sim):
    ctx = _ctx()
    read = {n: spec.reader(n).read(ctx) for n in NEW}
    assert read["region_ms.shared_block"] == pytest.approx(
        1e3 * (0.030 + 0.050 + 0.010 + 0.020 + 0.004) / 2)
    attn_ms = 1e3 * (0.030 + 0.050) / 2
    assert read["region_ms.shared_attn"] == pytest.approx(attn_ms)
    w = workcount_hybrid.shared_attn_work(ctx["cfg"], 1, 4096)
    least_ms = 1e3 * 3 * (w["fwd"][0] + w["bwd"][0]) / 197e12
    assert read["shared_attn_roofline"] == pytest.approx(
        100 * least_ms / attn_ms)


def test_hybrid_readers_read_nothing_in_a_mamba_step(no_sim):
    excerpt = (Path(__file__).parent / "data" / "regions_v5e.hlo").read_text()
    ctx = _ctx(hlo=excerpt)
    assert all(spec.reader(n).read(ctx) is None for n in NEW)


def test_hybrid_metrics_are_listed_for_the_hybrid_cell_alone(benchmark_json):
    cell = "zamba2-1.2b.train-4k"
    per_layer = {m["name"]: m for m in benchmark_json["per_layer"]}
    for n in NEW:
        assert per_layer[n]["workloads"] == [cell]
    got = {m["name"] for m in spec.metrics_of(benchmark_json, cell,
                                               "per_layer")}
    assert got == set(NEW) | {"train_mfu", "device_idle.train"}
