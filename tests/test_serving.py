"""Differential + property suite for the serving simulator (ISSUE 10).

The event loop (``core.serving.simulate_serving``) is pinned three ways:

* **differentially** — against the closed-form M/D/1 mean wait at
  utilizations 0.3/0.6/0.9, and bit-identically against a batch-of-1
  serial reference that replays the same float operations;
* **by property** — Little's law (the loop's independently-integrated
  ``int N(t) dt`` equals the summed sojourns), percentile ordering, TTFT
  monotone in arrival rate, throughput monotone in max-batch until the
  KV-residency knee, fixed-seed determinism, and conservation (every
  request completes or is rejected exactly once, under every policy);
* **at the seams** — the KV sizing against the real cache pytrees
  (``cache_bytes`` vs ``cache_abstract`` leaves), the ServeEngine golden
  path (``_pad_cache`` pads only the spec-declared kvseq axis), the
  phase-keyed zoo cost caches (prefill/decode cells at the zoo's equal
  reduced shapes must never alias), the per-opcode VPU tables on the
  serving decode path, and the committed ``BENCH_serving.json`` schema.
"""
import dataclasses
import json
import math
import random
from pathlib import Path

import numpy as np
import pytest

from repro.core import zoo
from repro.core.hlo import OpStat, Program
from repro.core.hwspec import A64FX_CORE, A64FX_NODE
from repro.core.memory import stream_time
from repro.core.serving import (LengthDist, RequestSpec,
                                ServingKnobs, SyntheticCostModel,
                                ZooCostModel, load_trace_jsonl,
                                node_kv_levels, pareto_front, percentile,
                                poisson_requests, requests_from_trace,
                                simulate_serving, traffic_for)

BENCH_JSON = Path(__file__).resolve().parents[1] / "BENCH_serving.json"


def _cost(**kw):
    base = dict(prefill_t0=2e-4, prefill_per_token=1e-5,
                decode_t0=1e-4, decode_per_seq=2e-5,
                bytes_per_token=1e6, bytes_per_request=5e6)
    base.update(kw)
    return SyntheticCostModel(**base)


# --------------------------------------------------------- M/D/1 differential
@pytest.mark.parametrize("rho,n,tol", [(0.3, 50_000, 0.05),
                                       (0.6, 50_000, 0.05),
                                       (0.9, 300_000, 0.05)])
def test_md1_mean_wait_matches_analytic(rho, n, tol):
    """Batch-1 FCFS with deterministic service IS an M/D/1 queue: the
    simulated mean wait must land within 5% of rho*S/(2(1-rho))."""
    prompt = 100
    cost = _cost(prefill_t0=0.0, decode_per_seq=0.0,
                 bytes_per_token=0.0, bytes_per_request=0.0)
    s = cost.prefill_time(prompt)
    lam = rho / s
    reqs = poisson_requests(n, lam, LengthDist(prompt, 0.0, 1, 0.0), seed=0)
    res = simulate_serving(reqs, cost, ServingKnobs(max_batch=1))
    waits = [st.wait for st in res.done()]
    assert len(waits) == n
    wq = sum(waits) / n
    analytic = rho * s / (2.0 * (1.0 - rho))
    assert abs(wq - analytic) / analytic < tol


def test_md1_number_in_system_matches_analytic():
    """Little's law against the analytic M/D/1 L = lambda(Wq + S)."""
    prompt, rho, n = 100, 0.6, 50_000
    cost = _cost(prefill_t0=0.0, decode_per_seq=0.0,
                 bytes_per_token=0.0, bytes_per_request=0.0)
    s = cost.prefill_time(prompt)
    lam = rho / s
    reqs = poisson_requests(n, lam, LengthDist(prompt, 0.0, 1, 0.0), seed=1)
    res = simulate_serving(reqs, cost, ServingKnobs(max_batch=1))
    mean_l = res.area_in_system / res.duration
    analytic = lam * (rho * s / (2.0 * (1.0 - rho)) + s)
    assert abs(mean_l - analytic) / analytic < 0.05


# ------------------------------------------------- batch-of-1 serial identity
def _serial_reference(reqs, cost):
    """Replay the degenerate loop: completion_i = max(arrival, t) +
    prefill + per-step decode, same float operations in the same order."""
    out = {}
    t = 0.0
    for r in sorted(reqs, key=lambda r: (r.t_arrival, r.rid)):
        if r.t_arrival > t:
            t = r.t_arrival
        t = t + cost.prefill_time(r.prompt_tokens)
        first = t
        g = 1
        while g < r.out_tokens:
            kv = cost.kv_bytes(1, r.prompt_tokens + g)
            t = t + cost.decode_step_time(1, kv)
            g += 1
        out[r.rid] = (first, t)
    return out


def test_batch_of_1_bit_identity():
    """max_batch=1 + whole-prompt prefill degenerates to the serial
    reference EXACTLY — bit-equal first-token and completion times."""
    cost = _cost()
    reqs = poisson_requests(300, 40.0, LengthDist(120, 0.7, 12, 0.5),
                            seed=3)
    res = simulate_serving(reqs, cost, ServingKnobs(max_batch=1))
    ref = _serial_reference(reqs, cost)
    for st in res.stats:
        first, done = ref[st.spec.rid]
        assert st.t_first == first          # bit-identical, not approx
        assert st.t_done == done


# ------------------------------------------------------------------ properties
def test_littles_law_bookkeeping_identity():
    """area_in_system is integrated inside the loop, independently of the
    per-request timestamps; when every request leaves the system the two
    accumulations are the same integral -> equal to float precision, and
    the derived L = lambda*W gap collapses."""
    cost = _cost()
    reqs = poisson_requests(400, 300.0, LengthDist(100, 0.6, 16, 0.4),
                            seed=5)
    for knobs in (ServingKnobs(max_batch=1),
                  ServingKnobs(max_batch=8),
                  ServingKnobs(max_batch=8, prefill_chunk=64),
                  ServingKnobs(max_batch=8, admission="spf")):
        res = simulate_serving(reqs, cost, knobs)
        sojourn = sum(st.sojourn for st in res.stats
                      if st.completed or st.rejected)
        assert res.area_in_system == pytest.approx(sojourn, rel=1e-9)
        assert res.little_law_gap() < 1e-9


def test_percentile_matches_numpy():
    rng = random.Random(0)
    for _ in range(20):
        xs = [rng.uniform(0, 100) for _ in range(rng.randint(1, 50))]
        for q in (0, 10, 50, 90, 99, 100):
            assert percentile(xs, q) == pytest.approx(
                float(np.percentile(xs, q)), rel=1e-12, abs=1e-12)


def test_percentile_ordering_p50_le_p99():
    cost = _cost()
    reqs = poisson_requests(500, 200.0, LengthDist(100, 0.8, 20, 0.6),
                            seed=9)
    res = simulate_serving(reqs, cost, ServingKnobs(max_batch=4))
    for xs in (res.ttfts(), res.tpots()):
        assert percentile(xs, 50) <= percentile(xs, 90) <= percentile(xs, 99)
    m = res.metrics()
    assert m["p50_ttft_ms"] <= m["p99_ttft_ms"]
    assert m["p50_tpot_ms"] <= m["p99_tpot_ms"]


def test_ttft_monotone_in_arrival_rate():
    """Shrinking every inter-arrival gap can only grow each request's
    wait at batch 1 (the Lindley recursion is monotone); batched p50
    TTFT follows the same trend."""
    cost = _cost()
    base = poisson_requests(500, 1.0, LengthDist(100, 0.5, 8, 0.5), seed=7)

    def scaled(f):
        return [dataclasses.replace(r, t_arrival=r.t_arrival / f)
                for r in base]

    prev = None
    for f in (50.0, 100.0, 200.0):
        res = simulate_serving(scaled(f), cost, ServingKnobs(max_batch=1))
        ttfts = {st.spec.rid: st.ttft for st in res.done()}
        if prev is not None:
            assert all(ttfts[k] >= prev[k] - 1e-12 for k in ttfts)
        prev = ttfts
    p50s = [simulate_serving(scaled(f), cost,
                             ServingKnobs(max_batch=8)).metrics()
            ["p50_ttft_ms"] for f in (50.0, 100.0, 200.0)]
    assert p50s == sorted(p50s)


def test_throughput_monotone_in_max_batch_until_knee():
    """Under saturation, tokens/s/node grows with max_batch (within a
    0.1% discretization ripple) until the KV pool caps the effective
    batch — beyond the knee extra slots buy nothing."""
    cost = _cost()
    heavy = poisson_requests(300, 2000.0, LengthDist(100, 0.5, 16, 0.3),
                             seed=1)
    tps = []
    for b in (1, 2, 4, 8, 16, 32):
        res = simulate_serving(heavy, cost, ServingKnobs(max_batch=b))
        tps.append(res.tokens_per_s)
    for lo, hi in zip(tps, tps[1:]):
        assert hi >= lo * (1 - 1e-3)
    assert tps[2] > tps[0] * 1.05       # real gain before saturation

    # knee: capacity for ~6 requests caps the decode batch at ~6 and
    # flattens throughput for every max_batch beyond it
    cap = cost.kv_bytes(1, 130) * 6
    tight = dataclasses.replace(cost, kv_capacity=cap)
    knee = [simulate_serving(heavy, tight, ServingKnobs(max_batch=b))
            for b in (8, 16, 32)]
    assert all(r.metrics()["mean_decode_batch"] <= 6.0 + 1e-9
               for r in knee)
    t8, t16, t32 = (r.tokens_per_s for r in knee)
    assert abs(t16 - t8) / t8 < 0.02 and abs(t32 - t8) / t8 < 0.02


def test_fixed_seed_determinism():
    assert poisson_requests(50, 10.0, LengthDist(64, 0.5, 8, 0.5), seed=4) \
        == poisson_requests(50, 10.0, LengthDist(64, 0.5, 8, 0.5), seed=4)
    cost = _cost()
    reqs = poisson_requests(200, 100.0, LengthDist(64, 0.5, 8, 0.5), seed=4)
    knobs = ServingKnobs(max_batch=8, prefill_chunk=32)
    a = simulate_serving(reqs, cost, knobs)
    b = simulate_serving(reqs, cost, knobs)
    assert a.metrics() == b.metrics()
    assert [(s.t_first, s.t_done) for s in a.stats] \
        == [(s.t_first, s.t_done) for s in b.stats]


def test_conservation_every_policy():
    """Every request ends in exactly one terminal state under every
    (admission x eviction x chunk) combination, including tight pools."""
    cost = _cost(kv_capacity=_cost().kv_bytes(1, 130) * 4)
    reqs = poisson_requests(150, 500.0, LengthDist(100, 0.6, 12, 0.4),
                            seed=2)
    for admission in ("fcfs", "spf"):
        for eviction in ("reject", "evict-oldest", "evict-newest"):
            for chunk in (0, 64):
                res = simulate_serving(reqs, cost, ServingKnobs(
                    max_batch=8, admission=admission,
                    eviction=eviction, prefill_chunk=chunk))
                comp = [st for st in res.stats if st.completed]
                rej = [st for st in res.stats if st.rejected]
                assert len(comp) + len(rej) == len(reqs)
                assert not any(st.completed and st.rejected
                               for st in res.stats)
                assert all(math.isfinite(st.t_first) for st in comp)


def test_reject_policy_never_exceeds_capacity():
    cost = _cost(kv_capacity=_cost().kv_bytes(1, 130) * 3)
    reqs = poisson_requests(100, 500.0, LengthDist(100, 0.5, 12, 0.3),
                            seed=6)
    res = simulate_serving(reqs, cost, ServingKnobs(max_batch=16))
    assert res.max_kv_bytes <= cost.kv_capacity
    # a request that can never fit alone is rejected terminally
    big = reqs + [RequestSpec(999, 0.0, 100_000, 4)]
    res2 = simulate_serving(big, cost, ServingKnobs(max_batch=16))
    st = next(s for s in res2.stats if s.spec.rid == 999)
    assert st.rejected and not st.completed


def test_eviction_preempts_and_completes():
    """Evict policies admit optimistically, preempt on overflow, and the
    evicted requests still finish (re-prefilling prompt + generated)."""
    cost = _cost(kv_capacity=_cost().kv_bytes(1, 130) * 4)
    reqs = poisson_requests(300, 2000.0, LengthDist(100, 0.5, 16, 0.3),
                            seed=1)
    for pol in ("evict-oldest", "evict-newest"):
        res = simulate_serving(reqs, cost, ServingKnobs(
            max_batch=8, eviction=pol))
        m = res.metrics()
        assert m["n_evictions"] > 0
        assert m["completed"] + m["rejected"] == len(reqs)
        evicted_done = [st for st in res.stats
                        if st.n_evictions > 0 and st.completed]
        assert evicted_done, "no evicted request ever completed"


def test_chunked_prefill_reduces_tail_tpot():
    """A long prompt landing mid-decode stalls every decoding request for
    its whole prefill when unchunked; chunking bounds the stall."""
    cost = _cost()
    mix = [RequestSpec(i, 1e-6 * i, 50, 40) for i in range(6)] \
        + [RequestSpec(9, 0.01, 4000, 4)]
    un = simulate_serving(mix, cost, ServingKnobs(max_batch=8))
    ch = simulate_serving(mix, cost,
                          ServingKnobs(max_batch=8, prefill_chunk=128))
    assert ch.metrics()["p99_tpot_ms"] < un.metrics()["p99_tpot_ms"]


def test_spf_admission_beats_fcfs_on_backlog():
    """Shortest-prompt-first is SJF on a batch-1 backlog: provably
    minimal mean wait, so it must beat FCFS on a scrambled batch."""
    cost = _cost()
    back = [RequestSpec(i, 0.0, p, 1)
            for i, p in enumerate([900, 30, 500, 60, 200, 40])]
    mean_wait = {}
    for adm in ("fcfs", "spf"):
        res = simulate_serving(back, cost,
                               ServingKnobs(max_batch=1, admission=adm))
        mean_wait[adm] = sum(st.wait for st in res.done()) / len(back)
        order = sorted(res.done(), key=lambda st: st.t_done)
        if adm == "spf":
            prompts = [st.spec.prompt_tokens for st in order]
            assert prompts == sorted(prompts)
    assert mean_wait["spf"] < mean_wait["fcfs"]


def test_trace_roundtrip_and_trace_driven_run(tmp_path):
    reqs = poisson_requests(20, 5.0, LengthDist(64, 0.5, 8, 0.5), seed=8)
    path = tmp_path / "trace.jsonl"
    path.write_text("\n".join(json.dumps({
        "rid": r.rid, "t_arrival": r.t_arrival,
        "prompt_tokens": r.prompt_tokens, "out_tokens": r.out_tokens})
        for r in reqs))
    loaded = load_trace_jsonl(path)
    assert loaded == reqs
    cost = _cost()
    a = simulate_serving(reqs, cost, ServingKnobs(max_batch=4))
    b = simulate_serving(loaded, cost, ServingKnobs(max_batch=4))
    assert a.metrics() == b.metrics()


def test_trace_driven_hand_case():
    """Two-request hand-checkable timeline at batch 1."""
    cost = SyntheticCostModel(prefill_t0=0.0, prefill_per_token=1e-3,
                              decode_t0=1e-2, decode_per_seq=0.0,
                              bytes_per_token=0.0, bytes_per_request=0.0)
    reqs = requests_from_trace([
        {"t_arrival": 0.0, "prompt_tokens": 10, "out_tokens": 3},
        {"t_arrival": 0.005, "prompt_tokens": 20, "out_tokens": 1},
    ])
    res = simulate_serving(reqs, cost, ServingKnobs(max_batch=1))
    st0, st1 = res.stats
    # r0: prefill 10ms -> first token at 10ms, +2 decode steps of 10ms
    assert st0.t_first == pytest.approx(0.010)
    assert st0.t_done == pytest.approx(0.030)
    # r1 admitted at r0's completion: prefill 20ms -> done at 50ms
    assert st1.t_first == pytest.approx(0.050)
    assert st1.t_done == pytest.approx(0.050)
    assert st1.wait == pytest.approx(0.030 - 0.005)


def test_knobs_validation_and_labels():
    with pytest.raises(ValueError):
        ServingKnobs(max_batch=0)
    with pytest.raises(ValueError):
        ServingKnobs(admission="lifo")
    with pytest.raises(ValueError):
        ServingKnobs(eviction="drop")
    assert ServingKnobs(max_batch=32).label == "fcfs_b32"
    assert ServingKnobs(max_batch=8, admission="spf", prefill_chunk=256,
                        eviction="evict-oldest").label \
        == "spf_b8_chunk256_evict-oldest"


# ----------------------------------------------------------- cost-model seams
def test_stream_time_residency_switch():
    levels = node_kv_levels()
    l2, hbm = levels
    assert stream_time(levels, l2.capacity / 2) \
        == pytest.approx(l2.capacity / 2 / l2.read_bw)
    spill = l2.capacity * 4
    assert stream_time(levels, spill) == pytest.approx(spill / hbm.read_bw)
    # beyond HBM there is nowhere further to miss to: outermost backstop
    huge = hbm.capacity * 2
    assert stream_time(levels, huge) == pytest.approx(huge / hbm.read_bw)
    assert stream_time(levels, 0.0) == 0.0
    assert stream_time(levels, spill, write=True) \
        == pytest.approx(spill / hbm.write_bw)


def test_node_kv_levels_a64fx_aggregates():
    l2, hbm = node_kv_levels()
    assert (l2.name, hbm.name) == ("l2", "hbm2")
    assert l2.capacity == 4 * 8 * 2**20 and hbm.capacity == 4 * 8 * 2**30
    assert l2.read_bw == 4 * A64FX_NODE.shared_read_bw["l2"]
    assert hbm.read_bw == 4 * A64FX_NODE.shared_read_bw["hbm2"]


def test_zoo_cost_model_interpolation():
    cm = ZooCostModel(arch="x", prefill_per_token=2e-6,
                      decode_grid=((1, 1e-4), (4, 2e-4), (16, 5e-4)),
                      bytes_per_token=0.0)
    assert cm.prefill_time(100) == pytest.approx(2e-4)
    for b, t in cm.decode_grid:                  # exact at grid points
        assert cm.decode_compute_time(b) == pytest.approx(t)
    assert cm.decode_compute_time(2) == pytest.approx(
        1e-4 + (2e-4 - 1e-4) * (2 - 1) / (4 - 1))
    assert cm.decode_compute_time(32) == pytest.approx(
        5e-4 + (5e-4 - 2e-4) / 12 * 16)          # last-slope extrapolation
    ts = [cm.decode_compute_time(b) for b in range(1, 40)]
    assert ts == sorted(ts)


def test_cost_model_kv_bytes_affine():
    cm = _cost()
    assert cm.kv_bytes(3, 100) == pytest.approx(3 * 5e6 + 100 * 1e6)
    # decode step pays the max of compute and KV streaming
    kv = 64 * 2**20                               # spills the 32 MiB L2
    hbm = cm.levels[-1]
    assert cm.decode_step_time(1, kv) == pytest.approx(
        max(cm.decode_compute_time(1), kv / hbm.read_bw))


def test_traffic_table_fallback():
    assert traffic_for("chatglm3-6b").prompt_mean == 256
    assert traffic_for("no-such-model") == traffic_for("another-unknown")


def test_pareto_front_non_domination():
    pts = [(1.0, 5.0), (2.0, 2.0), (3.0, 3.0), (5.0, 1.0), (1.0, 5.0)]
    front = pareto_front(pts)
    assert 2 not in front                        # dominated by (2, 2)
    for a in front:
        assert not any(pts[b][0] <= pts[a][0] and pts[b][1] <= pts[a][1]
                       and pts[b] != pts[a] for b in range(len(pts)))


# ------------------------------------------ phase-cache aliasing (satellite 6)
def test_serving_cost_key_phase_distinct():
    """ZOO_PREFILL and ZOO_DECODE have IDENTICAL reduced shapes (seq 256,
    batch 2) — only the phase in the key separates their cost cells."""
    from repro.configs.shapes import ZOO_DECODE, ZOO_PREFILL
    shape = dataclasses.replace(ZOO_PREFILL, name="alias", kind="prefill")
    k_pre = zoo.serving_cost_key("chatglm3-6b", "prefill", shape, 48,
                                 "f32", "float32")
    k_dec = zoo.serving_cost_key("chatglm3-6b", "decode", shape, 48,
                                 "f32", "float32")
    assert k_pre != k_dec
    assert (ZOO_PREFILL.seq_len, ZOO_PREFILL.global_batch) \
        == (ZOO_DECODE.seq_len, ZOO_DECODE.global_batch)


@pytest.mark.parametrize("field", ["platform", "device_kind", "jax"])
def test_cache_keys_name_the_compile_target(field, monkeypatch):
    """A trace compiled for one target (backend, chip kind, JAX version) is
    never read as another's: keys differing only in target differ."""
    shape = zoo.ZOO_SHAPES["decode"]

    def keys():
        return (zoo.hlo_cache_key("chatglm3-6b", "decode", shape, "float32"),
                zoo.serving_cost_key("chatglm3-6b", "decode", shape, 48,
                                     "f32", "float32"))

    here = keys()
    assert keys() == here
    there = dict(zoo.compile_target(), **{field: "elsewhere"})
    monkeypatch.setattr(zoo, "compile_target", lambda: there)
    elsewhere = keys()
    assert elsewhere[0] != here[0] and elsewhere[1] != here[1]


def test_hlo_cache_key_and_path_phase_distinct(tmp_path):
    from repro.configs.shapes import ZOO_PREFILL
    shape = dataclasses.replace(ZOO_PREFILL, name="alias")
    assert zoo.hlo_cache_key("chatglm3-6b", "prefill", shape, "float32") \
        != zoo.hlo_cache_key("chatglm3-6b", "decode", shape, "float32")
    p = zoo.hlo_cache_path(tmp_path, "chatglm3-6b", "prefill", shape,
                           "float32")
    d = zoo.hlo_cache_path(tmp_path, "chatglm3-6b", "decode", shape,
                           "float32")
    assert p != d


def test_program_cache_phase_keyed(monkeypatch, tmp_path):
    """The in-process trace memo and the disk HLO cache must both key on
    phase: equal reduced shapes, different phases -> different programs
    and different cache files (regression for prefill/decode aliasing)."""
    from repro.configs.shapes import ZOO_DECODE, ZOO_PREFILL
    texts = {
        "prefill": """
HloModule pre, num_partitions=1

ENTRY %main (p0: f32[64,64]) -> f32[64,64] {
  %p0 = f32[64,64] parameter(0)
  ROOT %dot = f32[64,64] dot(%p0, %p0), lhs_contracting_dims={1}
}
""",
        "decode": """
HloModule dec, num_partitions=1

ENTRY %main (p0: f32[64,64]) -> f32[64,64] {
  %p0 = f32[64,64] parameter(0)
  %dot = f32[64,64] dot(%p0, %p0), lhs_contracting_dims={1}
  ROOT %e = f32[64,64] exponential(%dot)
}
""",
    }
    monkeypatch.setattr(zoo, "_phase_hlo",
                        lambda arch, phase, shape, dtype: texts[phase])
    zoo.clear_trace_caches()
    try:
        pre = zoo.trace_phase("chatglm3-6b", "prefill", ZOO_PREFILL,
                              hlo_cache_dir=tmp_path)
        dec = zoo.trace_phase("chatglm3-6b", "decode", ZOO_DECODE,
                              hlo_cache_dir=tmp_path)
        assert len(pre.ops) != len(dec.ops)
        files = sorted(f.name for f in tmp_path.glob("*.hlo.txt"))
        assert len(files) == 2 and files[0] != files[1]
    finally:
        zoo.clear_trace_caches()


def test_vpu_opcode_table_prices_decode_path():
    """The per-opcode VPU latency table must reach the node engine the
    serving cost cells use: a decode-style elementwise stream of
    `minimum` ops (A64FX factor 2.0) costs more than the identical
    stream of plain adds — without the table both collapse to one
    t_est (the degeneracy the kernel suite fixed)."""
    from repro.core.node import compile_node, schedule_node

    def prog(opcode):
        nelems = 1e6
        ops = [OpStat(f"op{i}", opcode, "elementwise", "f32",
                      flops=nelems, bytes_accessed=1e4, read_bytes=1e4,
                      vpu_by_opcode={opcode: nelems})
               for i in range(8)]
        return Program(ops=ops, entry="e", n_partitions=1)

    ts = {}
    for opcode in ("add", "minimum"):
        nc = compile_node(prog(opcode), A64FX_CORE, compute_dtype="f32")
        ts[opcode] = schedule_node(nc, A64FX_CORE, 1,
                                   topology=A64FX_NODE).t_est
    assert ts["minimum"] > ts["add"] * 1.5


# ----------------------------------------- kvcache differential (satellite 1)
@pytest.mark.parametrize("arch", sorted(__import__("repro.configs",
                                                   fromlist=["ARCHS"]).ARCHS))
def test_cache_bytes_matches_abstract_leaves(arch):
    """cache_bytes must equal the summed bytes of cache_abstract's ACTUAL
    pytree leaves for every architecture family, dtype and (batch,
    max_seq) cell — the serving layer's KV sizing cannot drift from the
    real cache shapes."""
    import jax
    import jax.numpy as jnp

    from repro.configs import ARCHS, reduced_config
    from repro.models.lm import build_model
    from repro.serve.kvcache import cache_abstract, cache_bytes
    model = build_model(reduced_config(ARCHS[arch]))
    for dtype in (jnp.bfloat16, jnp.float32):
        for batch, max_seq in ((1, 8), (2, 16), (4, 64)):
            tree = cache_abstract(model, batch, max_seq, dtype)
            leaf_bytes = sum(x.size * x.dtype.itemsize
                             for x in jax.tree.leaves(tree))
            assert cache_bytes(model, batch, max_seq, dtype) == leaf_bytes


@pytest.mark.parametrize("arch", sorted(__import__("repro.configs",
                                                   fromlist=["ARCHS"]).ARCHS))
def test_kv_token_bytes_affine_exact(arch):
    """The serving layer's affine decomposition reproduces cache_bytes
    exactly at every sequence length (SSM: zero bytes/token)."""
    import jax.numpy as jnp

    from repro.configs import ARCHS, reduced_config
    from repro.models.lm import build_model
    from repro.serve.kvcache import cache_bytes, kv_token_bytes
    cfg = reduced_config(ARCHS[arch])
    model = build_model(cfg)
    per_tok, per_req = kv_token_bytes(model, jnp.bfloat16)
    for seq in (1, 7, 64, 333):
        assert per_req + per_tok * seq \
            == pytest.approx(cache_bytes(model, 1, seq, jnp.bfloat16))
    if cfg.family == "ssm":
        assert per_tok == 0.0 and per_req > 0
    else:
        assert per_tok > 0


# ------------------------------------------- ServeEngine golden (satellite 2)
@pytest.mark.slow
def test_serve_engine_fixed_seed_token_pin():
    """Sampled generation is a pure function of the seed: two engines
    built identically emit identical token sequences, and a different
    seed diverges (the RNG is actually consulted)."""
    import jax

    from repro.configs import ARCHS, reduced_config
    from repro.models.lm import build_model
    from repro.serve.engine import ServeEngine
    cfg = reduced_config(ARCHS["qwen1.5-32b"])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [[3, 1, 4, 1, 5], [2, 7]]

    def run(seed):
        eng = ServeEngine(model, params, max_seq=32, temperature=0.8,
                          seed=seed)
        return eng.generate(prompts, max_new_tokens=6)

    assert run(0) == run(0)
    assert run(0) != run(1)


@pytest.mark.slow
def test_pad_cache_pads_only_kvseq_axis():
    """Regression for the axis-scan bug: with n_layers == prompt length
    the old heuristic padded the LAYERS axis.  The padded cache must
    keep every non-kvseq dimension and grow kvseq to max_seq."""
    import jax

    from repro.configs import ARCHS, reduced_config
    from repro.models.lm import build_model
    from repro.serve.engine import ServeEngine
    cfg = reduced_config(ARCHS["qwen1.5-32b"])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, max_seq=24)
    prompt = list(range(1, cfg.n_layers + 1))     # len(prompt) == n_layers
    _, cache = eng._prefill_one(prompt, {})
    specs = model.cache_specs(1, 24)

    def check(x, p):
        if "kvseq" in p.axes and p.shape[p.axes.index("kvseq")] == 24:
            assert x.shape[p.axes.index("kvseq")] == 24
        for ax, name in enumerate(p.axes):
            if name != "kvseq":
                assert x.shape[ax] == p.shape[ax] or name == "batch"

    jax.tree.map(check, cache, specs)


@pytest.mark.slow
def test_generate_invariant_under_max_seq():
    """Greedy generation must not depend on the cache's padded length
    (the _pad_cache length-invariance property), including the
    adversarial prompt length == n_layers case."""
    import jax

    from repro.configs import ARCHS, reduced_config
    from repro.models.lm import build_model
    from repro.serve.engine import ServeEngine
    cfg = reduced_config(ARCHS["qwen1.5-32b"])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = list(range(1, cfg.n_layers + 1))

    def run(max_seq):
        eng = ServeEngine(model, params, max_seq=max_seq)
        return eng.generate([prompt], max_new_tokens=5)

    assert run(16) == run(24)


# --------------------------------------- committed artifact (satellite 3)
def test_bench_serving_artifact():
    """The committed BENCH_serving.json: schema, percentile ordering,
    finite/positive SLO fields, and Pareto non-domination per model —
    mirroring the test_dse.py committed-artifact pattern."""
    d = json.loads(BENCH_JSON.read_text())
    assert d["schema"] == 1
    assert len(d["models"]) >= 4
    assert len(d["policies"]) >= 3
    labels = {p["label"] for p in d["policies"]}
    for arch, row in d["models"].items():
        pols = row["policies"]
        assert set(pols) == labels
        for m in pols.values():
            assert m["p50_ttft_ms"] <= m["p99_ttft_ms"] + 1e-9
            assert m["p50_tpot_ms"] <= m["p99_tpot_ms"] + 1e-9
            for k in ("p50_ttft_ms", "p99_ttft_ms", "tokens_per_s"):
                assert math.isfinite(m[k]) and m[k] > 0
            assert m["little_law_gap"] < 1e-6
            assert m["completed"] + m["rejected"] == d["arrival"]["n_requests"]
        front = row["pareto"]
        assert front and set(front) <= labels
        pts = {lb: (pols[lb]["p99_ttft_ms"], -pols[lb]["tokens_per_s"])
               for lb in pols}
        for a in front:
            assert not any(
                pts[b][0] <= pts[a][0] and pts[b][1] <= pts[a][1]
                and pts[b] != pts[a] for b in pols), \
                f"{arch}: {a} dominated but on front"
        assert row["bytes_per_token"] >= 0
    assert d["wall_s"] > 0


# ------------------------------------------------------ zoo-backed smoke
@pytest.mark.slow
def test_build_zoo_cost_model_and_simulate(tmp_path):
    """End-to-end: trace one arch through the node engine, price a small
    Poisson run, and check the disk cost cells are phase-distinct files
    that make the rebuild a pure cache read."""
    from repro.core.serving import build_zoo_cost_model
    cm = build_zoo_cost_model("chatglm3-6b", batch_grid=(1, 4),
                              hlo_cache_dir=tmp_path / "hlo",
                              cost_cache_dir=tmp_path / "cost")
    assert cm.prefill_per_token > 0
    assert all(t > 0 for _, t in cm.decode_grid)
    assert cm.bytes_per_token > 0 and cm.kv_capacity == 32 * 2**30
    cells = sorted(f.name for f in (tmp_path / "cost").glob("*.json"))
    assert len(cells) == 3                   # prefill + 2 decode batches
    assert any("serve_prefill" in f for f in cells)
    assert any("serve_decode" in f for f in cells)
    cm2 = build_zoo_cost_model("chatglm3-6b", batch_grid=(1, 4),
                               hlo_cache_dir=tmp_path / "hlo",
                               cost_cache_dir=tmp_path / "cost")
    assert cm2.decode_grid == cm.decode_grid
    reqs = poisson_requests(40, 100.0, traffic_for("chatglm3-6b"), seed=0)
    res = simulate_serving(reqs, cm, ServingKnobs(max_batch=8))
    m = res.metrics()
    assert m["completed"] == 40 and m["tokens_per_s"] > 0
