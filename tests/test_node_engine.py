"""Test harness for the multi-core node engine (ISSUE 4).

Four layers pin the node layer to the single-core kernels and to the
paper's relative-evaluation goal:

* **differential** — ``engine="node"`` with one core and a degenerate
  topology is BIT-identical to ``schedule_arrays`` (random DAGs x random
  O3 knobs, the golden HLO fixtures, and — slow-marked — every compiled
  kernel-suite program), extending ``tests/test_compiled_schedule.py``'s
  sweep pattern;
* **property** (via ``tests/_hypothesis_compat``) — node time is
  monotonically non-increasing in core count for the shard partition,
  per-core effective bandwidth is monotonically non-increasing in the
  number of active sharers, and the node makespan never beats the
  dataflow critical path (nor escapes the zero-contention/serial
  sandwich);
* **accuracy regression** — Kendall-tau rank correlation between
  ``measured_us`` and ``t_est_schedule_us`` over the pinned
  ``BENCH_kernel_suite.json`` kernels, with a floor so calibration/model
  changes that scramble the kernel ordering fail CI (the paper's goal is
  *relative* evaluation);
* **non-degeneracy** — per-OpClass VPU latencies must separate
  add/div/sqrt/atan2 estimates on the A64FX and CPU_HOST parameter
  files (the BENCH collapse of add/div/min to one t_est).
"""
import json
import random
from pathlib import Path

import pytest

from repro.core import calibrate
from repro.core.hlo import OpStat, Program, parse_program
from repro.core.hwspec import (A64FX_CORE, A64FX_NODE, CPU_HOST,
                               NodeTopology, TPU_V5E)
from repro.core.node import (compile_node, effective_bandwidth,
                             schedule_node, simulate_node)
from repro.core.schedule import schedule_program, schedule_reference
from repro.core.simulate import simulate
from tests._hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from tests.test_compiled_schedule import random_knobs, random_program
from tests.test_schedule_engine import CHAIN_HLO, INDEP_HLO

BENCH_JSON = Path(__file__).resolve().parents[1] / "BENCH_kernel_suite.json"
KENDALL_TAU_FLOOR = 0.5


def mem_bound_program(n_ops: int = 64, nbytes: float = 64 * 2**20) -> Program:
    """Independent DRAM-resident streaming ops: the Stream-Triad-like
    shape where bandwidth contention is the whole story."""
    ops = [OpStat(f"cp{i}", "copy", "data", "f64", bytes_accessed=nbytes,
                  read_bytes=0.75 * nbytes, write_bytes=0.25 * nbytes)
           for i in range(n_ops)]
    return Program(ops=ops, entry="e", n_partitions=1)


# ------------------------------------------------------------- differential
def _assert_node_matches_single(prog, hw):
    """n_cores=1 + degenerate topology: every partition mode replays
    schedule_arrays' float ops, so the results are bit-identical."""
    ref = schedule_program(prog, hw)
    topo = NodeTopology.degenerate(1)
    for part in ("round-robin", "graph", "shard"):
        nr = simulate_node(prog, hw, 1, topology=topo, partition=part)
        assert nr.t_est == ref.t_est, part                 # bit-identical
        assert nr.schedule.port_busy == ref.port_busy, part
        assert nr.schedule.stall_by_reason == ref.stall_by_reason, part
        assert nr.schedule.t_serial == ref.t_serial, part
        assert nr.t_zero_contention == ref.t_est, part
        assert nr.iterations == 1, part


def test_differential_one_core_random_dags_x_random_knobs():
    """Seeded sweep (the test_compiled_schedule pattern): 40 random
    (program, knob) pairs, node engine vs the single-core fast path."""
    rng = random.Random(4321)
    for _ in range(40):
        prog = random_program(rng, rng.randint(0, 48))
        _assert_node_matches_single(prog, random_knobs(rng))


def test_differential_one_core_golden_fixtures():
    for hlo in (CHAIN_HLO, INDEP_HLO):
        prog = parse_program(hlo)
        for hw in (TPU_V5E, A64FX_CORE, CPU_HOST):
            _assert_node_matches_single(prog, hw)


def test_one_core_under_own_topology_matches_when_uncontended():
    """A64FX_CORE carries the real node topology; a single core never
    saturates a shared cap, so even the non-degenerate topology keeps
    the 1-core node path bit-identical to schedule_arrays."""
    rng = random.Random(99)
    for _ in range(10):
        prog = random_program(rng, rng.randint(1, 40))
        ref = schedule_program(prog, A64FX_CORE)
        nr = simulate_node(prog, A64FX_CORE, 1, partition="round-robin")
        assert nr.t_est == ref.t_est
        assert nr.schedule.stall_by_reason == ref.stall_by_reason


@pytest.mark.slow
def test_differential_one_core_on_kernel_suite_programs():
    """Acceptance: the 1-core node path is bit-identical to the
    single-core scheduler on every compiled kernel-suite program."""
    import jax

    from repro.configs.a64fx_kernelsuite import KERNELS
    hw = CPU_HOST
    with jax.enable_x64(True):
        for k in KERNELS:
            x1, x2, y0 = calibrate._kernel_inputs(k, k.n)
            f = calibrate._jit_kernel(k.name)
            prog = parse_program(f.lower(x1, x2, y0).compile().as_text())
            ref = schedule_reference(prog, hw, compute_dtype="f64")
            for part in ("round-robin", "shard"):
                nr = simulate_node(prog, hw, 1,
                                   topology=NodeTopology.degenerate(1),
                                   partition=part, compute_dtype="f64")
                assert nr.t_est == ref.t_est, (k.name, part)
                assert nr.schedule.port_busy == ref.port_busy


# ----------------------------------------------------------------- property
def test_effective_bandwidth_monotone_in_sharers():
    """Per-core effective bandwidth never increases as more cores share
    a level, and never exceeds the single-core draw or the aggregate."""
    prev = None
    for n_active in range(1, 49):
        bw = effective_bandwidth(64e9, 256e9, n_active)
        assert bw <= 64e9 + 1e-9
        assert bw * n_active <= 256e9 * (1 + 1e-9)
        if prev is not None:
            assert bw <= prev + 1e-9
        prev = bw
    # no shared cap -> the per-core path, independent of sharers
    assert effective_bandwidth(64e9, None, 48) == 64e9


def test_node_time_monotone_in_core_count_shard():
    """Shard partition: more cores never hurt (each core gets 1/k of the
    work; contention can flatten but never invert the scaling)."""
    rng = random.Random(31)
    for _ in range(10):
        prog = random_program(rng, rng.randint(1, 50))
        prev = None
        for k in (1, 2, 4, 8, 16, 48):
            t = simulate_node(prog, A64FX_CORE, k, partition="shard",
                              compute_dtype="f64").t_est
            if prev is not None:
                assert t <= prev * (1 + 1e-9), k
            prev = t


def test_node_time_monotone_dependency_free_round_robin():
    """Dependency-free uniform ops, contention-free topology: round-robin
    across more cores is never slower."""
    ops = [OpStat(f"e{i}", "add", "elementwise", "f32", flops=1e9,
                  bytes_accessed=8.0) for i in range(48)]
    prog = Program(ops=ops, entry="e", n_partitions=1)
    prev = None
    for k in (1, 2, 4, 8, 16, 48):
        t = simulate_node(prog, TPU_V5E, k,
                          topology=NodeTopology.degenerate(48),
                          partition="round-robin").t_est
        if prev is not None:
            assert t <= prev * (1 + 1e-9), k
        prev = t


def test_node_never_beats_critical_path_and_sandwich():
    """t_dataflow <= t_est <= t_serial, and the contended estimate never
    undercuts the zero-contention bound, for every partition mode."""
    rng = random.Random(17)
    for _ in range(15):
        prog = random_program(rng, rng.randint(1, 50))
        base = schedule_program(prog, A64FX_CORE, compute_dtype="f64")
        for part in ("round-robin", "graph", "shard"):
            for k in (1, 5, 12, 48):
                nr = simulate_node(prog, A64FX_CORE, k, partition=part,
                                   compute_dtype="f64")
                s = nr.schedule
                assert nr.t_est >= s.t_dataflow * (1 - 1e-9), (part, k)
                assert nr.t_est <= s.t_serial * (1 + 1e-9), (part, k)
                assert nr.t_est >= nr.t_zero_contention * (1 - 1e-9)
                if part != "shard":
                    # op partitions never beat the single-core dataflow
                    # bound (sharding legitimately splits op work)
                    assert nr.t_est >= base.t_dataflow * (1 - 1e-9)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_property_node_invariants_hypothesis(seed):
    rng = random.Random(seed)
    prog = random_program(rng, rng.randint(0, 40))
    _assert_node_matches_single(prog, random_knobs(rng))
    k = rng.choice([2, 7, 12, 48])
    part = rng.choice(["round-robin", "graph", "shard"])
    nr = simulate_node(prog, A64FX_CORE, k, partition=part,
                       compute_dtype="f64")
    assert nr.t_est >= nr.schedule.t_dataflow * (1 - 1e-9)
    assert nr.t_est <= nr.schedule.t_serial * (1 + 1e-9)
    assert nr.t_est >= nr.t_zero_contention * (1 - 1e-9)


# ------------------------------------------------------ contention behaviour
def test_contention_strictly_between_bounds_on_mem_bound_program():
    """Acceptance shape: 48-core estimates sit STRICTLY between the
    zero-contention bound and the single-core time on a memory-bound
    program, for every partition mode."""
    prog = mem_bound_program()
    t1 = simulate_node(prog, A64FX_CORE, 1, partition="shard",
                       compute_dtype="f64").t_est
    for part in ("shard", "round-robin", "graph"):
        nr = simulate_node(prog, A64FX_CORE, 48, partition=part,
                           compute_dtype="f64")
        assert nr.t_zero_contention < nr.t_est < t1, part
        # the CMG's HBM2 really is saturated: >4 active streams
        assert nr.per_cmg[0].n_active["hbm2"] > 4.0, part
        # effective per-core bandwidth is the aggregate share, below the
        # single-core draw limit
        assert nr.per_cmg[0].eff_read_bw["hbm2"] < 64e9, part


def test_contention_free_when_compute_bound():
    """A compute-dominated program leaves the shared levels idle most of
    the time: the fixpoint keeps n_active ~1 and the zero-contention
    bound is tight."""
    prog = parse_program(CHAIN_HLO)
    nr = simulate_node(prog, A64FX_CORE, 48, partition="shard",
                       compute_dtype="f64")
    assert nr.t_est == pytest.approx(nr.t_zero_contention, rel=1e-6)


def test_cmg_saturation_plateau():
    """12 cores on ONE CMG (compact pinning) saturate its 256 GB/s: the
    12-core time is ~4x the 4-core time's ideal scaling continuation
    (4 cores x 64 GB/s already saturate the stack), while 48 cores reach
    4 stacks."""
    prog = mem_bound_program()
    t = {k: simulate_node(prog, A64FX_CORE, k, partition="shard",
                          compute_dtype="f64").t_est
         for k in (1, 4, 12, 48)}
    # 1->4 cores: near-linear (per-core 64 GB/s draws sum to the stack)
    assert t[4] == pytest.approx(t[1] / 4, rel=0.05)
    # 4->12 cores on the same stack: little gain (aggregate is capped)
    assert t[12] > t[4] * 0.6
    # 48 cores = 4 stacks: ~4x the 12-core (one-stack) time
    assert t[48] == pytest.approx(t[12] / 4, rel=0.15)


def test_ring_latency_charged_on_cross_cmg_edges():
    """A dependence chain split across CMGs pays the ring hop; the same
    chain on one CMG does not."""
    ops = [OpStat(f"e{i}", "add", "elementwise", "f32", flops=1e6,
                  bytes_accessed=8.0, deps=[i - 1] if i else [],
                  dep_bytes=[8.0] if i else []) for i in range(8)]
    prog = Program(ops=ops, entry="e", n_partitions=1)
    nc = compile_node(prog, A64FX_CORE)
    import numpy as np
    both = schedule_node(nc, A64FX_CORE, 24, core_of=np.array(
        [0, 12, 0, 12, 0, 12, 0, 12]))          # cores 0/12 = CMGs 0/1
    one = schedule_node(nc, A64FX_CORE, 24, core_of=np.array(
        [0, 1, 0, 1, 0, 1, 0, 1]))              # same CMG
    assert both.t_est > one.t_est
    assert both.t_est - one.t_est == pytest.approx(
        7 * A64FX_NODE.ring_latency_s, rel=1e-6)


# --------------------------------------------------------------- simulate()
def test_simulate_node_engine_api_and_report():
    prog_text = INDEP_HLO
    rep = simulate(prog_text, hw=A64FX_CORE, engine="node", n_cores=48,
                   node_partition="shard", compute_dtype="f64")
    assert rep.node is not None
    assert rep.t_est == rep.node.t_est
    assert rep.engine_mode == "node"
    assert "node engine (48 cores" in rep.pa
    assert "cmg0" in rep.pa and "cmg3" in rep.pa
    assert "zero-contention" in rep.pa
    d = json.loads(rep.to_json())
    assert d["node"]["n_cores"] == 48
    assert d["node"]["t_est"] == rep.node.t_est
    assert len(d["node"]["per_cmg"]) == 4
    # non-node modes keep the old shape
    rep_occ = simulate(prog_text, hw=A64FX_CORE, compute_dtype="f64")
    assert rep_occ.node is None
    assert "node engine" not in rep_occ.pa


def test_simulate_rejects_bad_node_args():
    with pytest.raises(ValueError):
        simulate(INDEP_HLO, hw=A64FX_CORE, engine="node", n_cores=49)
    with pytest.raises(ValueError):
        simulate(INDEP_HLO, hw=A64FX_CORE, engine="node", n_cores=2,
                 node_partition="zigzag")


@pytest.mark.slow
def test_node_acceptance_on_compiled_kernel_suite():
    """Acceptance: on real compiled suite kernels under the A64FX node
    topology, 1-core node == single-core schedule bit-for-bit (degenerate
    topo) and the 48-core estimate is strictly between the single-core
    and zero-contention bounds."""
    import jax

    from repro.configs.a64fx_kernelsuite import KERNELS_BY_NAME
    with jax.enable_x64(True):
        for name in ("add", "mul", "exp"):
            k = KERNELS_BY_NAME[name]
            n = k.n * calibrate.SIZE_SCALE       # DRAM-resident
            x1, x2, y0 = calibrate._kernel_inputs(k, n)
            f = calibrate._jit_kernel(name)
            prog = parse_program(f.lower(x1, x2, y0).compile().as_text())
            ref = schedule_program(prog, A64FX_CORE, compute_dtype="f64")
            nr1 = simulate_node(prog, A64FX_CORE, 1,
                                topology=NodeTopology.degenerate(1),
                                partition="shard", compute_dtype="f64")
            assert nr1.t_est == ref.t_est, name
            nr48 = simulate_node(prog, A64FX_CORE, 48, partition="shard",
                                 compute_dtype="f64")
            assert nr48.t_zero_contention < nr48.t_est < nr1.t_est, name


# ------------------------------------------------------- accuracy (Kendall)
# one tau-b implementation serves the whole repo (self-checked in
# tests/test_zoo.py alongside the model-zoo rank-stability floor)
from repro.core.zoo import kendall_tau as kendall_tau_b  # noqa: E402


def test_kendall_tau_rank_floor_on_bench_artifact():
    """The paper's goal is accuracy sufficient for RELATIVE evaluation:
    the schedule engine must rank the suite kernels like the test chip
    does.  Pinned floor on Kendall-tau over BENCH_kernel_suite.json so a
    calibration/model change that scrambles the ordering fails CI."""
    if not BENCH_JSON.exists():
        pytest.skip("BENCH_kernel_suite.json not generated")
    data = json.loads(BENCH_JSON.read_text())
    kernels = data["kernels"]
    assert len(kernels) >= 5, "bench artifact too small to rank"
    measured = [v["measured_us"] for v in kernels.values()]
    estimated = [v["t_est_schedule_us"] for v in kernels.values()]
    tau = kendall_tau_b(measured, estimated)
    assert tau >= KENDALL_TAU_FLOOR, (
        f"Kendall-tau {tau:.3f} below the {KENDALL_TAU_FLOOR} floor: the "
        f"model no longer ranks kernels like the measurements do")




# ------------------------------------------- per-OpClass VPU non-degeneracy
def _suite_like_op(name, opclass, n, trans_opcode=None, vpu_opcode=None):
    kw = {}
    if trans_opcode:
        kw = {"transcendentals": float(n),
              "trans_by_opcode": {trans_opcode: float(n)}}
    elif vpu_opcode:
        kw = {"vpu_by_opcode": {vpu_opcode: float(n)}}
    return OpStat(name, name, opclass, "f64", flops=float(n),
                  bytes_accessed=24.0 * n, read_bytes=16.0 * n,
                  write_bytes=8.0 * n, **kw)


@pytest.mark.parametrize("hw", [A64FX_CORE, CPU_HOST],
                         ids=["a64fx_core", "cpu_host"])
def test_opclass_estimates_not_degenerate(hw):
    """Fix for the BENCH collapse (add/div/min at one identical t_est):
    the per-opcode VPU latency tables must separate the op classes."""
    n = 2048 * 8                       # Table-1 scale: cache-resident
    kernels = {
        "add": _suite_like_op("add", "elementwise", n, vpu_opcode="add"),
        "min": _suite_like_op("min", "elementwise", n,
                              vpu_opcode="minimum"),
        "div": _suite_like_op("div", "transcendental", n,
                              trans_opcode="divide"),
        "sqrt": _suite_like_op("sqrt", "transcendental", n,
                               trans_opcode="sqrt"),
        "exp": _suite_like_op("exp", "transcendental", n,
                              trans_opcode="exponential"),
        "atan2": _suite_like_op("atan2", "transcendental", n,
                                trans_opcode="atan2"),
    }
    t = {name: schedule_program(Program([op], "e", 1), hw,
                                compute_dtype="f64").t_est
         for name, op in kernels.items()}
    distinct = len(set(t.values()))
    assert distinct >= 4, t
    # the unpipelined/libm classes are strictly slower than streaming add
    assert t["div"] > t["add"]
    assert t["sqrt"] > t["add"]
    assert t["atan2"] > t["add"]
    # and the table separates them from each other
    assert t["div"] != t["atan2"]


def test_vpu_by_opcode_survives_fusion_and_is_neutral_without_factors():
    """The parser records elementwise opcode counts; a spec without
    factor entries costs them exactly as before (bit-for-bit)."""
    prog = parse_program(CHAIN_HLO)
    by_name = {o.name: o for o in prog.ops}
    assert by_name["neg"].vpu_by_opcode.get("negate") == 4096 * 4096
    # TPU_V5E has no opcode_factor entries: unchanged costing
    assert not TPU_V5E.opcode_factor
    r = schedule_program(prog, TPU_V5E)
    assert r.t_est > 0


# ----------------------------------------------------- sweep core-count axis
def test_sweep_o3_core_count_axis():
    """core_counts adds the node engine's core count to the sweep grid;
    the n_cores=1 rows are exactly the old single-core sweep."""
    rng = random.Random(5)
    programs = [random_program(rng, 30) for _ in range(2)]
    rows = [calibrate.KernelRow(f"p{i}", "synth", 1, measured_us=50.0,
                                simulated_us=50.0)
            for i in range(len(programs))]
    table = calibrate.AccuracyTable(rows, programs=programs)
    hw = A64FX_CORE
    kw = dict(windows=(4, 64), mem_widths=(1, 2), vpu_widths=(1,),
              queue_depths=(4,))
    single = calibrate.sweep_o3(table, hw, **kw)
    multi = calibrate.sweep_o3(table, hw, core_counts=(1, 12), **kw)
    assert {r["n_cores"] for r in multi.results} == {1, 12}
    assert len(multi.results) == 2 * len(single.results)
    key = lambda r: (r["inflight_window"], r["mem_issue_width"],   # noqa: E731
                     r["queue_depth"])
    ours = {key(r): r["mean_abs_diff_pct"] for r in multi.results
            if r["n_cores"] == 1}
    for r in single.results:
        assert ours[key(r)] == pytest.approx(r["mean_abs_diff_pct"],
                                             rel=1e-12)
    # best is picked among the smallest core count (measured data is
    # single-core)
    assert multi.best.inflight_window in (4, 64)


def test_node_perf_smoke_program_schedules_deterministically():
    from benchmarks.sched_throughput import NODE_CORES, synthetic_program
    prog = synthetic_program(n=300, seed=0)
    nc = compile_node(prog, A64FX_CORE, compute_dtype="f64")
    a = schedule_node(nc, A64FX_CORE, NODE_CORES, partition="round-robin")
    b = schedule_node(nc, A64FX_CORE, NODE_CORES, partition="round-robin")
    assert a.t_est == b.t_est
    assert a.iterations == b.iterations
    assert a.t_zero_contention <= a.t_est * (1 + 1e-9)
