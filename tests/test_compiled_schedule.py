"""Differential tests for the compiled array-form scheduling core (ISSUE 3).

The contract: the fast scalar kernel and the batched grid kernel both
replay the reference interpreter's float operations in the same order,
so ``t_est`` / ``port_busy`` / ``stall_by_reason`` are BIT-identical —
asserted here over random DAG programs x random O3 knobs (seeded
generator, plus hypothesis when it is installed), the canned golden
fixtures, and the sandwich invariant ``t_roofline <= t_est <= t_serial``
on the compiled path.
"""
import random
import time

import numpy as np
import pytest

from repro.core import calibrate
from repro.core.compiled import (O3Knobs, compile_program, schedule_arrays,
                                 schedule_batch)
from repro.core.cost import cost_program
from repro.core.hlo import OpStat, Program, parse_program
from repro.core.hwspec import A64FX_CORE, CPU_HOST, TPU_V5E
from repro.core.schedule import (CRITICAL_PATH_LIMIT, schedule_program,
                                 schedule_reference)
from tests._hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from tests.test_schedule_engine import CHAIN_HLO, INDEP_HLO

PORTS4 = ("mxu", "vpu", "mem", "ici")


def random_program(rng: random.Random, n: int) -> Program:
    """Random DAG with the op mix the cost model actually charges."""
    ops = []
    for i in range(n):
        k = min(i, rng.randint(0, 3))
        deps = sorted(rng.sample(range(i), k))
        cls = rng.choice(["elementwise", "data", "matmul", "reduce",
                          "transcendental", "unknown-class"])
        ops.append(OpStat(
            f"op{i}", "fusion", cls, "f32",
            flops=rng.uniform(1e3, 1e9),
            transcendentals=rng.uniform(0, 1e3),
            bytes_accessed=rng.uniform(1e3, 1e8),
            read_bytes=rng.uniform(1e3, 5e7),
            write_bytes=rng.uniform(0, 5e7),
            count=rng.choice([1.0, 1.0, 4.0]),
            deps=deps, dep_bytes=[rng.uniform(0, 1e6) for _ in deps]))
    return Program(ops=ops, entry="e", n_partitions=1)


def random_knobs(rng: random.Random):
    base = rng.choice([TPU_V5E, CPU_HOST, A64FX_CORE])
    return base.with_(
        inflight_window=rng.choice([1, 2, 7, 64, 1024]),
        issue_width={p: rng.randint(1, 4) for p in PORTS4},
        queue_depth={p: rng.randint(1, 32) for p in PORTS4})


def _assert_fast_matches_reference(prog, hw):
    ref = schedule_reference(prog, hw)
    fast = schedule_program(prog, hw)
    assert fast.t_est == ref.t_est                      # bit-identical
    assert fast.port_busy == ref.port_busy
    assert fast.stall_by_reason == ref.stall_by_reason
    assert fast.t_serial == ref.t_serial
    assert fast.t_dataflow == ref.t_dataflow
    assert fast.t_roofline == ref.t_roofline
    assert fast.n_edges == ref.n_edges
    assert fast.n_ops == ref.n_ops
    # sandwich invariant on the compiled path
    assert fast.t_roofline <= fast.t_est * (1 + 1e-9)
    assert fast.t_est <= fast.t_serial * (1 + 1e-9)
    assert fast.t_dataflow <= fast.t_est * (1 + 1e-9)
    return ref, fast


def test_differential_random_dags_x_random_knobs():
    """Seeded property sweep: 60 random (program, knob) pairs."""
    rng = random.Random(1234)
    for _ in range(60):
        prog = random_program(rng, rng.randint(0, 48))
        _assert_fast_matches_reference(prog, random_knobs(rng))


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_differential_hypothesis(seed):
    rng = random.Random(seed)
    prog = random_program(rng, rng.randint(0, 40))
    _assert_fast_matches_reference(prog, random_knobs(rng))


def test_differential_on_golden_hlo_fixtures():
    for hlo in (CHAIN_HLO, INDEP_HLO):
        prog = parse_program(hlo)
        for hw in (TPU_V5E, A64FX_CORE, CPU_HOST):
            _assert_fast_matches_reference(prog, hw)


def test_batched_kernel_matches_scalar_per_combo():
    rng = random.Random(7)
    prog = random_program(rng, 64)
    specs = [random_knobs(rng) for _ in range(25)]
    cp = compile_program(prog, TPU_V5E)
    got = schedule_batch(cp, O3Knobs.from_specs(specs))
    want = np.array([schedule_arrays(cp, hw)[0] for hw in specs])
    assert np.array_equal(got, want)


@pytest.mark.slow
def test_differential_on_kernel_suite_programs():
    """Acceptance: fast-path t_est equals the reference scheduler's to
    <=1e-9 relative error on every kernel-suite program (it is in fact
    bit-identical; compiled HLO of the real suite kernels, no
    measurement)."""
    import jax

    from repro.configs.a64fx_kernelsuite import KERNELS
    hw = CPU_HOST
    with jax.enable_x64(True):
        for k in KERNELS:
            x1, x2, y0 = calibrate._kernel_inputs(k, k.n)
            f = calibrate._jit_kernel(k.name)
            prog = parse_program(f.lower(x1, x2, y0).compile().as_text())
            ref = schedule_reference(prog, hw, compute_dtype="f64")
            fast = schedule_program(prog, hw, compute_dtype="f64")
            assert fast.t_est == pytest.approx(ref.t_est, rel=1e-9)
            assert fast.t_est == ref.t_est        # in fact bit-identical
            assert fast.port_busy == ref.port_busy
            assert fast.stall_by_reason == ref.stall_by_reason


def test_compile_program_memoizes_on_program_and_spec_identity():
    prog = parse_program(CHAIN_HLO)
    cp1 = compile_program(prog, TPU_V5E)
    cp2 = compile_program(prog, TPU_V5E)
    assert cp1 is cp2
    other = TPU_V5E.with_(op_startup_ns=0.0)
    cp3 = compile_program(prog, other)
    assert cp3 is not cp1
    assert compile_program(prog, TPU_V5E, compute_dtype="bf16") is not cp1


def test_shared_costed_list_bypasses_recosting():
    prog = parse_program(INDEP_HLO)
    costed = cost_program(prog, TPU_V5E)
    fast = schedule_program(prog, TPU_V5E, costed=costed)
    assert fast.t_est == schedule_reference(prog, TPU_V5E,
                                            costed=costed).t_est


def test_caller_supplied_costed_list_bypasses_compile_cache():
    """Regression: a modified costed list must not hit (or poison) the
    (program, spec) memo populated by an earlier plain call — the fast
    path has to schedule the costs it was GIVEN."""
    import dataclasses
    prog = parse_program(CHAIN_HLO)
    schedule_program(prog, TPU_V5E)                  # populate the cache
    scaled = [None if ot is None
              else dataclasses.replace(ot, t_compute=ot.t_compute * 70,
                                       t_mem=ot.t_mem * 70)
              for ot in cost_program(prog, TPU_V5E)]
    fast = schedule_program(prog, TPU_V5E, costed=scaled)
    ref = schedule_reference(prog, TPU_V5E, costed=scaled)
    assert fast.t_est == ref.t_est
    # and the plain cached path is not poisoned by the scaled costs
    assert schedule_program(prog, TPU_V5E).t_est == \
        schedule_reference(prog, TPU_V5E).t_est


# ------------------------------------------------------------- satellites
def test_memory_hierarchy_is_memoized():
    hw = TPU_V5E.with_(vmem_bw=12e12)        # fresh instance, empty cache
    assert hw.memory_hierarchy() is hw.memory_hierarchy()
    # with_ returns a NEW spec whose hierarchy reflects the new scalar
    # (the cache cannot leak through dataclasses.replace)
    shrunk = hw.with_(hbm_read_bw=1e9)
    assert shrunk.memory_hierarchy()[-1].read_bw == 1e9
    assert hw.memory_hierarchy()[-1].read_bw != 1e9


def test_bound_by_normalizes_port_busy_by_issue_width():
    """A 4-wide mem port with more RAW busy than a 1-wide vpu must not be
    crowned the binding port when its per-pipe time is lower — consistent
    with how t_roofline picks the binding term."""
    ops = ([OpStat(f"cp{i}", "copy", "data", "f32", bytes_accessed=1e9)
            for i in range(4)]
           + [OpStat("v", "add", "elementwise", "f32", flops=1.5e10,
                     bytes_accessed=1.0)])
    prog = Program(ops=ops, entry="e", n_partitions=1)
    hw = TPU_V5E.with_(issue_width={"mxu": 1, "vpu": 1, "mem": 4, "ici": 1})
    r = schedule_program(prog, hw)
    busy = r.port_busy
    assert busy["mem"] > busy["vpu"]                 # raw busy says mem
    assert busy["mem"] / 4 < busy["vpu"]             # per-pipe says vpu
    assert r.bound_by == "vpu"
    # reference path agrees
    assert schedule_reference(prog, hw).bound_by == "vpu"


def test_critical_path_truncation_flag_and_pa_note():
    """A binding chain longer than CRITICAL_PATH_LIMIT raises the flag
    and the PA report says the shown path is a suffix."""
    n = CRITICAL_PATH_LIMIT + 40
    ops = [OpStat(f"e{i}", "add", "elementwise", "f32", flops=1e9,
                  bytes_accessed=8.0, deps=[i - 1] if i else [],
                  dep_bytes=[8.0] if i else [])
           for i in range(n)]
    prog = Program(ops=ops, entry="e", n_partitions=1)
    r = schedule_reference(prog, TPU_V5E)
    assert r.critical_path_truncated
    assert len(r.critical_path) == CRITICAL_PATH_LIMIT
    # the lazily-built fast-path detail carries the flag too
    fast = schedule_program(prog, TPU_V5E)
    assert fast.critical_path_truncated
    from repro.core.engine import simulate_program
    from repro.core.pa import pa_report
    from repro.core.roofline import roofline_from_program
    eng = simulate_program(prog, TPU_V5E)
    rf = roofline_from_program(prog, TPU_V5E, 1, 0.0, "bf16")
    assert "TRUNCATED" in pa_report(rf, eng, prog, sched=r,
                                    engine_mode="schedule")
    # a short chain does not raise it
    short = schedule_reference(parse_program(CHAIN_HLO), TPU_V5E)
    assert not short.critical_path_truncated


def test_fast_path_detail_is_lazy_and_correct():
    prog = parse_program(INDEP_HLO)
    r = schedule_program(prog, TPU_V5E)
    assert r._timeline is None                       # nothing built yet
    ref = schedule_reference(prog, TPU_V5E)
    assert [s.op.name for s in r.timeline] == \
        [s.op.name for s in ref.timeline]
    assert [s.op.name for s in r.critical_path] == \
        [s.op.name for s in ref.critical_path]
    assert [s.start for s in r.timeline] == [s.start for s in ref.timeline]


def test_batched_sweep_beats_old_serial_grid_wall_time():
    """Acceptance: the enlarged default grid (5x3x2x3 = 90 combos),
    batched, must cost less wall time than the OLD 36-combo grid run
    serially through the reference interpreter."""
    rng = random.Random(3)
    programs = [random_program(rng, 120) for _ in range(4)]
    rows = [calibrate.KernelRow(f"p{i}", "synth", 1, measured_us=100.0,
                                simulated_us=100.0)
            for i in range(len(programs))]
    table = calibrate.AccuracyTable(rows, programs=programs)
    hw = CPU_HOST

    t0 = time.perf_counter()
    sweep = calibrate.sweep_o3(table, hw)
    t_batched = time.perf_counter() - t0
    assert len(sweep.results) == 90

    costed = [cost_program(p, hw, compute_dtype="f64") for p in programs]
    old_specs = [calibrate._knob_spec(hw, w, mw, 1, qd)
                 for w in (4, 16, 64, 256)
                 for mw in calibrate.O3_MEM_WIDTHS
                 for qd in calibrate.O3_QUEUE_DEPTHS]
    assert len(old_specs) == 36
    t0 = time.perf_counter()
    for cand in old_specs:
        for prog, ops in zip(programs, costed):
            schedule_reference(prog, cand, compute_dtype="f64", costed=ops)
    t_old = time.perf_counter() - t0
    assert t_batched < t_old, (t_batched, t_old)


def test_sweep_o3_results_match_reference_interpreter():
    """The batched sweep's per-combo t_est must be the reference
    scheduler's, so the tuned parameter file is the same one the PR-2
    serial sweep would have picked."""
    rng = random.Random(5)
    programs = [random_program(rng, 40) for _ in range(2)]
    rows = [calibrate.KernelRow(f"p{i}", "synth", 1, measured_us=50.0,
                                simulated_us=50.0)
            for i in range(len(programs))]
    table = calibrate.AccuracyTable(rows, programs=programs)
    hw = CPU_HOST
    sweep = calibrate.sweep_o3(table, hw, windows=(4, 64),
                               mem_widths=(1, 2), vpu_widths=(1,),
                               queue_depths=(4, 16))
    for r in sweep.results:
        cand = calibrate._knob_spec(hw, r["inflight_window"],
                                    r["mem_issue_width"],
                                    r["vpu_issue_width"], r["queue_depth"])
        diffs = [abs(schedule_reference(p, cand,
                                        compute_dtype="f64").t_est * 1e6
                     - row.measured_us) / row.measured_us * 100.0
                 for p, row in zip(programs, rows)]
        assert r["mean_abs_diff_pct"] == pytest.approx(
            sum(diffs) / len(diffs), rel=1e-12)


def test_perf_smoke_bench_program_is_deterministic():
    from benchmarks.sched_throughput import synthetic_program
    a = synthetic_program(n=200, seed=0)
    b = synthetic_program(n=200, seed=0)
    assert [o.deps for o in a.ops] == [o.deps for o in b.ops]
    assert [o.flops for o in a.ops] == [o.flops for o in b.ops]
    _assert_fast_matches_reference(a, CPU_HOST)
