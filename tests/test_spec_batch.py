"""Differential + property suite for the spec batch axis (DESIGN.md §19).

Pins the fused program × spec × knob pipeline bit-identical to the
per-spec scalar path:

* ``route_program_batch`` column s == ``route_program`` under spec s's
  hierarchy (times, per-level byte tallies) over random DAGs and both
  warm-cache and scratch-memory hierarchies;
* ``cost_program_batch`` column s == the ``cost_program`` loop (ports,
  compute/mem/ICI times), incl. collectives, per-opcode tables, denormal
  compute dtypes and degenerate 1-spec grids;
* ``schedule_spec_sweep`` == per-spec ``compile_node`` +
  ``schedule_node_batch`` loops (t_est / t_zero_contention / iterations);
* ``O3Knobs.unique`` dedup maps results back to the full grid;
* grid/structural validation, cache-identity regressions, and spec-axis
  monotonicity properties (bandwidth, flops, core count);
* spec-fuzz finiteness on extreme points (1-CMG, zero ring latency,
  g=1 collectives, zero ICI bandwidth).
"""
import dataclasses
import random

import numpy as np
import pytest

from repro.core.compiled import O3Knobs, schedule_batch
from repro.core.cost import cost_op, cost_program, cost_program_batch
from repro.core.hlo import OpStat, Program
from repro.core.hwspec import (A64FX_CORE, CPU_HOST, TPU_V5E, NodeTopology,
                               SpecGrid)
from repro.core.memory import route_program, route_program_batch
from repro.core.node import (compile_node, compile_node_batch,
                             compile_node_grid, schedule_node_batch,
                             schedule_node_sweep, schedule_spec_sweep)

from tests.test_compiled_schedule import random_knobs, random_program


# --------------------------------------------------------- generators
def rich_random_program(rng: random.Random, n: int) -> Program:
    """random_program's mix plus collectives, dot_dims matmuls and
    per-opcode latency-table entries — everything cost_op branches on."""
    prog = random_program(rng, n)
    for i, o in enumerate(prog.ops):
        r = rng.random()
        if r < 0.15:
            kind = rng.choice(["all-reduce", "all-gather", "reduce-scatter",
                               "all-to-all", "collective-permute"])
            prog.ops[i] = dataclasses.replace(
                o, opcode=kind, opclass="collective",
                comm_bytes=rng.choice([0.0, rng.uniform(1e3, 1e8)]),
                group_size=rng.choice([1, 2, 8]))
        elif o.opclass == "matmul" and r < 0.6:
            prog.ops[i] = dataclasses.replace(
                o, dot_dims=(rng.choice([1, 4, 96, 256]),
                             rng.choice([4, 128, 512]),
                             rng.choice([4, 128, 512])))
        elif o.opclass in ("elementwise", "reduce", "transcendental"):
            trans = {rng.choice(["exponential", "tanh", "sine"]):
                     rng.uniform(0, 1e3)} if rng.random() < 0.5 else {}
            vpu = {rng.choice(["minimum", "divide", "round-nearest-even"]):
                   rng.uniform(0, 1e3)} if rng.random() < 0.5 else {}
            prog.ops[i] = dataclasses.replace(
                o, trans_by_opcode=trans, vpu_by_opcode=vpu)
    return prog


def _vary(rng: random.Random, base, s: int):
    """One numeric variant of ``base`` (structure untouched)."""
    kw = dict(
        name=f"{base.name}_v{s}",
        transcendental_factor=base.transcendental_factor
        * rng.uniform(0.5, 2.0),
        peak_flops={k: v * rng.uniform(0.25, 4.0)
                    for k, v in base.peak_flops.items()},
        vpu_flops={k: v * rng.uniform(0.25, 4.0)
                   for k, v in base.vpu_flops.items()},
        hbm_read_bw=base.hbm_read_bw * rng.uniform(0.25, 4.0),
        hbm_write_bw=base.hbm_write_bw * rng.uniform(0.25, 4.0),
        vmem_bw=base.vmem_bw * rng.uniform(0.5, 2.0),
        ici_bw_per_link=base.ici_bw_per_link
        * rng.choice([0.0, 0.1, 1.0, 4.0]),
        collective_startup_us=base.collective_startup_us
        * rng.uniform(0.1, 2.0),
        op_startup_ns=base.op_startup_ns * rng.uniform(0.5, 2.0),
    )
    if rng.random() < 0.5:
        kw["opcode_factor"] = {k: v * rng.uniform(0.5, 2.0)
                               for k, v in base.opcode_factor.items()
                               if rng.random() < 0.7}
    if rng.random() < 0.5:
        kw["opclass_throughput"] = {"reduce": rng.uniform(0.5, 1.0),
                                    "elementwise": rng.uniform(0.8, 1.2)}
    sp = base.with_(**kw)
    if sp.mem_levels and rng.random() < 0.7:
        lv = tuple(dataclasses.replace(
            l, capacity=l.capacity * rng.choice([0.25, 1.0, 4.0]),
            read_bw=l.read_bw * rng.uniform(0.5, 2.0),
            write_bw=l.write_bw * rng.uniform(0.5, 2.0),
            latency_s=l.latency_s * rng.uniform(0.0, 2.0))
            for l in sp.mem_levels)
        sp = sp.with_(mem_levels=lv)
    return sp


def random_grid(rng: random.Random, S: int, base=None) -> SpecGrid:
    base = base or rng.choice([A64FX_CORE, CPU_HOST, TPU_V5E])
    return SpecGrid([_vary(rng, base, s) for s in range(S)])


# ------------------------------------------------ routing differential
@pytest.mark.parametrize("seed", range(4))
def test_route_batch_bit_identical(seed):
    rng = random.Random(seed)
    prog = rich_random_program(rng, 60)
    grid = random_grid(rng, 5)
    tb = route_program_batch(prog, grid.hierarchies(),
                             warm_caches=grid.warm_caches)
    assert tuple(tb.level_names) == grid.level_names
    names = list(grid.level_names)
    for s, sp in enumerate(grid.specs):
        ref = route_program(prog, sp.memory_hierarchy(),
                            warm_caches=sp.warm_caches)
        for i, tr in enumerate(ref):
            assert tb.t_read[i, s] == tr.t_read
            assert tb.t_write[i, s] == tr.t_write
            assert tb.latency[i, s] == tr.latency_s
            assert tb.t_mem[i, s] == tr.t_mem
            for k, nm in enumerate(names):
                assert tb.read_by_level[i, k, s] == \
                    tr.read_by_level.get(nm, 0.0)
                assert tb.write_by_level[i, k, s] == \
                    tr.write_by_level.get(nm, 0.0)


def test_route_batch_compute_dtype_and_empty():
    rng = random.Random(11)
    prog = rich_random_program(rng, 40)
    grid = random_grid(rng, 3, base=TPU_V5E)
    tb = route_program_batch(prog, grid.hierarchies(), compute_dtype="bf16",
                             warm_caches=grid.warm_caches)
    for s, sp in enumerate(grid.specs):
        ref = route_program(prog, sp.memory_hierarchy(),
                            compute_dtype="bf16",
                            warm_caches=sp.warm_caches)
        for i, tr in enumerate(ref):
            assert tb.t_mem[i, s] == tr.t_mem
    empty = route_program_batch(Program(ops=[], entry="e", n_partitions=1),
                                grid.hierarchies())
    assert empty.t_read.shape == (0, 3)
    with pytest.raises(ValueError):
        route_program_batch(prog, [])
    with pytest.raises(ValueError):
        route_program_batch(prog, [A64FX_CORE.memory_hierarchy(),
                                   TPU_V5E.memory_hierarchy()])


# --------------------------------------------------- cost differential
def _assert_cost_column_matches(prog, grid, bc, s, compute_dtype=None,
                                links=2):
    sp = grid.specs[s]
    ref = cost_program(prog, sp, links_per_collective=links,
                       compute_dtype=compute_dtype)
    names = list(grid.level_names)
    for i, ot in enumerate(ref):
        if ot is None:
            assert bc.port[i] is None
            assert bc.t_compute[i, s] == 0.0
            assert bc.t_mem[i, s] == 0.0
            assert bc.t_ici[i, s] == 0.0
            continue
        assert bc.port[i] == ot.port
        assert bc.count[i] == ot.op.count
        assert bc.t_compute[i, s] == ot.t_compute
        assert bc.t_mem[i, s] == ot.t_mem
        assert bc.t_ici[i, s] == ot.t_ici
        assert bc.t_op()[i, s] == ot.t_op
        if ot.traffic is None:        # collectives carry no memory traffic
            assert not bc.rd[i, :, s].any()
            assert not bc.wr[i, :, s].any()
        else:
            for k, nm in enumerate(names):
                assert bc.rd[i, k, s] == ot.traffic.read_by_level.get(nm, 0.0)
                assert bc.wr[i, k, s] == \
                    ot.traffic.write_by_level.get(nm, 0.0)


@pytest.mark.parametrize("seed", range(4))
def test_cost_batch_bit_identical(seed):
    rng = random.Random(100 + seed)
    prog = rich_random_program(rng, 60)
    grid = random_grid(rng, 5)
    bc = cost_program_batch(prog, grid)
    for s in range(grid.S):
        _assert_cost_column_matches(prog, grid, bc, s)


def test_cost_batch_compute_dtype_and_links():
    rng = random.Random(7)
    prog = rich_random_program(rng, 50)
    grid = random_grid(rng, 4, base=TPU_V5E)
    bc = cost_program_batch(prog, grid, links_per_collective=4,
                            compute_dtype="bf16")
    for s in range(grid.S):
        _assert_cost_column_matches(prog, grid, bc, s,
                                    compute_dtype="bf16", links=4)


def test_cost_batch_degenerate_single_spec():
    rng = random.Random(21)
    prog = rich_random_program(rng, 45)
    grid = SpecGrid([A64FX_CORE])
    bc = cost_program_batch(prog, grid)
    assert grid.S == 1
    _assert_cost_column_matches(prog, grid, bc, 0)


# ------------------------------------------------- grid validation
def test_spec_grid_rejects_structural_mismatch():
    with pytest.raises(ValueError):
        SpecGrid([])
    with pytest.raises(ValueError):
        SpecGrid([A64FX_CORE, TPU_V5E])           # level names differ
    with pytest.raises(ValueError):
        SpecGrid([CPU_HOST, CPU_HOST.with_(warm_caches=False)])
    with pytest.raises(ValueError):
        SpecGrid([TPU_V5E, TPU_V5E.with_(mxu_tile=(8, 8, 8))])
    with pytest.raises(ValueError):
        SpecGrid([TPU_V5E], topologies=[None, None])
    g1 = SpecGrid([A64FX_CORE, A64FX_CORE.with_(hbm_read_bw=1e9)])
    g2 = SpecGrid([A64FX_CORE, A64FX_CORE.with_(hbm_read_bw=1e9)])
    assert g1 == g2                                # value equality
    assert g1 != SpecGrid([A64FX_CORE])
    assert g1.topology_of(0).n_cores == 48
    assert SpecGrid([TPU_V5E]).topology_of(0).n_cores == 1


# --------------------------------------------- spec-axis monotonicity
def test_bandwidth_monotonicity_along_spec_axis():
    rng = random.Random(31)
    prog = rich_random_program(rng, 50)
    scales = [0.25, 0.5, 1.0, 2.0, 4.0]
    specs = []
    for s, sc in enumerate(scales):
        lv = tuple(dataclasses.replace(l, read_bw=l.read_bw * sc,
                                       write_bw=l.write_bw * sc)
                   for l in A64FX_CORE.mem_levels)
        specs.append(A64FX_CORE.with_(name=f"bw{s}", mem_levels=lv))
    bc = cost_program_batch(prog, SpecGrid(specs))
    # more bandwidth everywhere => per-op memory time never increases
    assert (np.diff(bc.t_mem, axis=1) <= 1e-18).all()


def test_flops_monotonicity_along_spec_axis():
    rng = random.Random(32)
    prog = rich_random_program(rng, 50)
    specs = [A64FX_CORE.with_(
        name=f"fl{s}",
        peak_flops={k: v * sc for k, v in A64FX_CORE.peak_flops.items()},
        vpu_flops={k: v * sc for k, v in A64FX_CORE.vpu_flops.items()})
        for s, sc in enumerate([0.5, 1.0, 2.0, 4.0])]
    bc = cost_program_batch(prog, SpecGrid(specs))
    assert (np.diff(bc.t_compute, axis=1) <= 1e-18).all()


# ---------------------------------------------------- spec-fuzz edges
@pytest.mark.parametrize("seed", range(6))
def test_extreme_spec_fuzz_finite(seed):
    """Extreme DSE corners (1-CMG, zero ring latency, g=1 collectives,
    zero ICI bandwidth, tiny caches) must cost finite non-negative."""
    rng = random.Random(500 + seed)
    prog = rich_random_program(rng, 40)
    base = A64FX_CORE
    lv = tuple(dataclasses.replace(
        l, capacity=max(l.capacity * rng.choice([1e-6, 1.0]), 1.0))
        for l in base.mem_levels)
    sp = base.with_(
        name=f"fuzz{seed}", mem_levels=lv,
        ici_bw_per_link=rng.choice([1e3, 1e10]),
        collective_startup_us=rng.choice([0.0, 10.0]),
        topology=NodeTopology(name="t1", n_cmgs=1,
                              cores_per_cmg=rng.choice([1, 8]),
                              ring_latency_s=0.0))
    costed = cost_program(prog, sp)
    for ot in costed:
        if ot is None:
            continue
        for v in (ot.t_compute, ot.t_mem, ot.t_ici):
            assert np.isfinite(v) and v >= 0.0
    grid = SpecGrid([sp, base])
    bc = cost_program_batch(prog, grid)
    for arr in (bc.t_compute, bc.t_mem, bc.t_ici, bc.latency):
        assert np.isfinite(arr).all() and (arr >= 0.0).all()


# ------------------------------------------------- knob-grid dedup
def test_o3knobs_unique_dedup_and_restore():
    w = np.array([1, 7, 1, 7, 3], dtype=np.int64)
    width = np.ones((5, 4), dtype=np.int64)
    depth = np.ones((5, 4), dtype=np.int64)
    width[4, 2] = 2
    k = O3Knobs(w, width, depth)
    uk, inv = k.unique()
    assert uk.batch == 3
    assert uk.window.tolist() == [1, 7, 3]       # first-occurrence order
    assert (uk.window[inv] == w).all()
    assert (uk.width[inv] == width).all()
    assert (uk.depth[inv] == depth).all()
    k2 = O3Knobs(np.array([1, 2], dtype=np.int64),
                 np.ones((2, 4), dtype=np.int64),
                 np.ones((2, 4), dtype=np.int64))
    uk2, inv2 = k2.unique()
    assert uk2 is k2 and (inv2 == np.arange(2)).all()


def test_schedule_batch_dedup_matches_per_combo():
    from repro.core.compiled import compile_program
    rng = random.Random(55)
    prog = random_program(rng, 80)
    hw = A64FX_CORE
    cp = compile_program(prog, hw)
    # (0, ...) clamps onto (1, ...): rows 0, 1, 3 alias
    knobs = O3Knobs.from_grid(hw, [(1, 1, 1, 4), (1, 1, 1, 4),
                                   (64, 2, 2, 16), (0, 1, 1, 4)])
    t = schedule_batch(cp, knobs)
    assert t[0] == t[1] == t[3]
    for b in range(knobs.batch):
        single = O3Knobs(knobs.window[b:b + 1], knobs.width[b:b + 1],
                         knobs.depth[b:b + 1])
        assert schedule_batch(cp, single)[0] == t[b]


def test_node_batch_dedup_and_pass_accounting():
    rng = random.Random(66)
    prog = random_program(rng, 50)
    sp = A64FX_CORE
    nc = compile_node(prog, sp)
    dup = O3Knobs.from_grid(sp, [(1, 1, 1, 4), (1, 1, 1, 4),
                                 (64, 2, 2, 16)])
    uniq = O3Knobs.from_grid(sp, [(1, 1, 1, 4), (64, 2, 2, 16)])
    res = schedule_node_batch(nc, sp, dup, 12, partition="shard")
    ref = schedule_node_batch(nc, sp, uniq, 12, partition="shard")
    assert res.t_est[0] == res.t_est[1] == ref.t_est[0]
    assert res.t_est[2] == ref.t_est[1]
    assert len(res.iterations) == 3
    # accounting counts passes actually run, not the expanded grid
    assert res.total_scheduled_ops == ref.total_scheduled_ops
    sw = schedule_node_sweep(nc, sp, dup, [1, 12], partition="shard")
    swu = schedule_node_sweep(nc, sp, uniq, [1, 12], partition="shard")
    assert (sw[:, [0, 2]] == swu).all()
    assert (sw[:, 0] == sw[:, 1]).all()


# ----------------------------------------------- fused spec-axis sweep
def _node_grid(rng: random.Random, S: int) -> SpecGrid:
    """A64FX-structured grid with per-spec numerics AND topologies."""
    specs = []
    for s in range(S):
        sp = _vary(rng, A64FX_CORE, s)
        topo = NodeTopology(
            name=f"t{s}", n_cmgs=rng.choice([1, 2, 4]), cores_per_cmg=12,
            shared_read_bw={"l2": rng.uniform(0.5, 2.0) * 900e9,
                            "hbm2": rng.uniform(0.5, 2.0) * 256e9},
            shared_write_bw={"l2": rng.uniform(0.5, 2.0) * 450e9,
                             "hbm2": rng.uniform(0.5, 2.0) * 256e9},
            ring_latency_s=rng.choice([0.0, 130e-9]), ring_bw=115e9)
        specs.append(sp.with_(topology=topo))
    return SpecGrid(specs)


@pytest.mark.parametrize("seed", range(3))
def test_spec_sweep_bit_identical_to_per_spec_loop(seed):
    rng = random.Random(900 + seed)
    prog = rich_random_program(rng, 40)
    grid = _node_grid(rng, 4)
    knobs = O3Knobs.from_specs([random_knobs(rng) for _ in range(3)])
    counts = [[1, min(12, grid.topology_of(s).n_cores)]
              for s in range(grid.S)]
    ngc = compile_node_grid(prog, grid)
    t = schedule_spec_sweep(ngc, knobs, core_counts=counts)
    assert t.shape == (grid.S, 2, 3)
    for s, sp in enumerate(grid.specs):
        nc = compile_node(prog, sp)
        # the grid's per-spec view carries the scalar pipeline's arrays
        assert (nc.cp.durations == ngc.durations0[:, s]).all()
        assert (nc.rd == ngc.views[s].rd).all()
        assert (nc.wr == ngc.views[s].wr).all()
        for c, k in enumerate(counts[s]):
            ref = schedule_node_batch(nc, sp, knobs, k,
                                      topology=grid.topologies[s],
                                      partition="shard")
            assert (t[s, c] == ref.t_est).all()


def test_spec_sweep_defaults_and_validation():
    rng = random.Random(44)
    prog = rich_random_program(rng, 30)
    grid = _node_grid(rng, 3)
    ngc = compile_node_grid(prog, grid)
    t = schedule_spec_sweep(ngc)          # per-spec full core count, C=1
    assert t.shape == (3, 1, 1)
    for s, sp in enumerate(grid.specs):
        nc = compile_node(prog, sp)
        ref = schedule_node_batch(nc, sp, O3Knobs.single(grid.specs[0]),
                                  grid.topology_of(s).n_cores,
                                  topology=grid.topologies[s],
                                  partition="shard")
        assert t[s, 0, 0] == ref.t_est[0]
    with pytest.raises(ValueError):
        schedule_spec_sweep(ngc, core_counts=[[1], [1]])   # ragged rows
    with pytest.raises(ValueError):
        schedule_spec_sweep(ngc, core_counts=[10_000])     # over topology


def test_spec_sweep_contention_monotone_in_shared_bandwidth():
    rng = random.Random(45)
    prog = rich_random_program(rng, 40)
    scales = [0.25, 0.5, 1.0, 2.0]
    topos = [NodeTopology(name=f"bw{i}", n_cmgs=4, cores_per_cmg=12,
                          shared_read_bw={"l2": sc * 900e9,
                                          "hbm2": sc * 256e9},
                          shared_write_bw={"l2": sc * 450e9,
                                           "hbm2": sc * 256e9})
             for i, sc in enumerate(scales)]
    grid = SpecGrid([A64FX_CORE.with_(name=f"s{i}", topology=tp)
                     for i, tp in enumerate(topos)])
    t = schedule_spec_sweep(compile_node_grid(prog, grid),
                            core_counts=[48])
    # more aggregate bandwidth at every shared level: never slower
    assert (np.diff(t[:, 0, 0]) <= 1e-12).all()


# -------------------------------------------------- compile caches
def test_compile_node_grid_cache_hit_and_no_alias():
    rng = random.Random(77)
    prog = random_program(rng, 30)
    g1 = SpecGrid([A64FX_CORE, A64FX_CORE.with_(hbm_read_bw=32e9)])
    ngc1 = compile_node_grid(prog, g1)
    # a VALUE-equal rebuilt grid hits the cache
    g1b = SpecGrid([A64FX_CORE, A64FX_CORE.with_(hbm_read_bw=32e9)])
    assert compile_node_grid(prog, g1b) is ngc1
    assert compile_node_grid(prog, g1, compute_dtype="bf16") is not ngc1
    ngc2 = compile_node_grid(prog, SpecGrid([A64FX_CORE]))
    assert ngc2 is not ngc1
    # a 1-spec grid compile never aliases the single-spec caches: the
    # scalar pipeline still compiles (and caches) its own entry, and the
    # two agree bitwise
    nc = compile_node(prog, A64FX_CORE)
    assert nc is not ngc2.views[0]
    assert nc is compile_node(prog, A64FX_CORE)       # scalar cache intact
    assert (nc.cp.durations == ngc2.durations0[:, 0]).all()
    assert (nc.t_comp == ngc2.views[0].t_comp).all()


def test_compile_node_batch_cache():
    rng = random.Random(78)
    prog = random_program(rng, 30)
    nc = compile_node(prog, A64FX_CORE)
    nb1 = compile_node_batch(nc, A64FX_CORE, 12, partition="shard")
    # shard structure is core-count independent: one cached form
    assert compile_node_batch(nc, A64FX_CORE, 48, partition="shard") \
        is nb1
    nb3 = compile_node_batch(nc, A64FX_CORE, 12, partition="round-robin")
    assert compile_node_batch(nc, A64FX_CORE, 12,
                              partition="round-robin") is nb3
    assert nb3 is not nb1
    # op partitions depend on the count: distinct entries
    assert compile_node_batch(nc, A64FX_CORE, 24,
                              partition="round-robin") is not nb3
    # a different topology VALUE gets its own entry
    assert compile_node_batch(nc, A64FX_CORE, 12,
                              topology=NodeTopology.degenerate(12),
                              partition="shard") is not nb1
    # explicit core_of bypasses the cache
    co = np.zeros(nc.n, dtype=np.int64)
    nb5 = compile_node_batch(nc, A64FX_CORE, 12, core_of=co)
    assert nb5 is not compile_node_batch(nc, A64FX_CORE, 12, core_of=co)


def test_g1_collective_zero_ici_bw_charges_startup_only():
    o = OpStat("c", "all-reduce", "collective", "f32", comm_bytes=1e6,
               group_size=1)
    sp = A64FX_CORE.with_(ici_bw_per_link=0.0)
    ot = cost_op(o, sp, ici_bw=0.0)
    assert ot.t_ici == sp.collective_startup_us * 1e-6
    # a real payload over a zero-bandwidth link is cleanly infeasible:
    # inf (never ZeroDivisionError), identically in both pipelines
    o2 = dataclasses.replace(o, group_size=8)
    ot2 = cost_op(o2, sp, ici_bw=0.0)
    assert ot2.t_ici == np.inf
    prog = Program(ops=[o, o2], entry="e", n_partitions=1)
    bc = cost_program_batch(prog, SpecGrid([sp, A64FX_CORE]))
    assert bc.t_ici[0, 0] == sp.collective_startup_us * 1e-6
    assert bc.t_ici[1, 0] == np.inf
    assert np.isfinite(bc.t_ici[:, 1]).all()


@pytest.mark.slow
def test_spec_batch_differential_on_kernel_suite_programs():
    """Acceptance: ``cost_program_batch`` columns (times, routed traffic,
    ports) are bit-identical to the scalar per-spec path on every
    compiled kernel-suite program — real XLA HLO, not just synthetic
    DAGs."""
    import jax

    from repro.configs.a64fx_kernelsuite import KERNELS
    from repro.core import calibrate
    from repro.core.hlo import parse_program

    rng = random.Random(11)
    grid = random_grid(rng, 3, base=CPU_HOST)
    with jax.enable_x64(True):
        for k in KERNELS:
            x1, x2, y0 = calibrate._kernel_inputs(k, k.n)
            f = calibrate._jit_kernel(k.name)
            prog = parse_program(f.lower(x1, x2, y0).compile().as_text())
            bc = cost_program_batch(prog, grid, compute_dtype="f64")
            for s in range(grid.S):
                _assert_cost_column_matches(prog, grid, bc, s,
                                            compute_dtype="f64")
