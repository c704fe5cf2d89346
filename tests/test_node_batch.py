"""Differential + property harness for the batched node engine (ISSUE 6).

The batched kernel's contract is *bit*-identity, not approximation: every
element of a ``schedule_node_batch`` call replays the scalar
``schedule_node`` interpreter's float ops in the same order, so ``==`` is
the assertion — any reassociation in the vectorized kernel is a bug, not
noise.  Three layers:

* **differential sweep** — random DAGs x {shard, round-robin, graph} x
  {1, 12, 48} cores x random O3 knob specs: each batch element equals the
  scalar engine on ``t_est``, ``t_zero_contention`` and ``iterations``;
  the fused core-count sweep equals per-count batched calls.
* **properties on the batched path** — the zero-contention/serial
  sandwich and shard-partition monotonicity in core count, asserted on
  whole batches at once.
* **compile caches** — ``compile_program`` / ``compile_node`` hit on
  VALUE-equal (not identical) HardwareSpecs, the regression for the
  ``chw is hw`` identity bug that made every ``with_``-derived knob spec
  recompile the program.
"""
import random

import numpy as np
import pytest

from repro.core.compiled import O3Knobs, compile_program
from repro.core.hwspec import A64FX_CORE, NodeTopology
from repro.core.node import (compile_node, schedule_node,
                             schedule_node_batch, schedule_node_sweep)
from tests.test_compiled_schedule import random_knobs, random_program

PARTITIONS = ("shard", "round-robin", "graph")
CORE_COUNTS = (1, 12, 48)


def _batch_for(nc, hw, specs, cores, partition):
    return schedule_node_batch(nc, hw, O3Knobs.from_specs(specs), cores,
                               partition=partition)


# ------------------------------------------------------------- differential
@pytest.mark.parametrize("part", PARTITIONS)
def test_batched_bit_identical_to_scalar_across_partitions_and_cores(part):
    """The headline contract: every (partition, core count, knob spec)
    cell of a batched call == the scalar engine, bitwise.  The programs
    and knobs are drawn from one seed, the same for every partition."""
    hw = A64FX_CORE
    rng = random.Random(0xB47C)
    cases = []
    for _ in range(4):
        prog = random_program(rng, rng.randint(24, 120))
        cases.append((prog, [random_knobs(rng) for _ in range(4)]))
    for prog, specs in cases:
        nc = compile_node(prog, hw, compute_dtype="f64")
        for cores in CORE_COUNTS:
            res = _batch_for(nc, hw, specs, cores, part)
            for m, sp in enumerate(specs):
                # random_knobs bases may carry foreign topologies —
                # pin the scalar run to the node under test
                r = schedule_node(nc, sp, cores, partition=part,
                                  topology=hw.topology)
                assert r.t_est == res.t_est[m], (cores, m)
                assert r.t_zero_contention == res.t_zero_contention[m]
                assert r.iterations == res.iterations[m]
            assert res.total_scheduled_ops == int(res.iterations.sum())


def test_batched_bit_identical_under_degenerate_topology():
    hw = A64FX_CORE
    topo = NodeTopology.degenerate(48)
    rng = random.Random(7)
    prog = random_program(rng, 80)
    nc = compile_node(prog, hw, compute_dtype="f64")
    specs = [random_knobs(rng) for _ in range(3)]
    for cores in CORE_COUNTS:
        res = schedule_node_batch(nc, hw, O3Knobs.from_specs(specs), cores,
                                  topology=topo, partition="round-robin")
        for m, sp in enumerate(specs):
            r = schedule_node(nc, sp, cores, partition="round-robin",
                              topology=topo)
            assert r.t_est == res.t_est[m]


def test_fused_core_sweep_equals_per_count_batches():
    """schedule_node_sweep folds the core axis into the knob batch for
    the shard partition; the [C, B] result must equal C independent
    batched calls, bitwise."""
    hw = A64FX_CORE
    rng = random.Random(21)
    prog = random_program(rng, 90)
    nc = compile_node(prog, hw, compute_dtype="f64")
    knobs = O3Knobs.from_specs([random_knobs(rng) for _ in range(5)])
    for part in ("shard", "round-robin"):
        sw = schedule_node_sweep(nc, hw, knobs, list(CORE_COUNTS),
                                 partition=part)
        assert sw.shape == (len(CORE_COUNTS), knobs.batch)
        for ki, cores in enumerate(CORE_COUNTS):
            per = schedule_node_batch(nc, hw, knobs, cores,
                                      partition=part).t_est
            assert np.array_equal(sw[ki], per), (part, cores)


# ----------------------------------------------------- batched properties
def test_batched_sandwich_and_iteration_bounds():
    hw = A64FX_CORE
    rng = random.Random(3)
    prog = random_program(rng, 100)
    nc = compile_node(prog, hw, compute_dtype="f64")
    specs = [random_knobs(rng) for _ in range(6)]
    for part in PARTITIONS:
        res = _batch_for(nc, hw, specs, 12, part)
        assert np.all(res.t_est >= res.t_zero_contention * (1 - 1e-12))
        assert np.all(res.iterations >= 1)
        # max_iters=8 fixpoint passes, plus the one final clamped pass
        assert np.all(res.iterations <= 9)
        assert np.all(np.isfinite(res.t_est))


def test_batched_shard_monotone_in_core_count():
    """More cores never hurt under the shard partition (each op's slice
    shrinks); asserted across the whole knob batch via the fused sweep."""
    hw = A64FX_CORE
    rng = random.Random(11)
    prog = random_program(rng, 100)
    nc = compile_node(prog, hw, compute_dtype="f64")
    knobs = O3Knobs.from_specs([random_knobs(rng) for _ in range(6)])
    sw = schedule_node_sweep(nc, hw, knobs, [1, 2, 4, 12, 48],
                             partition="shard")
    assert np.all(sw[1:] <= sw[:-1] * (1 + 1e-9))


# ------------------------------------------------------------ compile cache
def test_compile_program_cache_hits_on_value_equal_spec():
    rng = random.Random(9)
    prog = random_program(rng, 40)
    hw = A64FX_CORE
    cp = compile_program(prog, hw, compute_dtype="f64")
    clone = hw.with_()                       # fresh object, equal value
    assert clone is not hw and clone == hw
    assert compile_program(prog, clone, compute_dtype="f64") is cp
    # a genuinely different spec must MISS
    other = hw.with_(inflight_window=max(2, hw.inflight_window // 2))
    assert compile_program(prog, other, compute_dtype="f64") is not cp


def test_compile_node_cache_hits_on_value_equal_spec():
    rng = random.Random(10)
    prog = random_program(rng, 40)
    hw = A64FX_CORE
    nc = compile_node(prog, hw, compute_dtype="f64")
    clone = hw.with_()
    assert clone is not hw and clone == hw
    assert compile_node(prog, clone, compute_dtype="f64") is nc
    other = hw.with_(inflight_window=max(2, hw.inflight_window // 2))
    assert compile_node(prog, other, compute_dtype="f64") is not nc
