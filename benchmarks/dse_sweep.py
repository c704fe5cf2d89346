"""Hardware DSE sweep: the candidate-architecture grid over the zoo.

The fused spec-axis engine (``core.dse``, DESIGN.md §19) drives the
64-point candidate grid (CMG count x cores/CMG x HBM stacks x ring
latency x VPU width) through zoo workloads as ONE batched costing +
contention fixpoint per program, and times that path against the
per-spec Python loop (``cost_program`` + ``compile_node`` +
``schedule_node_batch`` once per candidate).  The two are bit-identical
per element (``tests/test_spec_batch.py`` pins it); the sweep exists to
make the loop's wall time go away, so the build FAILS when the fused
path drops below ``--floor`` times the loop.

    PYTHONPATH=src python -m benchmarks.dse_sweep            # full, needs zoo HLO
    PYTHONPATH=src python -m benchmarks.dse_sweep --quick    # synthetic, jax-free CI smoke

Full mode writes the committed ``BENCH_dse.json`` (schema: DESIGN.md
§16): per-workload per-candidate estimates, Pareto fronts over
(cycles, HBM bytes, cores), the cross-workload Kendall-tau
ranking-stability matrix, and the measured throughput block.  ``--quick``
writes ``BENCH_dse_quick.json`` from a synthetic DAG — no jax, no HLO
cache, seconds of wall time — and enforces the same floor.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from repro.core.compiled import O3Knobs
from repro.core.dse import generate_grid, run_dse, spec_grid, sweep_workload
from repro.core.node import (compile_node, compile_node_grid,
                             schedule_node_batch, schedule_spec_sweep)

BENCH_JSON = Path("BENCH_dse.json")
QUICK_JSON = Path("BENCH_dse_quick.json")
SPEEDUP_FLOOR = 10.0
# prefill + decode for the model_zoo --quick cut: 10 workloads, one per
# family class x serving phase (train HLO is much bigger; the committed
# artifact stays regenerable in seconds from a warm cache)
FULL_MODELS = ("chatglm3-6b", "qwen1.5-32b", "llama4-scout-17b-a16e",
               "mamba2-1.3b", "whisper-large-v3")
FULL_PHASES = ("prefill", "decode")
HLO_CACHE = Path("experiments/zoo_hlo")
QUICK_N_OPS = 2_000


def _clear_caches(prog) -> None:
    """Drop the per-Program compile memos so every timed round pays the
    same cold-cache cost (the grid cache would otherwise hide the fused
    path's compile, and the 8-entry node cache thrashes at 64 specs
    anyway — clearing makes both paths honestly cold)."""
    for k in ("_node_cache", "_node_grid_cache", "_cost_cache",
              "_compile_cache"):
        prog.__dict__.pop(k, None)


def measure_throughput(prog, grid, compute_dtype="f32",
                       loop_rounds: int = 1,
                       fused_rounds: int = 3) -> dict:
    """Time the fused spec sweep against the per-spec loop on ``prog``.

    Both paths run cold (caches cleared per round) and compute the same
    [S] vector: each candidate scheduled shard-partitioned at its full
    core count with its own default O3 knobs.  Returns wall times,
    per-spec throughputs and the speedup."""
    S = grid.S

    def fused():
        _clear_caches(prog)
        ngc = compile_node_grid(prog, grid, compute_dtype=compute_dtype)
        return schedule_spec_sweep(ngc)[:, 0, 0]

    def loop():
        _clear_caches(prog)
        out = np.empty(S)
        for s, sp in enumerate(grid.specs):
            topo = grid.topology_of(s)
            nc = compile_node(prog, sp, compute_dtype=compute_dtype)
            res = schedule_node_batch(nc, sp, O3Knobs.single(sp),
                                      topo.n_cores, topology=topo,
                                      partition="shard")
            out[s] = res.t_est[0]
        return out

    t_fused = fused()          # warm numpy / allocator once
    t0 = time.perf_counter()
    for _ in range(fused_rounds):
        t_fused = fused()
    wall_fused = (time.perf_counter() - t0) / fused_rounds
    t0 = time.perf_counter()
    for _ in range(loop_rounds):
        t_loop = loop()
    wall_loop = (time.perf_counter() - t0) / loop_rounds

    if not np.array_equal(t_fused, t_loop):
        raise AssertionError(
            "fused sweep diverged from the per-spec loop "
            f"(max delta {np.abs(t_fused - t_loop).max():.3e})")
    return {
        "n_ops": len(prog.ops), "n_specs": S,
        "fused_wall_s": wall_fused, "loop_wall_s": wall_loop,
        "fused_specs_per_s": S / wall_fused,
        "loop_specs_per_s": S / wall_loop,
        "speedup": wall_loop / wall_fused,
        "bit_identical": True,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="synthetic-DAG smoke (no jax/zoo); writes "
                         f"{QUICK_JSON}")
    ap.add_argument("--floor", type=float, default=SPEEDUP_FLOOR,
                    help="fail when fused/loop speedup drops below this")
    ap.add_argument("--no-hlo-cache", action="store_true",
                    help="always retrace (ignore experiments/zoo_hlo/)")
    args = ap.parse_args(argv)

    points = generate_grid()
    grid = spec_grid(points)

    if args.quick:
        from benchmarks.sched_throughput import synthetic_program
        print(f"== DSE sweep: {grid.S} candidate specs (synthetic smoke) ==")
        prog = synthetic_program(QUICK_N_OPS)
        thr = measure_throughput(prog, grid)
        sw = sweep_workload(prog, grid)
        out = {
            "schema": 1, "mode": "quick",
            "n_specs": grid.S, "n_ops": thr["n_ops"],
            "throughput": thr, "floor_speedup": args.floor,
            "t_est_min_s": float(sw["t_est"].min()),
            "t_est_max_s": float(sw["t_est"].max()),
        }
        QUICK_JSON.write_text(json.dumps(out, indent=1))
        target = QUICK_JSON
    else:
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
        from repro.core.zoo import zoo_workloads
        workloads = zoo_workloads(FULL_MODELS, FULL_PHASES)
        print(f"== DSE sweep: {grid.S} candidate specs "
              f"({len(workloads)} zoo workloads) ==")
        cache = None if args.no_hlo_cache else HLO_CACHE
        out = run_dse(workloads, points=points, hlo_cache_dir=cache,
                      progress=lambda m: print(f"  {m}", flush=True))
        # time the fused-vs-loop race on the biggest traced workload
        from repro.core.zoo import trace_phase
        key = max(out["per_workload"],
                  key=lambda k: out["per_workload"][k]["n_ops"])
        arch, phase = key.split("/")
        prog = trace_phase(arch, phase, hlo_cache_dir=cache)
        thr = measure_throughput(prog, grid)
        out["throughput"] = {**thr, "workload": key,
                             "floor_speedup": args.floor}
        rs = out["rank_stability"]
        print(f"  rank stability: mean tau {rs['mean_tau']:+.3f}, "
              f"min {rs['min_tau']:+.3f} across "
              f"{len(out['workloads'])} workloads")
        BENCH_JSON.write_text(json.dumps(out, indent=1))
        target = BENCH_JSON

    print(f"fused:  {thr['fused_wall_s'] * 1e3:8.1f} ms/sweep "
          f"({thr['fused_specs_per_s']:,.0f} specs/s)")
    print(f"loop:   {thr['loop_wall_s'] * 1e3:8.1f} ms/sweep "
          f"({thr['loop_specs_per_s']:,.0f} specs/s)")
    print(f"speedup: {thr['speedup']:.1f}x (bit-identical), "
          f"floor {args.floor:.0f}x")
    print(f"wrote {target}")
    if thr["speedup"] < args.floor:
        print(f"FAIL: fused sweep speedup {thr['speedup']:.1f}x is below "
              f"the floor of {args.floor:.0f}x")
        return 1
    print("OK: fused sweep above the floor")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
