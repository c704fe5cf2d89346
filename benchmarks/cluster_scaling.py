"""Cluster scaling study: parallel plans over a TofuD-style mesh.

The multi-node engine (``core.cluster``, DESIGN.md §20) prices tensor/
data/pipeline-parallel train configs as REAL scheduled collectives
overlapping compute inside the batched node engine, on a TofuD-style
torus whose links contend through the same fixpoint machinery as the
node's shared memory levels.

    PYTHONPATH=src python -m benchmarks.cluster_scaling          # full
    PYTHONPATH=src python -m benchmarks.cluster_scaling --quick  # CI smoke

Full mode sweeps the registry's largest MoE (grok-1-314b: expert
parallelism in play) and largest dense config (nemotron-4-340b) from 2
to 1024 nodes and writes the committed ``BENCH_cluster.json`` (schema:
DESIGN.md §16): scaling curves, the winning plan per node count, the
model rank table and plan-rank Kendall taus.  ``--quick`` runs a
synthetic collective-free DAG as the workload at 2 and 8 nodes — no
jax, no HLO cache, seconds of wall time — and enforces sanity floors:
the 2-node DP efficiency must beat the floor (tiny grad payload, near-
free sync), efficiencies must stay in (0, 1], and every step time must
be finite and above its compute floor.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from repro.core.cluster import ClusterWorkload, cluster_sweep
from repro.core.zoo import (DEFAULT_CLUSTER_MODELS, DEFAULT_NODE_COUNTS,
                            ClusterReport, run_cluster)

BENCH_JSON = Path("BENCH_cluster.json")
QUICK_JSON = Path("BENCH_cluster_quick.json")
HLO_CACHE = Path("experiments/zoo_hlo")
QUICK_N_OPS = 256
QUICK_NODE_COUNTS = (2, 8)
# 2-node pure-DP on the synthetic workload: one tiny grad all-reduce per
# "layer" against a 256-op step — overlap must keep efficiency high
QUICK_EFFICIENCY_FLOOR = 0.5


def quick_report() -> ClusterReport:
    """The jax-free smoke: a synthetic DAG dressed as a 4-layer model."""
    from benchmarks.sched_throughput import synthetic_program
    prog = synthetic_program(QUICK_N_OPS, seed=0)
    w = ClusterWorkload(
        name="synthetic", prog=prog, repeats=8, layers=4, d_model=512,
        seq_len=128, batch=2, param_bytes=64e6, frac_attn=0.4)
    report = ClusterReport(
        hw="a64fx_core", topology="a64fx_node", cluster="tofu_d",
        n_cores=48, compute_dtype="f32",
        node_counts=QUICK_NODE_COUNTS)
    t0 = time.perf_counter()
    report.results[w.name] = cluster_sweep(
        w, QUICK_NODE_COUNTS, n_cores=48, max_tp=4, max_pp=2)
    report.wall_s = time.perf_counter() - t0
    return report


def check_sanity(report: ClusterReport, efficiency_floor: float) -> list:
    """Invariants every sweep must satisfy; returns failure strings."""
    fails = []
    for model, rows in report.results.items():
        for r in rows:
            tag = f"{model} N={r.n_nodes} {r.plan.label}"
            if not (0.0 < r.parallel_efficiency <= 1.0 + 1e-9):
                fails.append(f"{tag}: efficiency "
                             f"{r.parallel_efficiency:.3f} outside (0, 1]")
            if not (r.t_step_s > 0.0 and r.t_step_s < float("inf")):
                fails.append(f"{tag}: non-finite step time {r.t_step_s}")
            if r.t_step_s + 1e-12 < r.t_floor_s:
                fails.append(f"{tag}: step {r.t_step_s:.3e} beats its "
                             f"compute floor {r.t_floor_s:.3e}")
        n0 = report.node_counts[0]
        dp_only = [r for r in rows
                   if r.n_nodes == n0 and r.plan.tp == 1 and r.plan.pp == 1]
        for r in dp_only:
            if r.parallel_efficiency < efficiency_floor:
                fails.append(
                    f"{model} N={n0} {r.plan.label}: DP efficiency "
                    f"{r.parallel_efficiency:.3f} below the "
                    f"{efficiency_floor:.2f} floor")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="synthetic smoke (no jax/zoo); writes "
                         f"{QUICK_JSON}")
    ap.add_argument("--efficiency-floor", type=float,
                    default=QUICK_EFFICIENCY_FLOOR,
                    help="minimum 2-node pure-DP parallel efficiency")
    ap.add_argument("--no-hlo-cache", action="store_true",
                    help="always retrace (ignore experiments/zoo_hlo/)")
    args = ap.parse_args(argv)

    if args.quick:
        print(f"== cluster scaling: synthetic smoke at "
              f"{QUICK_NODE_COUNTS} nodes ==")
        report = quick_report()
        target = QUICK_JSON
    else:
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
        cache = None if args.no_hlo_cache else HLO_CACHE
        print(f"== cluster scaling: {DEFAULT_CLUSTER_MODELS} over "
              f"{DEFAULT_NODE_COUNTS} nodes ==")
        report = run_cluster(
            hlo_cache_dir=cache,
            progress=lambda m, msg: print(f"  {m}: {msg}", flush=True))
        target = BENCH_JSON

    out = report.to_dict()
    out["mode"] = "quick" if args.quick else "full"
    target.write_text(json.dumps(out, indent=1))

    for model in report.results:
        print(f"{model}:")
        for n in report.node_counts:
            if not report.cells(model, n):
                continue
            b = report.best(model, n)
            print(f"  N={n:5d} best {b.plan.label:16s} "
                  f"t_step {b.t_step_s * 1e3:9.3f} ms  "
                  f"eff {b.parallel_efficiency:5.3f}  "
                  f"tok/s {b.tokens_per_s:12,.0f}")
        taus = report.plan_rank_stability(model)
        print(f"  plan-rank tau min {taus['min']:+.3f}")
    print(f"wrote {target} ({report.wall_s:.1f}s sweep)")

    fails = check_sanity(report, args.efficiency_floor)
    if fails:
        for f in fails:
            print(f"FAIL: {f}")
        return 1
    print("OK: all scaling-sanity floors hold")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
