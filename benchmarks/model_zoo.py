"""Model-zoo sweep: every registry config through the node engine.

The one-node-application counterpart of ``benchmarks/kernel_suite.py``
(DESIGN.md §15): traces each architecture's train/prefill/decode phases
through the real model stack into compiled HLO, shards them over the
A64FX node topology, and reports contention-aware cycle estimates across
the 1 / 12 / 48 core axis plus rank-stability Kendall taus.

    PYTHONPATH=src python -m benchmarks.model_zoo            # full zoo
    PYTHONPATH=src python -m benchmarks.model_zoo --quick    # 5-model CI cut
    PYTHONPATH=src python -m benchmarks.model_zoo --arch mamba2-1.3b

Artifact: ``BENCH_model_zoo.json`` at the repo root (schema: DESIGN.md
§16) — committed, pinned by the rank-stability test in
``tests/test_zoo.py``, and rendered into EXPERIMENTS.md §Model-zoo by
``benchmarks/experiments_md.py``.  ``--budget`` makes the wall clock a
CI-enforceable gate: exit 1 when the sweep exceeds it.  Compiled HLO is
cached under ``experiments/zoo_hlo/`` so warm reruns skip the jax
compiles.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

from repro.configs import ARCHS
from repro.core.hwspec import A64FX_CORE
from repro.core.zoo import DEFAULT_CORE_COUNTS, run_zoo

BENCH_JSON = Path("BENCH_model_zoo.json")
HLO_CACHE = Path("experiments/zoo_hlo")

# the CI --quick cut: one model per family class that matters to the rank
# tables (dense, GQA dense, MoE, SSM, enc-dec)
QUICK_MODELS = ("chatglm3-6b", "qwen1.5-32b", "llama4-scout-17b-a16e",
                "mamba2-1.3b", "whisper-large-v3")


def _progress(arch: str, phase: str, pe, wall: float) -> None:
    by_core = "  ".join(
        f"{ce.n_cores}c {ce.t_est_s * 1e6:9.1f}us" for ce in pe.per_core)
    print(f"  {arch:<24s}{phase:<9s}{pe.n_ops:>5d} ops  {by_core}  "
          f"x{pe.node_speedup:5.1f}  {pe.roofline_dominant:<7s}"
          f"[{wall:5.1f}s]", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help=f"sweep only {len(QUICK_MODELS)} representative "
                         "models (the CI cut)")
    ap.add_argument("--arch", action="append", default=None,
                    help="sweep only this architecture (repeatable)")
    ap.add_argument("--phases", default=None,
                    help="comma-separated subset of train,prefill,decode")
    ap.add_argument("--core-counts", default=None,
                    help="comma-separated core counts "
                         f"(default {DEFAULT_CORE_COUNTS})")
    ap.add_argument("--budget", type=float, default=900.0,
                    help="wall-clock budget in seconds; exceeding it fails "
                         "the run (CI gate). 0 disables")
    ap.add_argument("--no-o3-grid", action="store_true",
                    help="skip the batched O3 knob grid per cell")
    ap.add_argument("--no-hlo-cache", action="store_true",
                    help="always recompile (ignore experiments/zoo_hlo/)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    models = args.arch
    if models is None:
        models = list(QUICK_MODELS) if args.quick else sorted(ARCHS)
    for m in models:
        if m not in ARCHS:
            ap.error(f"unknown arch {m!r}; known: {sorted(ARCHS)}")
    phases = args.phases.split(",") if args.phases else None
    core_counts = (tuple(int(c) for c in args.core_counts.split(","))
                   if args.core_counts else DEFAULT_CORE_COUNTS)

    print(f"== model zoo -> node engine ({A64FX_CORE.name}, "
          f"{len(models)} models, cores {core_counts}) ==")
    report = run_zoo(
        models=models, phases=phases, hw=A64FX_CORE,
        core_counts=core_counts, with_o3_grid=not args.no_o3_grid,
        hlo_cache_dir=None if args.no_hlo_cache else HLO_CACHE,
        progress=_progress)

    print("\n== rank tables (fastest first) & stability ==")
    for ph in report.phases:
        taus = report.rank_stability(ph)
        ranks = report.rank_table(ph, min(core_counts))
        print(f"  {ph:<9s}tau(min over core axis)={taus['min']:+.2f}  "
              f"tau(vs traced flops)={taus['vs_flops']:+.2f}")
        print(f"           @{min(core_counts)}c: {' > '.join(ranks)}")

    d = report.to_dict()
    BENCH_JSON.write_text(json.dumps(d, indent=1, sort_keys=True))
    print(f"\nwrote {BENCH_JSON} "
          f"({len(models)} models x {len(report.phases)} phases x "
          f"{len(core_counts)} core counts) in {report.wall_s:.1f}s")

    if args.budget and report.wall_s > args.budget:
        print(f"BUDGET EXCEEDED: {report.wall_s:.1f}s > {args.budget:.0f}s "
              "(tighten the zoo shapes or warm the HLO cache)",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
