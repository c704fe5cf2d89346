"""Sampled-estimation perf smoke: sampled vs full node scheduling.

Measures the SimPoint-style sampler (``core.sample``, DESIGN.md §18) on
three program families and FAILS the build when it stops paying for
itself:

* **bench DAG** — the repetitive 10k-op synthetic trace (the
  ``sched_throughput`` step unrolled 40x), monolithic full schedule vs
  sampled reconstruction at 48 cores.  CI floors: sampled wall-clock
  speedup >= 3x while scheduling <= 20% of op instances within 5%
  reconstruction error.
* **zoo long traces** — full-depth/multi-step zoo cells
  (``zoo.trace_long_phase``: the reduced step unrolled by the
  full/reduced layer ratio, 1024 decode steps) through the FULL
  ``estimate_program`` pipeline (3 core counts x 12-knob O3 grid), once
  unsampled and once sampled.  The unsampled pass is the one that blows
  the ``--budget`` gate; the sampled pass must complete under it, within
  5% of the unsampled estimate at 12 cores.  ``--quick`` restricts to
  one model (the CI cut; warm HLO cache from the model_zoo step, no new
  jax compiles).
* **kernel suite** (full mode only, jax) — every calibration kernel
  program unrolled 32x, same error/fraction pin.

Usage:  PYTHONPATH=src python -m benchmarks.sampled_estimation [--quick]

Artifact: ``BENCH_sampling.json`` at the repo root (schema: DESIGN.md
§18) — committed, rendered into EXPERIMENTS.md §Sampled-estimation, and
uploaded by CI.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

from repro.configs import ARCHS, ZOO_SHAPES, zoo_phases_for
from repro.core.hwspec import A64FX_CORE
from repro.core.sample import SamplingConfig, measure_sampled_vs_full, \
    unroll_program
from repro.core.zoo import estimate_program, phase_model_flops, \
    trace_long_phase, zoo_config, zoo_o3_knobs

from .sched_throughput import synthetic_program

BENCH_JSON = Path("BENCH_sampling.json")
HLO_CACHE = Path("experiments/zoo_hlo")

SPEEDUP_FLOOR = 3.0          # sampled >= 3x full on the 10k-op bench DAG
FRAC_CEIL = 0.20             # while scheduling <= 20% of op instances
ERR_CEIL_PCT = 5.0           # within 5% reconstruction error
BENCH_CORES = 48
ZOO_CORES = (1, 12, 48)
DECODE_STEPS = 1024
KERNEL_REPEATS = 32
QUICK_MODELS = ("chatglm3-6b",)


def bench_dag_row() -> dict:
    """Monolithic vs sampled on the repetitive 10k-op bench DAG."""
    step = synthetic_program(250, seed=3)
    step_inst = sum(o.count for o in step.ops)
    prog = unroll_program(step, 40)
    cfg = SamplingConfig(interval_ops=step_inst, phase_aware=False)
    row = measure_sampled_vs_full(prog, A64FX_CORE, BENCH_CORES,
                                  config=cfg, compute_dtype="f64")
    row["n_cores"] = BENCH_CORES
    return row


def zoo_phase_row(arch: str, phase: str, budget_s: float) -> dict:
    """One full-depth zoo cell through estimate_program, unsampled vs
    sampled (the budget-gate demonstration)."""
    prog, repeats = trace_long_phase(arch, phase, hlo_cache_dir=HLO_CACHE,
                                     decode_steps=DECODE_STEPS)
    cfg = zoo_config(arch)
    flops = phase_model_flops(cfg, ZOO_SHAPES[phase])
    knobs = zoo_o3_knobs(A64FX_CORE)
    step_inst = sum(o.count for o in prog.ops) / repeats

    t0 = time.perf_counter()
    pe_full = estimate_program(prog, A64FX_CORE, ZOO_CORES,
                               model_flops=flops, o3_knobs=knobs,
                               arch=arch, phase=phase)
    wall_full = time.perf_counter() - t0

    t0 = time.perf_counter()
    pe_sam = estimate_program(
        prog, A64FX_CORE, ZOO_CORES, model_flops=flops, o3_knobs=knobs,
        arch=arch, phase=phase,
        sampling=SamplingConfig(interval_ops=step_inst,
                                phase_aware=False))
    wall_sampled = time.perf_counter() - t0

    t_full = pe_full.at(12).t_est_s
    t_sam = pe_sam.at(12).t_est_s
    return {
        "n_ops": pe_full.n_ops,
        "trace_repeats": repeats,
        "k": pe_sam.sampling["k"],
        "n_intervals": pe_sam.sampling["n_intervals"],
        "frac_ops_scheduled": pe_sam.sampling["frac_ops_scheduled"],
        "t_full_us": t_full * 1e6,
        "t_sampled_us": t_sam * 1e6,
        "reconstruction_error_pct":
            100.0 * (t_sam - t_full) / max(t_full, 1e-30),
        "wall_full_s": wall_full,
        "wall_sampled_s": wall_sampled,
        "speedup": wall_full / max(wall_sampled, 1e-30),
        "budget_s": budget_s,
        "full_exceeds_budget": wall_full > budget_s,
        "sampled_under_budget": wall_sampled <= budget_s,
    }


def kernel_rows() -> dict:
    """Full mode: the jax kernel-suite programs, unrolled 32x."""
    from repro.core.calibrate import kernel_accuracy_table
    table = kernel_accuracy_table(A64FX_CORE, keep_programs=True)
    out = {}
    for krow, prog in zip(table.rows, table.programs):
        step_inst = sum(o.count for o in prog.ops)
        long_prog = unroll_program(prog, KERNEL_REPEATS)
        row = measure_sampled_vs_full(
            long_prog, A64FX_CORE, 12,
            config=SamplingConfig(interval_ops=step_inst,
                                  phase_aware=False),
            compute_dtype="f64")
        row["repeats"] = KERNEL_REPEATS
        out[krow.name] = row
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help=f"bench DAG + {len(QUICK_MODELS)} zoo model(s) "
                         "only, no jax kernel suite (the CI cut)")
    ap.add_argument("--speedup-floor", type=float, default=SPEEDUP_FLOOR,
                    help="fail if bench-DAG sampled speedup drops below")
    ap.add_argument("--budget", type=float, default=10.0,
                    help="per-phase wall budget (s) a sampled full-depth "
                         "zoo estimate must stay under (the gate the "
                         "unsampled pass blows). 0 disables")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    t_start = time.perf_counter()
    print(f"== sampled estimation ({A64FX_CORE.name}) ==")
    dag = bench_dag_row()
    print(f"  bench DAG   {dag['n_ops']:>6d} ops  k={dag['k']}/"
          f"{dag['n_intervals']}  frac={dag['frac_ops_scheduled']:.3f}  "
          f"err={dag['reconstruction_error_pct']:+.3f}%  "
          f"speedup={dag['speedup']:.1f}x")

    models = QUICK_MODELS if args.quick else tuple(sorted(ARCHS))
    zoo: dict = {}
    for arch in models:
        zoo[arch] = {}
        for phase in zoo_phases_for(zoo_config(arch)):
            row = zoo_phase_row(arch, phase, args.budget)
            zoo[arch][phase] = row
            print(f"  {arch:<24s}{phase:<9s}{row['n_ops']:>6d} ops "
                  f"x{row['trace_repeats']:<3d} k={row['k']}/"
                  f"{row['n_intervals']:<4d} "
                  f"frac={row['frac_ops_scheduled']:.3f}  "
                  f"err={row['reconstruction_error_pct']:+.3f}%  "
                  f"full={row['wall_full_s']:5.1f}s  "
                  f"sampled={row['wall_sampled_s']:5.2f}s", flush=True)

    kernels = {} if args.quick else kernel_rows()
    for name, row in kernels.items():
        print(f"  kernel:{name:<17s}{row['n_ops']:>6d} ops  "
              f"frac={row['frac_ops_scheduled']:.3f}  "
              f"err={row['reconstruction_error_pct']:+.3f}%")

    out = {
        "schema": 1,
        "hw": A64FX_CORE.name,
        "quick": bool(args.quick),
        "floors": {"speedup": args.speedup_floor, "frac": FRAC_CEIL,
                   "error_pct": ERR_CEIL_PCT, "budget_s": args.budget},
        "bench_dag": dag,
        "zoo": zoo,
        "kernels": kernels,
        "wall_s": time.perf_counter() - t_start,
    }
    BENCH_JSON.write_text(json.dumps(out, indent=1, sort_keys=True))
    print(f"wrote {BENCH_JSON} in {out['wall_s']:.1f}s")

    ok = True
    if dag["speedup"] < args.speedup_floor:
        print(f"FAIL: bench DAG sampled speedup {dag['speedup']:.2f}x is "
              f"below the {args.speedup_floor:.1f}x floor",
              file=sys.stderr)
        ok = False
    if dag["frac_ops_scheduled"] > FRAC_CEIL:
        print(f"FAIL: bench DAG scheduled "
              f"{100 * dag['frac_ops_scheduled']:.1f}% of instances "
              f"(> {100 * FRAC_CEIL:.0f}%)", file=sys.stderr)
        ok = False
    if abs(dag["reconstruction_error_pct"]) > ERR_CEIL_PCT:
        print(f"FAIL: bench DAG reconstruction error "
              f"{dag['reconstruction_error_pct']:+.2f}% exceeds "
              f"{ERR_CEIL_PCT:.0f}%", file=sys.stderr)
        ok = False
    for arch, by_phase in zoo.items():
        for phase, row in by_phase.items():
            cell = f"{arch}/{phase}"
            if abs(row["reconstruction_error_pct"]) > ERR_CEIL_PCT:
                print(f"FAIL: {cell} error "
                      f"{row['reconstruction_error_pct']:+.2f}% exceeds "
                      f"{ERR_CEIL_PCT:.0f}%", file=sys.stderr)
                ok = False
            if row["frac_ops_scheduled"] > FRAC_CEIL:
                print(f"FAIL: {cell} scheduled "
                      f"{100 * row['frac_ops_scheduled']:.1f}% of "
                      f"instances (> {100 * FRAC_CEIL:.0f}%)",
                      file=sys.stderr)
                ok = False
            if args.budget and not row["sampled_under_budget"]:
                print(f"FAIL: {cell} sampled estimate took "
                      f"{row['wall_sampled_s']:.1f}s "
                      f"(> {args.budget:.0f}s budget)", file=sys.stderr)
                ok = False
    for name, row in kernels.items():
        if abs(row["reconstruction_error_pct"]) > ERR_CEIL_PCT or \
                row["frac_ops_scheduled"] > FRAC_CEIL:
            print(f"FAIL: kernel {name} "
                  f"err={row['reconstruction_error_pct']:+.2f}% "
                  f"frac={row['frac_ops_scheduled']:.2f}",
                  file=sys.stderr)
            ok = False
    if not ok:
        return 1
    n_over = sum(r["full_exceeds_budget"]
                 for by in zoo.values() for r in by.values())
    print(f"OK: bench DAG {dag['speedup']:.1f}x >= "
          f"{args.speedup_floor:.1f}x at "
          f"{100 * dag['frac_ops_scheduled']:.1f}% ops, all errors within "
          f"{ERR_CEIL_PCT:.0f}%; {n_over} full-depth cell(s) over the "
          f"{args.budget:.0f}s budget completed sampled")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
