"""Paper Table 1 + Fig. 3: the 28-kernel suite, simulator-vs-measured.

Two outputs, mirroring the two axes of Fig. 3:

1. **Accuracy** (the orange dots): % execution-time difference between the
   RIKEN-style simulator (``core.simulate`` on the compiled HLO, with the
   *calibrated* CPU_HOST parameter file) and the host CPU — the only silicon
   in this container, playing the A64FX test chip's role.  Summary stats are
   printed against the paper's (mean +1.3%, std 7.8%, |mean| 6.6%, 82%
   within +-10%).

2. **Throughput** (the bar chart): simulated cycles per 8-element operation
   on a single A64FX core (the paper's own target), from the same compiled
   HLO costed with the ``A64FX_CORE`` parameter file.

Usage:  PYTHONPATH=src python -m benchmarks.kernel_suite [--quick]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from repro.configs.a64fx_kernelsuite import (
    KERNELS, PAPER_MEAN_ABS_DIFF_PCT, PAPER_MEAN_DIFF_PCT,
    PAPER_STD_DIFF_PCT, PAPER_WITHIN_10PCT_FRACTION)
from repro.core import calibrate
from repro.core.compiled import compile_program, schedule_arrays, \
    schedule_batch
from repro.core.cost import cost_program
from repro.core.hwspec import A64FX_CORE, HardwareSpec
from repro.core.schedule import schedule_reference
from repro.core.simulate import simulate

OUT = Path("experiments/bench")
BENCH_JSON = Path("BENCH_kernel_suite.json")


def scheduler_throughput(table: calibrate.AccuracyTable,
                         hw: HardwareSpec, min_wall_s: float = 0.2) -> dict:
    """Wall-clock throughput of the O3 scheduler over the suite's parsed
    programs (pure python/numpy, no jax): the perf number to track as the
    scheduling engine grows.  Programs are compiled to array form OUTSIDE
    the timed loops so the metric isolates the scheduler from the cost
    pipeline.

    Three numbers, one hot path: the headline ``ops_per_s`` is the
    compiled BATCHED kernel driving the full default O3 knob grid (the
    sweep engine's inner loop — every combo counts as scheduling the
    program once, because it is); ``single_ops_per_s`` is the compiled
    scalar kernel one knob set at a time; ``reference_ops_per_s`` is the
    per-op interpreter the differential tests pin both against."""
    compiled = [compile_program(p, hw, compute_dtype="f64")
                for p in table.programs]
    knobs = calibrate.default_o3_knobs(hw)

    def timed(fn, per_round: int) -> dict:
        n_ops = rounds = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < min_wall_s:
            fn()
            n_ops += per_round
            rounds += 1
        wall = time.perf_counter() - t0
        return {"scheduled_ops": n_ops, "rounds": rounds, "wall_s": wall,
                "ops_per_s": n_ops / wall if wall > 0 else 0.0}

    suite_ops = sum(len(p.ops) for p in table.programs)

    def batched():
        for cp in compiled:
            schedule_batch(cp, knobs)

    def single():
        for cp in compiled:
            schedule_arrays(cp, hw)

    # reference interpreter with precomputed costed lists (the PR-2 metric)
    costed = [cost_program(p, hw, compute_dtype="f64")
              for p in table.programs]

    def reference():
        for prog, ops in zip(table.programs, costed):
            schedule_reference(prog, hw, costed=ops)

    res = timed(batched, suite_ops * knobs.batch)
    res["mode"] = "compiled_batched_o3_grid"
    res["grid_combos"] = knobs.batch
    # UNIT CHANGE vs the PR-2 number (75,143, single interpreter passes):
    # every grid combo counts as one schedule of the program — which it
    # is, bit-identically.  The like-for-like single-schedule trajectory
    # continues under single_ops_per_s / reference_ops_per_s below.
    res["pr2_baseline_single_ops_per_s"] = 75143.0
    res["single_ops_per_s"] = timed(single, suite_ops)["ops_per_s"]
    res["reference_ops_per_s"] = timed(reference, suite_ops)["ops_per_s"]
    return res


def a64fx_kernel_hlo(kernel_name: str, n: int) -> str:
    """Compile one suite kernel once; both A64FX sections reuse the text."""
    from repro.configs.a64fx_kernelsuite import KERNELS_BY_NAME
    with calibrate.host_x64():
        x1, x2, y0 = calibrate._kernel_inputs(KERNELS_BY_NAME[kernel_name], n)
        f = calibrate._jit_kernel(kernel_name)
        return f.lower(x1, x2, y0).compile().as_text()


def a64fx_cycles_per_8elem(hlo_text: str, n: int) -> float:
    """Simulated single-core A64FX cycles per 8-element operation."""
    rep = simulate(hlo_text, hw=A64FX_CORE, n_chips=1, compute_dtype="f64")
    cycles = rep.engine.t_est * 1.8e9
    return cycles / (n / 8)


# node estimates: 1 core / one full CMG / the whole 4-CMG node (the old
# code's only node story was A64FX_CORE's hardcoded ~1/4-of-HBM2 draw;
# these come from the contention model instead)
NODE_CORE_COUNTS = (1, 12, 48)


def a64fx_node_estimates(hlo_text: str) -> dict:
    """Contention-aware node estimates (OpenMP-style shard partition) for
    one suite kernel on the A64FX node topology.  Parses and costs the
    program once; only the node schedule reruns per core count."""
    from repro.core.hlo import parse_program
    from repro.core.node import compile_node, schedule_node
    prog = parse_program(hlo_text)
    nc = compile_node(prog, A64FX_CORE, compute_dtype="f64")
    out = {}
    for k in NODE_CORE_COUNTS:
        nr = schedule_node(nc, A64FX_CORE, k, partition="shard")
        out[k] = {
            "t_est_us": nr.t_est * 1e6,
            "t_zero_contention_us": nr.t_zero_contention * 1e6,
            "hbm2_n_active": nr.per_cmg[0].n_active.get("hbm2", 1.0),
        }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="subset of kernels, fewer repeats")
    ap.add_argument("--size-scale", type=int, default=calibrate.SIZE_SCALE)
    ap.add_argument("--sweep-o3", action="store_true",
                    help="grid-sweep the O3 schedule knobs (window / mem "
                         "issue width / queue depth) against the measured "
                         "kernels and report the tuned parameter file")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    kernels = KERNELS[::4] if args.quick else KERNELS

    print("== calibrating CPU_HOST parameter file (the paper's Fujitsu-"
          "parameter step, fitted not NDA'd) ==")
    hw = calibrate.fit_cpu_host()
    print(f"  vpu {hw.vpu_flops['f64'] / 1e9:.2f} GFLOP/s  "
          f"hbm {hw.hbm_read_bw / 1e9:.2f} GB/s  "
          f"llc {hw.vmem_bw / 1e9:.2f} GB/s  "
          f"startup {hw.op_startup_ns / 1e3:.0f} us")
    print(f"  opcode factors: "
          f"{ {k: round(v, 1) for k, v in sorted(hw.opcode_factor.items())} }")

    print("\n== accuracy vs the host 'test chip' (Fig. 3 orange dots; "
          "occupancy vs schedule engine) ==")
    table = calibrate.kernel_accuracy_table(hw, size_scale=args.size_scale,
                                            kernels=kernels,
                                            keep_programs=True)
    print(table.report())

    thr = scheduler_throughput(table, hw)
    print(f"\n== scheduler throughput: {thr['ops_per_s']:.0f} ops/s "
          f"({thr['scheduled_ops']} ops in {thr['wall_s'] * 1e3:.0f} ms) ==")

    sweep = None
    sweep_timing = None
    if args.sweep_o3:
        print("\n== O3 resource-knob sweep (paper §4: OoO parameter "
              "tuning, fitted against the test chip; batched array "
              "kernel) ==")
        t0 = time.perf_counter()
        sweep = calibrate.sweep_o3(table, hw)
        t_new = time.perf_counter() - t0
        print(sweep.report())
        b = sweep.results[0]
        print(f"  tuned: window={b['inflight_window']} "
              f"mem_width={b['mem_issue_width']} "
              f"vpu_width={b['vpu_issue_width']} qdepth={b['queue_depth']}")
        # wall-cost comparison vs the PR-2 sweep: the OLD 4x3x3 grid run
        # serially through the reference interpreter
        old_specs = [calibrate._knob_spec(hw, w, mw, 1, qd)
                     for w in (4, 16, 64, 256)
                     for mw in calibrate.O3_MEM_WIDTHS
                     for qd in calibrate.O3_QUEUE_DEPTHS]
        costed = [cost_program(p, hw, compute_dtype="f64")
                  for p in table.programs]
        t0 = time.perf_counter()
        for cand in old_specs:
            for prog, ops in zip(table.programs, costed):
                schedule_reference(prog, cand, compute_dtype="f64",
                                   costed=ops)
        t_old = time.perf_counter() - t0
        sweep_timing = {
            "combos": len(sweep.results), "wall_s": t_new,
            "old_combos": len(old_specs), "old_wall_s": t_old,
            "speedup_vs_old_grid": t_old / t_new if t_new > 0 else 0.0,
        }
        print(f"  wall: {len(sweep.results)} combos batched in "
              f"{t_new * 1e3:.1f} ms vs old {len(old_specs)}-combo serial "
              f"grid {t_old * 1e3:.1f} ms "
              f"({sweep_timing['speedup_vs_old_grid']:.1f}x)")

    print("\n== simulated A64FX single-core throughput "
          "(Fig. 3 bars; cycles / 8-element op) ==")
    bars = {}
    hlo_texts = {k.name: a64fx_kernel_hlo(k.name, k.n * 8) for k in kernels}
    for k in kernels:
        c = a64fx_cycles_per_8elem(hlo_texts[k.name], k.n * 8)
        bars[k.name] = c
        print(f"  {k.name:<8s}{k.ktype:<10s}{c:8.2f} cyc/8elem")

    print("\n== A64FX node estimates (contention model, shard partition; "
          "1 core / 1 CMG / full node) ==")
    node_rows = {}
    for k in kernels:
        est = a64fx_node_estimates(hlo_texts[k.name])
        node_rows[k.name] = est
        t1, t12, t48 = (est[c]["t_est_us"] for c in NODE_CORE_COUNTS)
        print(f"  {k.name:<8s}1c {t1:9.2f} us  12c {t12:9.2f} us "
              f"(x{t1 / max(t12, 1e-12):5.1f})  48c {t48:9.2f} us "
              f"(x{t1 / max(t48, 1e-12):5.1f})  "
              f"hbm2 active@12c {est[12]['hbm2_n_active']:.1f}")

    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / "kernel_suite.json").write_text(json.dumps({
        "rows": [{"name": r.name, "type": r.ktype, "n": r.n,
                  "measured_us": r.measured_us,
                  "simulated_us": r.simulated_us,
                  "diff_pct": r.diff_pct,
                  "simulated_sched_us": r.simulated_sched_us,
                  "sched_diff_pct": r.sched_diff_pct,
                  "bound_by": r.bound_by,
                  "fit_input": r.fit_input} for r in table.rows],
        "o3_sweep": sweep.results if sweep is not None else None,
        "o3_sweep_timing": sweep_timing,
        "summary": {
            "mean_diff_pct": table.mean_diff,
            "std_diff_pct": table.std_diff,
            "mean_abs_diff_pct": table.mean_abs_diff,
            "within_10pct": table.within_10pct,
            "sched_mean_abs_diff_pct": table.sched_mean_abs_diff,
            "sched_within_10pct": table.sched_within_10pct,
            "paper": {
                "mean_diff_pct": PAPER_MEAN_DIFF_PCT,
                "std_diff_pct": PAPER_STD_DIFF_PCT,
                "mean_abs_diff_pct": PAPER_MEAN_ABS_DIFF_PCT,
                "within_10pct": PAPER_WITHIN_10PCT_FRACTION,
            },
        },
        "a64fx_core_cycles_per_8elem": bars,
        "a64fx_node_estimates": node_rows,
        "calibrated_host": {
            "vpu_gflops": hw.vpu_flops["f64"] / 1e9,
            "hbm_gbps": hw.hbm_read_bw / 1e9,
            "llc_gbps": hw.vmem_bw / 1e9,
            "startup_us": hw.op_startup_ns / 1e3,
            "opcode_factor": hw.opcode_factor,
        },
    }, indent=1))
    print(f"wrote {OUT / 'kernel_suite.json'}")

    # perf-trajectory artifact (tracked from ISSUE 2 onward): per-kernel
    # t_est under both engines + wall-clock scheduler throughput
    BENCH_JSON.write_text(json.dumps({
        "kernels": {r.name: {"measured_us": r.measured_us,
                             "t_est_occupancy_us": r.simulated_us,
                             "t_est_schedule_us": r.simulated_sched_us,
                             "a64fx_node_us": {
                                 str(c): node_rows[r.name][c]["t_est_us"]
                                 for c in NODE_CORE_COUNTS}
                             if r.name in node_rows else None}
                    for r in table.rows},
        "scheduler_throughput": thr,
        "summary": {
            "mean_abs_diff_pct": table.mean_abs_diff,
            "sched_mean_abs_diff_pct": table.sched_mean_abs_diff,
            "within_10pct": table.within_10pct,
            "sched_within_10pct": table.sched_within_10pct,
        },
    }, indent=1))
    print(f"wrote {BENCH_JSON}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
