import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST be the first lines: jax locks the platform and device count on first
# init.  The dry-run (and ONLY the dry-run) needs 512 placeholder host devices
# so jax.make_mesh can build the production meshes; it only compiles, so it
# names the CPU even where an accelerator is attached.  Smoke tests and
# benches see the normal single device.
"""Multi-pod dry-run: lower + compile EVERY (arch x shape) cell on the
single-pod (16, 16) mesh AND the 2-pod (2, 16, 16) mesh, prove it fits
(memory_analysis), extract roofline terms (cost_analysis + collective bytes
from the partitioned HLO), and feed the RIKEN-style simulator.

    PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch mamba2-1.3b
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multi --force

Artifacts: experiments/dryrun/<mesh>/<arch>__<shape>.json — consumed by
EXPERIMENTS.md §Dry-run/§Roofline and by benchmarks/roofline_table.py.

(No ``from __future__ import annotations`` here: the environment lines above
must stay the first statements in the file, which PEP 236 disallows for
__future__ imports.  Plain py3.9+ annotations only.)
"""
import argparse
import json
import time
import traceback
from pathlib import Path


from ..configs import ARCHS, SHAPES, skipped_shapes_for
from ..core.hwspec import TPU_V5E
from ..core.simulate import simulate
from .cell import all_cells, build_cell, model_flops_for
from .mesh import make_production_mesh, n_chips

HBM_PER_CHIP = TPU_V5E.hbm_bytes
OUT_DIR = Path("experiments/dryrun")


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: Path = OUT_DIR, force: bool = False,
             run_overrides: dict | None = None,
             act_rule_overrides: dict | None = None,
             tag: str = "") -> dict:
    mesh_name = "multi_pod" if multi_pod else "single_pod"
    dest = out_dir / mesh_name / f"{arch}__{shape_name}{tag}.json"
    if dest.exists() and not force:
        return json.loads(dest.read_text())

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    cell = build_cell(arch, shape_name, mesh, run_overrides=run_overrides,
                      act_rule_overrides=act_rule_overrides)
    lowered = cell.lower()
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cfg = ARCHS[arch]
    mf = model_flops_for(cfg, SHAPES[shape_name])
    rep = simulate(compiled, hw=TPU_V5E, n_chips=n_chips(mesh),
                   model_flops_global=mf,
                   title=f"{arch} {shape_name} {mesh_name}")

    mem = rep.memory_analysis or {}
    peak = mem.get("peak_bytes_est", 0.0)
    result = {
        "arch": arch,
        "shape": shape_name,
        "kind": cell.kind,
        "mesh": mesh_name,
        "n_chips": n_chips(mesh),
        "t_lower_s": round(t_lower, 2),
        "t_compile_s": round(t_compile, 2),
        "fits_hbm": bool(peak and peak <= HBM_PER_CHIP) if peak else None,
        "hbm_per_chip": HBM_PER_CHIP,
        "model_flops_global": mf,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "microbatch": cell.run.microbatch,
        "roofline": rep.roofline.as_dict(),
        "engine": {
            "t_est": rep.engine.t_est,
            "t_roofline": rep.engine.t_roofline,
            "port_busy": rep.engine.port_busy,
            "bound_by": rep.engine.bound_by,
            "mxu_utilization": rep.engine.mxu_utilization,
            "collective_time_by_kind": rep.engine.collective_time_by_kind,
        },
        "program": rep.program_summary,
        "memory_analysis": rep.memory_analysis,
        "xla_cost_analysis": rep.xla_cost_analysis,
        "pa_report": rep.pa,
    }
    dest.parent.mkdir(parents=True, exist_ok=True)
    dest.write_text(json.dumps(result, indent=1, sort_keys=True))
    return result


def fmt_row(r: dict) -> str:
    rf = r["roofline"]
    mem = r.get("memory_analysis") or {}
    peak_gib = (mem.get("peak_bytes_est") or 0) / 2**30
    return (f"{r['arch']:<24s}{r['shape']:<13s}{r['mesh']:<11s}"
            f"{rf['compute_s']:>10.4f}{rf['memory_s']:>10.4f}"
            f"{rf['collective_s']:>11.4f}  {rf['dominant']:<10s}"
            f"{rf['useful_flops_ratio']:>7.2f}{peak_gib:>9.2f}GiB"
            f"{r['t_compile_s']:>8.1f}s")


HEADER = (f"{'arch':<24s}{'shape':<13s}{'mesh':<11s}{'compute_s':>10s}"
          f"{'memory_s':>10s}{'collect_s':>11s}  {'dominant':<10s}"
          f"{'MF/HF':>7s}{'peak':>12s}{'compile':>9s}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one architecture id")
    ap.add_argument("--shape", default=None, help="one shape name")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=str(OUT_DIR))
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    cells = all_cells()
    if args.arch:
        cells = [(a, s) for a, s in cells if a == args.arch]
    if args.shape:
        cells = [(a, s) for a, s in cells if s == args.shape]
    if args.list:
        for a, s in cells:
            print(f"{a:<26s}{s}")
        for name, cfg in ARCHS.items():
            for shape, why in skipped_shapes_for(cfg):
                print(f"{name:<26s}{shape.name:<13s}SKIP: {why}")
        return 0

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    out_dir = Path(args.out)
    print(HEADER)
    failures = []
    for multi_pod in meshes:
        for arch, shape in cells:
            try:
                r = run_cell(arch, shape, multi_pod=multi_pod,
                             out_dir=out_dir, force=args.force)
                print(fmt_row(r), flush=True)
            except Exception as e:  # noqa: BLE001 — report all failures at end
                failures.append((arch, shape, multi_pod, repr(e)))
                print(f"{arch:<24s}{shape:<13s}"
                      f"{'multi_pod' if multi_pod else 'single_pod':<11s}"
                      f"FAILED: {e}", flush=True)
                traceback.print_exc()
    # skipped cells, accounted
    for name, cfg in ARCHS.items():
        for shape, why in skipped_shapes_for(cfg):
            print(f"{name:<24s}{shape.name:<13s}{'(both)':<11s}SKIPPED: {why[:60]}...")
    if failures:
        print(f"\n{len(failures)} FAILURES")
        for f in failures:
            print("  ", f)
        return 1
    print("\nall cells compiled")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
