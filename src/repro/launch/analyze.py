import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ same platform and device-count contract as dryrun.py (first lines,
# before jax init): a compile-only run on placeholder host devices.
"""PA-data deep-dive for one cell: top ops by time/bytes, trip counts,
collective schedule — the RIKEN simulator's per-section profiling applied to
a compiled (arch x shape x mesh) cell.

    PYTHONPATH=src python -m repro.launch.analyze --arch chatglm3-6b \
        --shape decode_32k [--multi-pod] [--dump-hlo /tmp/x.hlo]
"""
import argparse
import collections

from ..core.hwspec import TPU_V5E
from ..core.simulate import simulate
from ..configs import ARCHS, SHAPES
from .cell import build_cell, model_flops_for
from .mesh import make_production_mesh, n_chips


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--dump-hlo", default=None)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--microbatch", type=int, default=None)
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    run_overrides = {}
    if args.microbatch is not None:
        run_overrides["microbatch"] = args.microbatch
    cell = build_cell(args.arch, args.shape, mesh,
                      run_overrides=run_overrides or None)
    lowered = cell.lower()
    compiled = lowered.compile()
    text = compiled.as_text()
    if args.dump_hlo:
        with open(args.dump_hlo, "w") as f:
            f.write(text)
        print(f"wrote {len(text)} chars of HLO to {args.dump_hlo}")

    mf = model_flops_for(ARCHS[args.arch], SHAPES[args.shape])
    # one simulate() call: the report carries the parsed program and the
    # engine result, so the deep-dive below reuses the single costing pass
    rep = simulate(compiled, hw=TPU_V5E, n_chips=n_chips(mesh),
                   model_flops_global=mf,
                   title=f"{args.arch} {args.shape}")
    prog, eng = rep.program, rep.engine
    print(rep.pa)
    print(f"\nmemory_analysis: {rep.memory_analysis}")

    print(f"\n== top {args.top} ops by modeled time ==")
    print(f"{'op':<44s}{'opcode':<18s}{'count':>9s}{'GF':>8s}{'GB':>9s}"
          f"{'commGB':>9s}{'t_total_ms':>11s}")
    for t in eng.top_ops[:args.top]:
        o = t.op
        print(f"{o.name[:43]:<44s}{o.opcode:<18s}{o.count:>9.0f}"
              f"{o.flops * o.count / 1e9:>8.1f}"
              f"{o.bytes_accessed * o.count / 1e9:>9.2f}"
              f"{o.comm_bytes * o.count / 1e9:>9.2f}"
              f"{t.t_op * o.count * 1e3:>11.2f}")

    # trip-count audit: group op counts
    counts = collections.Counter(o.count for o in prog.ops)
    print("\n== op-count histogram (multiplier -> n_ops) ==")
    for c, n in sorted(counts.items()):
        print(f"  x{c:<10.0f} {n}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
