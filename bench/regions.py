"""Device time of the train step's regions, and the simulator's beside it.

The program's train step runs under named scopes (``stats.REGIONS``), and
the compiled step keeps each scope in its instructions' ``op_name``
metadata.  Each device op of the traced window counts its self time
(``tracereduce.op_totals``) to the region and phase of its instruction
(``hlo.op_names``, ``stats.region_of``), in ms a step; an op in no region
counts as ``other``.  The simulator prices the same compiled step region by
region (``SimReport.sections``, TPU_V5E, f32, as ``sim_est_err`` calls it).

The reduction runs once per traced window and prints the region x phase
table on standard error.  A program whose step holds no region metadata
gives nothing to read, and every reader of this module returns None.
"""
from __future__ import annotations

import sys
import time
import traceback
from collections import defaultdict

import tracereduce

OTHER = "other"
PHASES = ("forward", "recompute", "backward", "optimizer")


def reduce(ctx: dict):
    """``{"ms": {(region, phase): ms a step}, "sim_ms": {region: ms} or
    None}``, or None where the step names no region; kept in ``ctx``."""
    if "regions" not in ctx:
        ctx["regions"] = _reduce(ctx)
    return ctx["regions"]


def _reduce(ctx: dict):
    try:
        from repro.core.hlo import op_names
        from repro.core.stats import region_of
    except ImportError:         # a program from before the region scopes
        return None
    where = {n: region_of(o) for n, o in op_names(ctx["hlo_text"]).items()}
    if not any(r for r, _ in where.values()):
        return None
    unnamed = region_of("")
    lo, hi, devices = ctx["lo"], ctx["hi"], ctx["devices"]
    scale = 1e3 / (ctx["steps"] * len(devices))
    ms: dict = defaultdict(float)
    for ops in devices:
        for name, s in tracereduce.op_totals(ops, lo, hi).items():
            region, phase = where.get(name, unnamed)
            ms[(region or OTHER, phase)] += s * scale
    out = {"ms": dict(ms), "sim_ms": simulated(ctx["hlo_text"])}
    print(table(out), file=sys.stderr, flush=True)
    return out


def simulated(hlo_text: str):
    """{region: the simulator's t_est of its ops, in ms}, or None if the
    simulator fails on the step (its error then reads nothing)."""
    try:
        from repro.core.hwspec import TPU_V5E
        from repro.core.simulate import simulate

        t0 = time.perf_counter()
        rep = simulate(hlo_text, hw=TPU_V5E, n_chips=1, compute_dtype="f32")
        print(f"bench: regions: simulate() of the step took "
              f"{time.perf_counter() - t0:.3f} s", file=sys.stderr)
    except Exception:
        traceback.print_exc()
        return None
    s = rep.sections
    return {name: 1e3 * s.get("t_est_s", name) for name in s.sections()}


def table(red: dict) -> str:
    ms, sim = red["ms"], red["sim_ms"] or {}
    names = sorted({r for r, _ in ms} | set(sim),
                   key=lambda r: (r == OTHER, r))
    head = "".join(f"{p:>11s}" for p in PHASES)
    lines = ["bench: regions, device ms a step (simulated ms beside)",
             f"{'region':<17s}{head}{'total':>11s}{'simulated':>11s}"]
    for r in names:
        row = [ms.get((r, p), 0.0) for p in PHASES]
        sim_r = f"{sim[r]:11.3f}" if r in sim else f"{'-':>11s}"
        lines.append(f"{r:<17s}" + "".join(f"{v:11.3f}" for v in row)
                     + f"{sum(row):11.3f}{sim_r}")
    cols = [sum(v for (_, p), v in ms.items() if p == ph) for ph in PHASES]
    lines.append(f"{'busy':<17s}" + "".join(f"{v:11.3f}" for v in cols)
                 + f"{sum(cols):11.3f}{sum(sim.values()):11.3f}")
    return "\n".join(lines)


def region_ms(ctx: dict, *regions: str):
    """Device ms a step of ``regions``, every phase."""
    red = reduce(ctx)
    if red is None:
        return None
    return sum(v for (r, _), v in red["ms"].items() if r in regions)


def phase_ms(ctx: dict, phase: str):
    """Device ms a step of every op in ``phase``."""
    red = reduce(ctx)
    if red is None:
        return None
    return sum(v for (_, p), v in red["ms"].items() if p == phase)


def sim_region_err(ctx: dict):
    """Sum over the regions and ``other`` of |simulated - measured|, over
    the measured sum, in %."""
    red = reduce(ctx)
    if red is None or red["sim_ms"] is None:
        return None
    meas: dict = defaultdict(float)
    for (r, _), v in red["ms"].items():
        meas[r] += v
    sim = red["sim_ms"]
    err = sum(abs(sim.get(r, 0.0) - meas.get(r, 0.0))
              for r in set(meas) | set(sim))
    return 100.0 * err / sum(meas.values())
