"""Run one benchmark cell once, on the machine it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic, limits and metric readers are found by
name from ``BENCHMARK.json`` (see ``spec.py``).  With ``--trace 0`` the
result line carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiled window.  The last line of standard
output is one JSON object; the numbers ``correct`` was judged by are the last
lines of standard error and the last key of that object.

There is no fallback: a run that finds no accelerator, or fewer chips than
the cell asks for, exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
OUT = BENCH / "out"
sys.path.insert(0, str(BENCH.parent / "src"))
# the TPU runtime's logs stay in the checkout, not at a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", str(OUT / "tpu_logs"))

import spec  # noqa: E402


def device_gate(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < chips:
        raise SystemExit(
            f"bench: this cell needs {chips} accelerator chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s) "
            f"({devices[0].device_kind}). No result.")
    return devices


def per_layer(bench: dict, cell: str, ctx: dict) -> dict:
    """Each per-layer metric of the cell from its reader.  A reader that
    finds nothing to read returns None, and the metric is left out of the
    line.  Every metric a cell lists has something to read there, so the
    gap is named on standard error, and a line that lacks a metric its cell
    lists is refused by whoever checks the benchmark."""
    out = {}
    for m in spec.metrics_of(bench, cell, "per_layer"):
        value = spec.reader(m["name"]).read(ctx)
        if value is None:
            print(f"bench: error: {m['name']} found nothing to read in "
                  f"{cell}'s trace; left out of the line", file=sys.stderr,
                  flush=True)
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def end_to_end(bench: dict, cell: str, values: dict) -> dict:
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in spec.metrics_of(bench, cell, "end_to_end")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = spec.benchmark()
    cell = spec.workload(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    limits = spec.limits(cell["name"])
    driver = spec.driver(traffic["kind"])

    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    devices = device_gate(cell["chips"])
    import peaks
    from repro.launch.compile_cache import use_compile_cache

    peak = peaks.peaks(devices[0].device_kind)
    use_compile_cache()
    out_dir = OUT / f"{cell['name']}.{args.seed}"
    res = driver.run(cfg, traffic, limits, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace), t0=T0,
                     out_dir=out_dir)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"]}
    if args.trace:
        ctx = dict(res["ctx"], peak=peak)
        summary = ctx["summary"]
        line["metrics"] = per_layer(bench, cell["name"], ctx)
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
    else:
        line["metrics"] = end_to_end(bench, cell["name"], res["e2e"])
    line["device"] = device
    line["checks"] = res["checks"]
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
