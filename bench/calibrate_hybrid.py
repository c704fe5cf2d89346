"""Readings that the hybrid (Zamba2) cell's limits are set from: the
variants of ``calibrate.py`` (the program, the int8 control, the faults),
through ``drivers/train_hybrid.py``.

    python bench/calibrate_hybrid.py --workload zamba2-1.2b.train-4k \
        --seeds 1 2 3 [--variants program control half_batch unchanged]

One JSON object per (seed, variant) goes to standard output.  At this size
one chip holds one compiled step with its state, or the reference with its
own: each variant's state is freed and every compiled program dropped
(``jax.clear_caches``) before the next is loaded, again for each seed from
JAX's compilation cache.  Like ``run.py`` it needs the chip and has no
fallback.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402

import calibrate  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402
from run import device_gate  # noqa: E402
from traffic import TokenFeed  # noqa: E402

train = calibrate.train = spec.driver("train_hybrid")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+",
                    default=["program", "control", "half_batch"])
    args = ap.parse_args()
    bench = spec.benchmark()
    cell = spec.workload(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    device_gate(cell["chips"])
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    for seed in args.seeds:
        tokens = TokenFeed.from_traffic(traffic, cfg["vocab_size"], seed)

        def feed(i):
            return {"tokens": jax.numpy.asarray(tokens.batch(i))}

        readings = {}
        for v in args.variants:
            t0 = time.perf_counter()
            if v == "control":
                readings[v] = train.reference_readings(
                    cfg, traffic, seed, tokens.batch, mm=reference.int8_mm)
            else:
                prog, precision = calibrate.variant(v, cfg, traffic)
                with precision():
                    params, opt, readings[v] = prog.check_steps(seed, feed)
                train._free(params, opt)
                del prog, params, opt
            jax.clear_caches()
            gc.collect()
            readings[v]["seconds"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        ref = train.reference_readings(cfg, traffic, seed, tokens.batch)
        ref_s = time.perf_counter() - t0
        jax.clear_caches()
        gc.collect()
        for v, r in readings.items():
            print(json.dumps({"cell": cell["name"], "seed": seed,
                              "variant": v, **train.gaps(r, ref, leaves=True),
                              "losses": r["losses"],
                              "ref_losses": ref["losses"],
                              "seconds": r["seconds"],
                              "reference_seconds": ref_s}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
