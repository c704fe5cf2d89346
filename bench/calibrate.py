"""Readings that limits are set from: the program, its control and its
faults against the plain reference, over many seeds in one process.

    python bench/calibrate.py --workload <cell> --seeds 1 2 3 \
        [--variants program control half_batch]

Each variant compiles once; every seed then runs the cell's check steps
through it from the seed's weights and reads the three numbers ``correct``
compares.  One JSON object per (seed, variant) goes to standard output.
Variants:

* ``program``: the program as the configuration states it;
* ``control``: the plain reference in the program's place, its products in
  int8 (``reference.int8_mm``), one precision step below the program's;
* ``bf16_path``: the program's own bfloat16 compute path;
* a fault of ``faults.FAULTS``;
* ``pallas_highest`` / ``jnp_highest``: the program at full matmul
  precision through the Pallas SSD kernel or its jnp path, to find which
  path carries a gap.

Like ``run.py`` it needs the chip and has no fallback.
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))

import jax  # noqa: E402

import faults  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402
from run import device_gate  # noqa: E402
from traffic import TokenFeed  # noqa: E402

train = spec.driver("train")


def variant(name: str, cfg: dict, traffic: dict):
    """(Program, precision context) for a variant's name."""
    highest = contextlib.nullcontext
    if name == "program":
        return train.Program(cfg, traffic), highest
    if name == "bf16_path":
        return train.Program(cfg, traffic, compute_dtype="bfloat16"), highest
    if name in faults.FAULTS:
        return train.Program(cfg, traffic,
                             step_wrapper=faults.FAULTS[name]), highest
    impl = {"pallas_highest": "pallas", "jnp_highest": "jnp"}[name]
    cfg = copy.deepcopy(cfg)
    cfg["run"]["ssd_impl"] = impl
    return (train.Program(cfg, traffic),
            lambda: jax.default_matmul_precision("highest"))


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

    progs = {v: variant(v, cfg, traffic) for v in args.variants
             if v != "control"}
    for seed in args.seeds:
        tokens = TokenFeed.from_traffic(traffic, cfg["vocab_size"], seed)

        def feed(i):
            return {"tokens": jax.numpy.asarray(tokens.batch(i))}

        readings = {}
        for v, (prog, precision) in progs.items():
            t0 = time.perf_counter()
            with precision():
                params, opt, readings[v] = prog.check_steps(seed, feed)
            train._free(params, opt)
            readings[v]["seconds"] = time.perf_counter() - t0
        if "control" in args.variants:
            t0 = time.perf_counter()
            readings["control"] = train.reference_readings(
                cfg, traffic, seed, tokens.batch, mm=reference.int8_mm)
            readings["control"]["seconds"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        ref = train.reference_readings(cfg, traffic, seed, tokens.batch)
        ref_s = time.perf_counter() - t0
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
