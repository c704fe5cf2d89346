"""Paper Figs. 4 & 5: Stream Triad scaling, simulator vs measured.

The paper sweeps 1..12 A64FX cores against a shared L2 + HBM2; the
hardware-adaptation analogue here sweeps 1..12 host "cores" (XLA host
platform devices, one thread pool each) against the host's shared LLC +
DRAM — same experiment: per-core bandwidth until the shared level
saturates.  Each thread count runs in a *subprocess* (the device count is
locked at jax init, exactly the dry-run's XLA_FLAGS constraint).

Two sizes, as in the paper:
  * triad_l2:  working set sized to the shared-cache capacity (Fig. 4),
  * triad_mem: 2x that, DRAM-resident (Fig. 5).

The simulator side is the engine's saturating-bandwidth model:
    t_pred(n) = bytes / min(n * bw_1core, bw_shared_level)
with bw_1core and bw_shared_level taken from the *calibrated* CPU_HOST file
(fitted once, at n=1 — the paper's parameter-tuning step).  The orange-dot
analogue is the per-n % difference, reported exactly like Figs 4/5.

Usage:  PYTHONPATH=src python -m benchmarks.triad [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
OUT = Path("experiments/bench")

_CHILD = r"""
import json, statistics, sys, time
import jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec

n_threads = {n_threads}
n_elems = {n_elems}

mesh = jax.make_mesh((n_threads,), ("data",), (AxisType.Auto,))
sh = NamedSharding(mesh, PartitionSpec("data"))
a = jax.device_put(jnp.arange(n_elems, dtype=jnp.float64) * 1e-6, sh)
b = jax.device_put(jnp.ones(n_elems, dtype=jnp.float64), sh)

@jax.jit
def triad(a, b):
    return a + 3.0 * b

jax.block_until_ready(triad(a, b))
ts = []
for _ in range({repeats}):
    t0 = time.perf_counter()
    jax.block_until_ready(triad(a, b))
    ts.append(time.perf_counter() - t0)
print(json.dumps({{"t": statistics.median(ts)}}))
"""


def run_child(n_threads: int, n_elems: int, repeats: int) -> float:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_threads}"
    env["JAX_ENABLE_X64"] = "1"
    env["JAX_PLATFORMS"] = "cpu"        # the host is what this measures
    code = _CHILD.format(n_threads=n_threads, n_elems=n_elems,
                         repeats=repeats)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, check=True,
                         cwd=REPO)
    return json.loads(out.stdout.strip().splitlines()[-1])["t"]


def sweep(name: str, n_elems: int, threads, repeats: int):
    """Measure the whole thread sweep first, then fit the two-parameter
    saturating-bandwidth model (per-core bw from t=1, plateau from the
    sweep max — the paper's parameter-tuning step) and report the per-point
    % difference, exactly like Figs. 4/5: endpoints anchor the fit, the
    INTERIOR of the curve tests the model."""
    nbytes = 3 * 8 * n_elems                 # 2 reads + 1 write, f64
    meas = {n: run_child(n, n_elems, repeats) for n in threads}
    agg = {n: nbytes / t for n, t in meas.items()}
    bw1 = agg[threads[0]]
    plateau = max(agg.values())
    rows = []
    print(f"\n== {name}: {nbytes / 2**20:.0f} MiB working set "
          f"(fit: bw1 {bw1 / 1e9:.2f} GB/s, plateau "
          f"{plateau / 1e9:.2f} GB/s) ==")
    print(f"{'threads':>8s}{'measured_GB/s':>15s}{'simulated_GB/s':>16s}"
          f"{'diff%':>8s}")
    for n in threads:
        t_meas = meas[n]
        t_sim = nbytes / min(n * bw1, plateau)
        diff = 100.0 * (t_sim - t_meas) / t_meas
        rows.append({"threads": n, "measured_s": t_meas,
                     "simulated_s": t_sim,
                     "measured_gbps": agg[n] / 1e9,
                     "simulated_gbps": nbytes / t_sim / 1e9,
                     "diff_pct": diff})
        print(f"{n:>8d}{agg[n] / 1e9:>15.2f}"
              f"{nbytes / t_sim / 1e9:>16.2f}{diff:>8.1f}")
    return rows, {"bw1": bw1, "plateau": plateau}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args(argv)

    # every count divides 3 * 2^k sizes (10 doesn't; the paper's 1..12 grid
    # minus that point)
    threads = [1, 2, 4, 8] if args.quick else [1, 2, 3, 4, 6, 8, 12]
    repeats = 7 if args.quick else 15

    # sizes divisible by every thread count in the sweep (3 * 2^18, 3 * 2^22)
    l2_elems = 786_432            # 18 MiB working set (LLC, per the suite)
    mem_elems = 12_582_912        # 288 MiB working set (DRAM)
    rows_l2, fit_l2 = sweep("triad_l2 (Fig. 4 analogue)", l2_elems, threads,
                            repeats)
    rows_mem, fit_mem = sweep("triad_mem (Fig. 5 analogue)", mem_elems,
                              threads, repeats)

    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / "triad.json").write_text(json.dumps({
        "calibration": {"l2": fit_l2, "mem": fit_mem},
        "triad_l2": rows_l2,
        "triad_mem": rows_mem,
    }, indent=1))
    print(f"\nwrote {OUT / 'triad.json'}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
