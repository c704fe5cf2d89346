"""The paper's workflow, end to end: estimate a full-scale application's
execution profile on hardware you don't have.

    PYTHONPATH=src python examples/simulate_app.py --arch grok-1-314b \
        --shape train_4k

Lowers + compiles the FULL-size architecture for the production 256-chip
mesh (placeholder host devices — no allocation), then prints the simulator's
PA report: roofline terms, bound-by classification, collective schedule and
tuning hints.  This is what the RIKEN simulator did for Post-K applications,
adapted to XLA/TPU (DESIGN.md §2).

NOTE: spawns a subprocess so the 512-device XLA flag does not leak into the
parent (jax locks the device count at first init).
"""
import argparse
import os
import subprocess
import sys

CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
from repro.launch.dryrun import run_cell
r = run_cell("{arch}", "{shape}", multi_pod={multi}, force=True)
print(r["pa_report"])
mem = r.get("memory_analysis") or {{}}
print()
print("memory_analysis per device:",
      {{k: f"{{v/2**30:.2f}} GiB" for k, v in mem.items()}})
print("fits 16 GiB HBM:", r["fits_hbm"])
"""


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="grok-1-314b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    code = CHILD.format(arch=args.arch, shape=args.shape,
                        multi=args.multi_pod)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath("src")
    return subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__)))).returncode


if __name__ == "__main__":
    raise SystemExit(main())
