"""Elastic-scaling dry-run: the same cell compiles on a DEGRADED mesh.

A production job that loses a pod slice must restart on fewer chips (the
checkpoint layer already reshards state — test_checkpoint_fault).  This
test proves the sharding rules are elastic at the compile level: the same
(arch x shape) lowers and compiles on a half-pod (8, 16) = 128-chip mesh
with no code changes — only the mesh tuple differs.

Runs in a subprocess because the forced device count locks at jax init.
"""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow        # 128-device recompile in a subprocess

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=128"
import json
import jax
from jax.sharding import AxisType
from repro.launch.cell import build_cell

mesh = jax.make_mesh((8, 16), ("data", "model"), (AxisType.Auto,) * 2,
                     devices=jax.devices())
cell = build_cell("{arch}", "{shape}", mesh)
compiled = cell.lower().compile()
m = compiled.memory_analysis()
print(json.dumps({{"ok": True,
                   "temp_gb": m.temp_size_in_bytes / 2**30}}))
"""


@pytest.mark.parametrize("arch,shape", [
    ("qwen1.5-110b", "train_4k"),
    ("mamba2-1.3b", "decode_32k"),
])
def test_cell_compiles_on_degraded_half_pod(arch, shape):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath("src")
    code = _CHILD.format(arch=arch, shape=shape)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["ok"]
