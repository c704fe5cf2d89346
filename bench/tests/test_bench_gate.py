"""bench/run.py exits non-zero and prints no result without a chip, or with
nothing but the benchmark's own files."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "mamba2-1.3b.train-2k", "--seed", "2147483999",
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(p):
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_no_accelerator_no_result():
    p = _run(ROOT)
    _no_result(p)
    assert "accelerator" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    _no_result(_run(tmp_path))
