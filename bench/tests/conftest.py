"""Shared set-up of the benchmark's own tests: the harness's modules are
imported by their plain names, as ``bench/run.py`` imports them."""
import copy
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

# A small stand-in for the configuration: every width cut, the same
# structure (a tied stack of Mamba-2 mixers).
TINY_SSM = {
    "name": "tiny-ssm", "registry": "mamba2-1.3b",
    "d_model": 64, "n_layers": 2, "vocab_size": 500, "padded_vocab": 512,
    "tie_embeddings": True, "norm_eps": 1e-6,
    "ssm": {"d_state": 16, "d_conv": 4, "expand": 2, "head_dim": 32,
            "chunk": 32, "n_groups": 1},
    "reduced": ["n_layers", "d_model", "padded_vocab", "ssm"],
    "run": {"param_dtype": "float32", "compute_dtype": "float32",
            "ssd_impl": "pallas", "attn_impl": "blocked", "remat": "full"},
    "optimizer": {"name": "adamw", "lr": 3e-4, "b1": 0.9, "b2": 0.95,
                  "eps": 1e-8, "weight_decay": 0.1, "grad_clip": 1.0},
}
TINY_TRAFFIC = {"name": "tiny", "kind": "train", "batch": 2, "seq": 64,
                "mix": {"ramp": 1, "markov": 1}, "check_steps": 3,
                "trace_steps": 2}


@pytest.fixture
def tiny():
    return copy.deepcopy(TINY_SSM), copy.deepcopy(TINY_TRAFFIC)


@pytest.fixture
def benchmark_json():
    return json.loads((BENCH.parent / "BENCHMARK.json").read_text())
