"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points that compile (the benchmark, ``python3 bench/run.py --workload
<cell> --seed <n> --seconds 10 --trace <0|1>``, and the sweeps under
``benchmarks/``) call :func:`use_compile_cache` once, before their first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
this module sets no other directory.  Otherwise the cache goes to
``experiments/jax_cache/`` under the checkout, a path derived from this
package's location so that it is the same on every run (the path is part of
what makes a cache hit).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The in-checkout cache directory used when the environment names none.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / "experiments" \
    / "jax_cache"


def use_compile_cache() -> Path:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return DEFAULT_CACHE_DIR
