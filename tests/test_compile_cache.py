"""Where JAX's persistent compilation cache goes: the directory the
environment names, else one fixed path inside the checkout."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    """Restore JAX's cache directory after a test that sets it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_environment_names_the_cache(monkeypatch, tmp_path,
                                     cache_dir_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == tmp_path
    assert jax.config.jax_compilation_cache_dir == before   # set no other


def test_default_cache_is_a_fixed_path_in_the_checkout(monkeypatch,
                                                       cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.use_compile_cache()
    assert first == REPO / "experiments" / "jax_cache"
    assert jax.config.jax_compilation_cache_dir == str(first)
    assert compile_cache.use_compile_cache() == first        # same every run
