"""The persistent compilation cache is placed by one helper, at start-up."""
from pathlib import Path

import jax

from repro.utils import cache


def test_cache_dir_is_fixed_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert cache.enable_compile_cache() == str(cache.REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(
            cache.REPO_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    repo = Path(__file__).resolve().parents[1]
    assert cache.REPO_CACHE_DIR == repo / ".jax_cache"


def test_cache_dir_from_the_environment_is_left_to_jax(monkeypatch,
                                                       tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
