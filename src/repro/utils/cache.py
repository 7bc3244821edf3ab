"""JAX's persistent compilation cache, placed once per process.

Entry points call :func:`enable_compile_cache` at start-up (never at import
time). Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
this sets nothing. Otherwise the cache lives at one fixed directory of the
checkout, ``<repo>/.jax_cache``: the path is part of what a later run must
find again, so it never depends on a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
