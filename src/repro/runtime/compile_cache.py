"""JAX's persistent compilation cache, at one fixed place per checkout.

The cache key includes the cache directory, so a directory that moves
between runs (a temporary name, a pid, a timestamp) never hits.  Entry
points call :func:`enable_compile_cache` before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/src/repro/runtime/compile_cache.py -> <repo>/.jax_cache
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing is configured here; otherwise the cache lives in
    :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
