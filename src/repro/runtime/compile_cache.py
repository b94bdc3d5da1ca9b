"""JAX's persistent compilation cache, at one fixed place.

A cold compile of a 224-px network is a large share of a short run, and the
cache key includes the cache's path, so the directory must not move between
runs.  Entry points call :func:`enable_compile_cache` once, before their
first compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache — src/repro/runtime/ is three levels below the root.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set.  Otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout (gitignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
