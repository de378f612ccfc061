"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so a path that moves between runs
never hits: the default is one fixed directory inside the checkout,
never a temporary name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/cache.py -> the checkout root
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that variable
    itself and no other directory is set here. Otherwise the cache goes
    to ``<checkout>/.jax_cache``. Call before the first compilation.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
