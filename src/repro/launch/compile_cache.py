"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so a cache that moves between runs
never hits.  `JAX_COMPILATION_CACHE_DIR`, when set, wins and JAX reads it by
itself; otherwise entry points pass a fixed directory inside the checkout.
Call before the first compile.
"""
from __future__ import annotations

import os

import jax

__all__ = ["use_compile_cache"]


def use_compile_cache(default_dir: str) -> str:
    """Point the persistent cache at `default_dir` unless the environment
    already names one; return the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", default_dir)
    return default_dir
