"""Compiled Mosaic or the Pallas interpreter: the one rule every op wrapper uses.

An explicit ``interpret=`` from the caller wins.  Otherwise kernels compile
iff the active JAX backend is TPU and run in the interpreter everywhere else,
so ``use_kernel=True`` on a TPU always runs the compiled kernel.  The answer
depends on process-global state only (the backend), so it is safe as a jit
static argument or an lru_cache key.
"""
from __future__ import annotations

from typing import Optional

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(explicit: Optional[bool] = None) -> bool:
    """``explicit`` if given, else True unless the default backend is TPU."""
    if explicit is not None:
        return bool(explicit)
    return jax.default_backend() != "tpu"
