"""Pure-jnp oracle for the residual Gram kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["gram_ref", "row_gram_ref"]


def gram_ref(r: jnp.ndarray) -> jnp.ndarray:
    """(D, N) -> (D, D) = R @ R.T, fp32 accumulation."""
    r32 = r.astype(jnp.float32)
    return jnp.matmul(r32, r32.T, precision=jax.lax.Precision.HIGHEST)


def row_gram_ref(v: jnp.ndarray, r: jnp.ndarray) -> jnp.ndarray:
    """(N,), (D, N) -> (D,) = R @ v, fp32 accumulation."""
    return jnp.matmul(r.astype(jnp.float32), v.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
