"""jit'd public wrappers for the Gram kernels: padding, dtype and batching.

Layout, packing and the batching rule are kernels.runtime's: the padded
single-trial call runs the batch-gridded kernel at B=1, and ``jax.vmap``
over `gram`/`row_gram` (any depth) runs it at the batch size.  The kernels
compile on TPU and run in the interpreter elsewhere
(kernels.runtime.resolve_interpret); core reaches them only when
``use_kernel`` is set and calls the jnp product otherwise.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.gram.kernel import gram_pallas, row_gram_pallas
from repro.kernels.runtime import batch_gridded, pad2, pad_geometry, row_pack

__all__ = ["gram", "row_gram"]


@partial(jax.jit, static_argnames=("block_n",))
def gram(r: jnp.ndarray, block_n: int = 2048) -> jnp.ndarray:
    """(D, N) -> fp32 (D, D) = R @ R^T on the Pallas kernel."""
    d, n = r.shape
    dp, np_, bn = pad_geometry(d, n, block_n)
    out = batch_gridded(gram_pallas, bn)(pad2(r, dp, np_))
    return out[:d, :d]


@partial(jax.jit, static_argnames=("block_n",))
def row_gram(v: jnp.ndarray, r: jnp.ndarray,
             block_n: int = 2048) -> jnp.ndarray:
    """(N,), (D, N) -> fp32 (D,) = R @ v on the Pallas kernel.

    The incremental covariance engine's hot product: one residual-row delta
    against every agent's transmitted residuals (the rank-2 update of
    core.covstate).
    """
    d, n = r.shape
    dp, np_, bn = pad_geometry(d, n, block_n)
    out = batch_gridded(row_gram_pallas, bn)(pad2(r, dp, np_),
                                             row_pack(v, np_))
    return out[:d, 0]
