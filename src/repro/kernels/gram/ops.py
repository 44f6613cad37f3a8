"""jit'd public wrapper for the Gram kernel: padding, dtype, batching, fallback.

TPU is the target; on CPU we validate through the interpreter (exercised in
tests) but default to the ref oracle for speed inside ICOA itself.  The
compiled-vs-interpreter choice defaults to `interpret=None` = auto-select
from the JAX backend via kernels.runtime.resolve_interpret (compiled Mosaic
on TPU, interpreter elsewhere).

Batching: `pallas_call` has no built-in vmap rule, so the Pallas paths are
wrapped in `jax.custom_batching.custom_vmap` — `jax.vmap(gram)` (the Monte-
Carlo trial axis of api.batch_fit) lowers to the `*_batched` kernels of
kernel.py, which grid over the batch dimension instead of failing to batch.
The rule re-enters a custom-vmap function, so nested vmaps flatten into one
batch grid axis; unbatched operands are broadcast to the batch.
"""
from __future__ import annotations

import functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.kernels.gram.kernel import (gram_pallas, gram_pallas_batched,
                                       row_gram_pallas, row_gram_pallas_batched)
from repro.kernels.gram.ref import gram_ref, row_gram_ref
from repro.kernels.runtime import resolve_interpret

__all__ = ["gram", "row_gram"]

_LANE = 128


def _pad_to(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.lru_cache(maxsize=None)
def _gram_vmappable(block_n: int, interpret: bool):
    """The padded single-trial Pallas call, with a vmap rule that reroutes a
    batch (of any nesting depth) to the batch-gridded kernel."""

    @custom_vmap
    def call(rp: jnp.ndarray) -> jnp.ndarray:
        return gram_pallas(rp, block_n=block_n, interpret=interpret)

    @call.def_vmap
    def _rule(axis_size, in_batched, rp):
        if not in_batched[0]:
            rp = jnp.broadcast_to(rp, (axis_size,) + rp.shape)
        return batched(rp), True

    @custom_vmap
    def batched(rp: jnp.ndarray) -> jnp.ndarray:
        return gram_pallas_batched(rp, block_n=block_n, interpret=interpret)

    @batched.def_vmap
    def _nested(axis_size, in_batched, rp):
        if not in_batched[0]:
            rp = jnp.broadcast_to(rp, (axis_size,) + rp.shape)
        out = batched(rp.reshape((-1,) + rp.shape[2:]))
        return out.reshape(rp.shape[:2] + out.shape[1:]), True

    return call


@functools.lru_cache(maxsize=None)
def _row_gram_vmappable(block_n: int, interpret: bool):
    """Batching wrapper for the fused row-Gram call (same scheme as above)."""

    @custom_vmap
    def call(rp: jnp.ndarray, vp: jnp.ndarray) -> jnp.ndarray:
        return row_gram_pallas(rp, vp, block_n=block_n, interpret=interpret)

    @call.def_vmap
    def _rule(axis_size, in_batched, rp, vp):
        if not in_batched[0]:
            rp = jnp.broadcast_to(rp, (axis_size,) + rp.shape)
        if not in_batched[1]:
            vp = jnp.broadcast_to(vp, (axis_size,) + vp.shape)
        return batched(rp, vp), True

    @custom_vmap
    def batched(rp: jnp.ndarray, vp: jnp.ndarray) -> jnp.ndarray:
        return row_gram_pallas_batched(rp, vp, block_n=block_n,
                                       interpret=interpret)

    @batched.def_vmap
    def _nested(axis_size, in_batched, rp, vp):
        if not in_batched[0]:
            rp = jnp.broadcast_to(rp, (axis_size,) + rp.shape)
        if not in_batched[1]:
            vp = jnp.broadcast_to(vp, (axis_size,) + vp.shape)
        out = batched(rp.reshape((-1,) + rp.shape[2:]),
                      vp.reshape((-1,) + vp.shape[2:]))
        return out.reshape(rp.shape[:2] + out.shape[1:]), True

    return call


@partial(jax.jit, static_argnames=("use_pallas", "interpret", "block_n"))
def gram(r: jnp.ndarray, use_pallas: bool = False,
         interpret: Optional[bool] = None, block_n: int = 2048) -> jnp.ndarray:
    """(D, N) -> (D, D) = R @ R^T with fp32 accumulation.

    `use_pallas=True` routes through the TPU kernel; `interpret=None` (the
    default) auto-selects compiled-vs-interpreter from the backend (compiled
    on TPU, the Python interpreter as the CPU correctness-validation path —
    kernels.runtime.resolve_interpret).  Safe under `jax.vmap` (any depth):
    batches lower to the batch-gridded kernel.
    """
    d, n = r.shape
    if not use_pallas:
        return gram_ref(r)
    bn = min(block_n, _pad_to(n, _LANE))
    dp = _pad_to(d, _LANE)
    np_ = _pad_to(n, bn)
    rp = jnp.zeros((dp, np_), r.dtype).at[:d, :n].set(r)
    out = _gram_vmappable(bn, resolve_interpret(interpret))(rp)
    return out[:d, :d]


@partial(jax.jit, static_argnames=("use_pallas", "interpret", "block_n"))
def row_gram(v: jnp.ndarray, r: jnp.ndarray, use_pallas: bool = False,
             interpret: Optional[bool] = None,
             block_n: int = 2048) -> jnp.ndarray:
    """(N,), (D, N) -> (D,) = R @ v with fp32 accumulation.

    The incremental covariance engine's hot product: one residual-row delta
    against every agent's transmitted residuals (the rank-2 update of
    core.covstate). Padding/fallback mirror `gram`: `use_pallas=True` routes
    through the TPU kernel, `interpret=None` auto-selects compiled on TPU /
    interpreter elsewhere (kernels.runtime.resolve_interpret).  Safe under
    `jax.vmap` (any depth) via the batch-gridded kernel.
    """
    d, n = r.shape
    if not use_pallas:
        return row_gram_ref(v, r)
    bn = min(block_n, _pad_to(n, _LANE))
    dp = _pad_to(d, _LANE)
    np_ = _pad_to(n, bn)
    rp = jnp.zeros((dp, np_), r.dtype).at[:d, :n].set(r)
    vp = jnp.zeros((8, np_), v.dtype).at[0, :n].set(v)
    out = _row_gram_vmappable(bn, resolve_interpret(interpret))(rp, vp)
    return out[:d, 0]
