"""What the main-path kernel ops share: the interpret rule, the layout and
the batching rule.

Interpret rule.  An explicit ``interpret=`` from the caller wins.  Otherwise
kernels compile iff the active JAX backend is TPU and run in the interpreter
everywhere else, so ``use_kernel=True`` on a TPU always runs the compiled
kernel.  The answer depends on process-global state only (the backend), so
it is safe as a jit static argument or an lru_cache key.

Layout (gram, row_gram, probe, commit).  The residual matrix R (D, N) is
zero-padded to (Dp, Np): D to a multiple of the 8-row fp32 sublane width,
N to a multiple of the N-block ``bn`` (at most ``block_n``, at least one
128-lane width).  D lies on sublanes in every (Dp, .) operand and each
block spans the whole D axis, so any multiple of 8 tiles, and a wider pad
would only stream zero rows.  TPU Pallas wants >= 2-D operands, so
D-vectors travel as (Dp, 8) column packs (payload in column 0), N-vectors
as (8, Np) row packs (payload in row 0) and scalars on an (8, 128)
parameter plate (payload along row 0).  The zero padding is load-bearing:
it makes full-array reductions equal payload reductions.  Outputs are fp32
(the accumulation dtype); the ops slice the payload back out.

Batching rule.  Each op has ONE batch-gridded Pallas kernel, grid (B, NK):
the batch axis outermost, the N-blocks innermost and sequential.  An
unbatched op call runs it at B=1 (a leading unit axis in, ``[0]`` out: free
bitcasts).  ``pallas_call`` has no vmap rule, so `batch_gridded` wraps the
kernel in ``jax.custom_batching.custom_vmap``: ``jax.vmap`` over an op (the
Monte-Carlo trial axis of api.batch_fit) runs the same kernel at B = the
vmap size, nested vmaps fold into the one batch axis, and unbatched
operands are broadcast to the batch.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

__all__ = ["resolve_interpret", "pad_geometry", "pad2", "row_pack",
           "col_pack", "plate", "batch_gridded"]

_LANE = 128
_SUBLANE = 8


def resolve_interpret(explicit: Optional[bool] = None) -> bool:
    """``explicit`` if given, else True unless the default backend is TPU."""
    if explicit is not None:
        return bool(explicit)
    return jax.default_backend() != "tpu"


def _pad_to(x: int, m: int) -> int:
    return -(-x // m) * m


def pad_geometry(d: int, n: int, block_n: int):
    """(Dp, Np, bn) for a (d, n) residual matrix and a requested N-block."""
    bn = min(block_n, _pad_to(n, _LANE))
    return _pad_to(d, _SUBLANE), _pad_to(n, bn), bn


def pad2(x: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    """Zero-pad a 2-D array to (rows, cols)."""
    return jnp.zeros((rows, cols), x.dtype).at[:x.shape[0], :x.shape[1]].set(x)


def row_pack(v: jnp.ndarray, cols: int, dtype=None) -> jnp.ndarray:
    """(8, cols) row pack with ``v`` in row 0."""
    return jnp.zeros((8, cols), dtype or v.dtype).at[0, :v.shape[0]].set(v)


def col_pack(v: jnp.ndarray, rows: int) -> jnp.ndarray:
    """(rows, 8) column pack with ``v`` in column 0."""
    return jnp.zeros((rows, 8), v.dtype).at[:v.shape[0], 0].set(v)


def plate(*vals) -> jnp.ndarray:
    """(8, 128) f32 parameter plate with ``vals`` along row 0."""
    return row_pack(jnp.stack([jnp.asarray(v, jnp.float32) for v in vals]),
                    128)


def _broadcast(axis_size, in_batched, args):
    return tuple(a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                 for b, a in zip(in_batched, args))


def _all_batched(outs):
    return jax.tree.map(lambda _: True, outs)


@functools.lru_cache(maxsize=None)
def _gridded(kernel: Callable, block_n: int, interpret: bool):
    @custom_vmap
    def batched(*args):
        return kernel(*args, block_n=block_n, interpret=interpret)

    @batched.def_vmap
    def _nested(axis_size, in_batched, *args):
        args = _broadcast(axis_size, in_batched, args)
        lead = args[0].shape[:2]
        outs = batched(*(a.reshape((-1,) + a.shape[2:]) for a in args))
        outs = jax.tree.map(lambda o: o.reshape(lead + o.shape[1:]), outs)
        return outs, _all_batched(outs)

    @custom_vmap
    def call(*args):
        outs = batched(*(a[None] for a in args))
        return jax.tree.map(lambda o: o[0], outs)

    @call.def_vmap
    def _rule(axis_size, in_batched, *args):
        outs = batched(*_broadcast(axis_size, in_batched, args))
        return outs, _all_batched(outs)

    return call


def batch_gridded(kernel: Callable, block_n: int) -> Callable:
    """The padded single-trial call of a batch-gridded ``kernel(*operands,
    block_n=, interpret=)``: unbatched it runs the kernel at B=1, under
    ``jax.vmap`` (any depth) at B = the batch size."""
    return _gridded(kernel, block_n, resolve_interpret())
