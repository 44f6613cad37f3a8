"""Pallas TPU kernel: blocked Gram matrix R @ R^T for residual covariance.

This is the paper's per-sweep compute hot-spot (eq. 14): D agent residual
vectors of N instances each, N >> D. TPU mapping:

  * grid over N-blocks; each step loads one (Dp, BN) tile of R into VMEM
    (Dp = D padded to the 8-row fp32 sublane multiple by the wrapper, BN a
    multiple of 128) and issues a (Dp, BN) x (BN, Dp) MXU matmul;
  * a (Dp, Dp) fp32 VMEM scratch accumulates across grid steps (the N axis is
    the sequential innermost grid dim), written out on the last step.

VMEM budget at the default BN=2048: the tile is Dp*2048*4 bytes, 64 KiB at
Dp=8 (D<=8) and 1 MiB at Dp=128, plus a (Dp, Dp) scratch — comfortably
inside the ~16 MiB/core VMEM.  Dp=1024 at BN=2048 does not fit a v5e's
VMEM; the main path stays at D<=512 until D is tiled.

Each kernel is batch-gridded, grid (B, NK) with the batch outermost and the
N-blocks innermost-sequential, so a whole Monte-Carlo trial batch runs as
ONE launch: each batch step re-initialises the VMEM accumulator at its first
N-block and flushes at its last, reusing the same scratch across batch
elements.  A single trial runs at B=1; kernels.runtime holds the layout and
the batching rule the ops in ops.py use.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gram_pallas", "row_gram_pallas"]

# full-precision f32 dots split their operands in VMEM; at Dp=512 and
# block_n=2048 that passes the default 16 MiB scoped limit (a v5e core has
# 128 MiB of VMEM)
_VMEM = pltpu.CompilerParams(vmem_limit_bytes=48 * 2**20)


def _dot(x, y):
    """x @ y^T at full f32 precision (the TPU default rounds f32 operands to
    bf16, which the covariance inverse downstream amplifies)."""
    return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _gram_batch_kernel(r_ref, out_ref, acc_ref, *, nk: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    blk = r_ref[0].astype(jnp.float32)          # (Dp, BN)
    acc_ref[...] += _dot(blk, blk)

    @pl.when(k == nk - 1)
    def _flush():
        out_ref[0] = acc_ref[...]


def gram_pallas(r: jnp.ndarray, *, block_n: int = 2048,
                interpret: bool = True) -> jnp.ndarray:
    """r: (B, Dp, Np), Np a multiple of block_n -> fp32 (B, Dp, Dp).

    The accumulator scratch carries within one batch element and is
    re-zeroed at each element's first N-block, so the batch axis needs no
    VMEM beyond one element's.
    """
    b, dp, np_ = r.shape
    assert np_ % block_n == 0, (np_, block_n)
    nk = np_ // block_n
    return pl.pallas_call(
        functools.partial(_gram_batch_kernel, nk=nk),
        grid=(b, nk),
        in_specs=[pl.BlockSpec((1, dp, block_n), lambda i, k: (i, 0, k))],
        out_specs=pl.BlockSpec((1, dp, dp), lambda i, k: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, dp, dp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dp, dp), jnp.float32)],
        compiler_params=_VMEM,
        interpret=interpret,
    )(r)


def _row_gram_batch_kernel(r_ref, v_ref, out_ref, acc_ref, *, nk: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    blk = r_ref[0].astype(jnp.float32)           # (Dp, BN)
    vec = v_ref[0].astype(jnp.float32)           # (8, BN); row 0 is the payload
    acc_ref[...] += _dot(blk, vec)

    @pl.when(k == nk - 1)
    def _flush():
        out_ref[0] = acc_ref[...]


def row_gram_pallas(r: jnp.ndarray, v: jnp.ndarray, *, block_n: int = 2048,
                    interpret: bool = True) -> jnp.ndarray:
    """Fused row-Gram r_i @ R^T: the one unavoidable O(N*D) product of the
    incremental covariance engine's rank-2 row update (DESIGN.md §5).

    r: (B, Dp, Np), v: (B, 8, Np) with the probe row in v[:, 0] and zero
    padding below (8 = fp32 sublane width); Np a multiple of block_n.
    Returns fp32 (B, Dp, 8) whose column 0 is R @ v[0].  Same grid and
    accumulator discipline as `gram_pallas`; the (Dp, BN) x (BN, 8) product
    rides the MXU with the vector broadcast across sublanes.
    """
    b, dp, np_ = r.shape
    assert np_ % block_n == 0, (np_, block_n)
    assert v.shape == (b, 8, np_), (v.shape, r.shape)
    nk = np_ // block_n
    return pl.pallas_call(
        functools.partial(_row_gram_batch_kernel, nk=nk),
        grid=(b, nk),
        in_specs=[pl.BlockSpec((1, dp, block_n), lambda i, k: (i, 0, k)),
                  pl.BlockSpec((1, 8, block_n), lambda i, k: (i, 0, k))],
        out_specs=pl.BlockSpec((1, dp, 8), lambda i, k: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, dp, 8), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dp, 8), jnp.float32)],
        compiler_params=_VMEM,
        interpret=interpret,
    )(r, v)
