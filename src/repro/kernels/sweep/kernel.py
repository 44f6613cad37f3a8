"""Pallas TPU kernels: the fused ICOA sweep inner loop (probe + commit).

Two kernels cover one agent update of `core.icoa._sweep_fused`:

`probe_sweep_pallas` — the whole back-search in one pass over the residual
matrix.  The probe direction is fixed per agent, so the closed-form schedule
of kernels.sweep.ref needs only (cross = s @ R, p = R @ cross, ||cross||^2)
— and all three come out of ONE read of R because the gradient normalisation
scalar factors out of p:

  * grid over N-blocks, R tile (Dp, BN) in VMEM; per block the (8, BN)
    cross-block is block-local (cross_blk = s @ R_blk), so p and ||cross||^2
    accumulate from it immediately:  acc_p += R_blk @ cross_blk^T,
    acc_gg += sum(cross_blk^2).  XLA cannot fuse these two dependent
    contractions into one memory pass; here the tile never leaves VMEM.
  * on the last block the ENTIRE probe schedule (every backtracked step)
    is evaluated in-core against the (Dp, Dp) m_inv resident in VMEM —
    `max_probes` objective probes with zero extra HBM traffic.

`commit_sweep_pallas` — row-Gram + accept/reject + symmetric rank-2 SMW
update in one pass: accumulates w = R @ delta / m and <delta, delta> over
the same N-grid, then applies the whole `covstate._smw_pieces` algebra
(post-projection objective probe, accept gate, rank-2 m_inv/s update) in-core
with accept folded into the coefficients (rejection multiplies the update by
zero — an exact no-op, matching the reference bit for bit in fp32).

Operands follow the packing contract of kernels.runtime: D-vectors as
(Dp, 8) column packs, N-vectors as (8, Np) row packs, scalars on an (8, 128)
parameter plate read back here via iota masks, all zero-padded.

VMEM at BN=2048: R tile Dp*2048*4 bytes (64 KiB at Dp=8, 832 KiB at the
D=100 fit's Dp=104) + m_inv Dp*Dp*4 + packs/accumulators — a D=100/N=2000
sweep is a single resident tile.  Both kernels compile for a TPU v5e up to
Dp=512 at BN=2048; at Dp=1024 they run out of VMEM, so D above 512 needs
tiling over D.

Both kernels are batch-gridded like kernels.gram's: every operand has a
leading B axis, grid (B, NK) with the batch outermost and the N-blocks
innermost-sequential, accumulators re-initialised per element.  A single
agent update runs at B=1.

No in-kernel determinant sanitisation: the checkify rail lives in the ref
oracle (kernels.sweep.ref) that validates this kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["probe_sweep_pallas", "commit_sweep_pallas"]

_F32 = jnp.float32

# full-precision f32 dots split their operands in VMEM; at Dp=512 and
# block_n=2048 that passes the default 16 MiB scoped limit (a v5e core has
# 128 MiB of VMEM)
_VMEM = pltpu.CompilerParams(vmem_limit_bytes=48 * 2**20)


def _dot(x, y, contract):
    """f32 product at full f32 precision.  At the default precision a TPU
    rounds f32 operands to bf16, which moves the back-search's accept
    decisions away from the f32 reference."""
    return jax.lax.dot_general(x, y, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _iota2(shape, dim):
    # Mosaic only builds integer iotas; the f32 cast is exact for any index.
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim).astype(_F32)


def _plate_scalar(plate, j: int):
    """Read entry (0, j) of an (8, 128) parameter plate via an iota mask."""
    mask = (_iota2(plate.shape, 0) == 0.0) & (_iota2(plate.shape, 1) == float(j))
    return jnp.sum(jnp.where(mask, plate, 0.0))


def _col0_entry(colpack, i_f):
    """Entry (i, 0) of a (Dp, 8) column pack, i given as an f32 scalar."""
    mask = (_iota2(colpack.shape, 0) == i_f) & (_iota2(colpack.shape, 1) == 0.0)
    return jnp.sum(jnp.where(mask, colpack, 0.0))


def _probe_finalize(minv, s_col, pars, steps, acc_p, acc_gg):
    """Last-block epilogue of the probe kernel: arrays in, (etas, p, stats)
    out — the caller owns the output writes."""
    i_f = _plate_scalar(pars, 0)
    m = _plate_scalar(pars, 1)
    eta = _plate_scalar(pars, 2)

    s_i = _col0_entry(s_col, i_f)
    gg_cross = _plate_scalar(acc_gg, 0)
    scale = 2.0 * s_i / m
    gnorm = jnp.sqrt(gg_cross) * jnp.abs(scale) + 1e-30
    p_col = acc_p * (scale / (m * gnorm))            # (Dp, 8): R @ g_unit / m

    q_col = _dot(minv, p_col, ((1,), (0,)))
    a = jnp.sum(p_col * q_col)                       # <p, q>: pad cols are zero
    b = _col0_entry(q_col, i_f)
    dmask = (_iota2(minv.shape, 0) == i_f) & (_iota2(minv.shape, 1) == i_f)
    c = jnp.sum(jnp.where(dmask, minv, 0.0))         # m_inv[i, i]
    e = jnp.sum(p_col * s_col)                       # <p, s>
    t1 = s_i
    gg = (scale / gnorm) ** 2 * gg_cross             # <g_unit, g_unit>
    c2h = gg / (2.0 * m)

    beta = c2h * steps * steps                       # alpha=1: c1h = 0
    k12 = 1.0 - steps * b + beta * c
    k22 = steps * steps * a - 2.0 * steps * beta * b + beta * beta * c
    t2 = -steps * e + beta * t1
    det = c * k22 - k12 * k12                        # zero-padded steps: det=-1
    etas = eta - (k22 * t1 * t1 - 2.0 * k12 * t1 * t2 + c * t2 * t2) / det
    col = _iota2(steps.shape, 1)
    stats = jnp.where(col == 0.0, gnorm, jnp.where(col == 1.0, scale, 0.0))
    return etas, p_col, stats


def _probe_batch_kernel(r_ref, minv_ref, s_ref, pars_ref, steps_ref,
                        etas_ref, cross_ref, p_ref, stats_ref,
                        acc_p, acc_gg, *, nk: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_p[...] = jnp.zeros_like(acc_p)
        acc_gg[...] = jnp.zeros_like(acc_gg)

    blk = r_ref[0].astype(_F32)
    s_col = s_ref[0].astype(_F32)
    cross_blk = _dot(s_col, blk, ((0,), (0,)))
    cross_ref[0] = cross_blk
    acc_p[...] += _dot(blk, cross_blk, ((1,), (1,)))
    acc_gg[...] += jnp.sum(cross_blk * cross_blk)

    @pl.when(k == nk - 1)
    def _flush():
        etas, p_col, stats = _probe_finalize(
            minv_ref[0].astype(_F32), s_col, pars_ref[0].astype(_F32),
            steps_ref[0].astype(_F32), acc_p[...], acc_gg[...])
        etas_ref[0] = etas
        p_ref[0] = p_col
        stats_ref[0] = stats


def probe_sweep_pallas(r, m_inv, s, pars, steps, *, block_n: int = 2048,
                       interpret: bool = True):
    """r: (B, Dp, Np), m_inv: (B, Dp, Dp), s: (B, Dp, 8), pars/steps:
    (B, 8, 128) with pars[:, 0, :3] = (i, m, eta) and steps[:, 0] the
    zero-padded schedule; Np a multiple of block_n.  Returns fp32 (etas
    (B, 8, 128), cross (B, 8, Np), p (B, Dp, 8), stats (B, 8, 128)) with
    stats[:, 0, :2] = (gnorm, scale)."""
    b, dp, np_ = r.shape
    assert np_ % block_n == 0, (np_, block_n)
    nk = np_ // block_n
    return pl.pallas_call(
        functools.partial(_probe_batch_kernel, nk=nk),
        grid=(b, nk),
        in_specs=[pl.BlockSpec((1, dp, block_n), lambda i, k: (i, 0, k)),
                  pl.BlockSpec((1, dp, dp), lambda i, k: (i, 0, 0)),
                  pl.BlockSpec((1, dp, 8), lambda i, k: (i, 0, 0)),
                  pl.BlockSpec((1, 8, 128), lambda i, k: (i, 0, 0)),
                  pl.BlockSpec((1, 8, 128), lambda i, k: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1, 8, 128), lambda i, k: (i, 0, 0)),
                   pl.BlockSpec((1, 8, block_n), lambda i, k: (i, 0, k)),
                   pl.BlockSpec((1, dp, 8), lambda i, k: (i, 0, 0)),
                   pl.BlockSpec((1, 8, 128), lambda i, k: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, 8, 128), _F32),
                   jax.ShapeDtypeStruct((b, 8, np_), _F32),
                   jax.ShapeDtypeStruct((b, dp, 8), _F32),
                   jax.ShapeDtypeStruct((b, 8, 128), _F32)],
        scratch_shapes=[pltpu.VMEM((dp, 8), _F32),
                        pltpu.VMEM((8, 128), _F32)],
        compiler_params=_VMEM,
        interpret=interpret,
    )(r, m_inv, s, pars, steps)


def _commit_finalize(minv, s_col, pars, acc_w, acc_dd):
    """Last-block epilogue of the commit kernel: arrays in, (m_inv', s',
    u_eff, stats) out — the caller owns the writes."""
    i_f = _plate_scalar(pars, 0)
    m = _plate_scalar(pars, 1)
    eta = _plate_scalar(pars, 2)
    diag_keep = _plate_scalar(pars, 3)
    diag_add = _plate_scalar(pars, 4)
    threshold = _plate_scalar(pars, 5)
    can_tx = _plate_scalar(pars, 6)

    w = acc_w / m                                    # (Dp, 8): R @ delta / m
    dd_auto = _plate_scalar(acc_dd, 0) / (2.0 * m)
    rowmask = _iota2(w.shape, 0) == i_f
    cellmask = rowmask & (_iota2(w.shape, 1) == 0.0)
    w_i = jnp.sum(jnp.where(cellmask, w, 0.0))
    u = jnp.where(cellmask, diag_keep * (w_i + dd_auto) + diag_add, w)

    e_col = jnp.where(cellmask, 1.0, 0.0)            # (Dp, 8): e_i in column 0
    z1 = _dot(minv, e_col, ((1,), (0,)))
    z2 = _dot(minv, u, ((1,), (0,)))
    dmask = (_iota2(minv.shape, 0) == i_f) & (_iota2(minv.shape, 1) == i_f)
    k11 = jnp.sum(jnp.where(dmask, minv, 0.0))
    k12 = 1.0 + _col0_entry(z2, i_f)
    k22 = jnp.sum(u * z2)
    det = k11 * k22 - k12 * k12
    t1 = _col0_entry(s_col, i_f)
    t2 = jnp.sum(u * s_col)
    obj_post = eta - (k22 * t1 * t1 - 2.0 * k12 * t1 * t2
                      + k11 * t2 * t2) / det
    acc = jnp.where((obj_post > threshold) & (can_tx > 0.5), 1.0, 0.0)

    def outer(x, y):                                 # (Dp,8)x(Dp,8) -> (Dp,Dp)
        return _dot(x, y, ((1,), (1,)))

    corr = (k22 * outer(z1, z1) - k12 * (outer(z1, z2) + outer(z2, z1))
            + k11 * outer(z2, z2)) / det
    minv_new = minv - acc * corr
    c1 = acc * (k22 * t1 - k12 * t2) / det
    c2 = acc * (k11 * t2 - k12 * t1) / det
    s_new = s_col - c1 * z1 - c2 * z2
    col = _iota2(pars.shape, 1)
    stats = jnp.where(col == 0.0, obj_post, jnp.where(col == 1.0, acc, 0.0))
    return minv_new, s_new, acc * u, stats


def _commit_batch_kernel(r_ref, delta_ref, minv_ref, s_ref, pars_ref,
                         minv_out, s_out, u_out, stats_ref,
                         acc_w, acc_dd, *, nk: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_w[...] = jnp.zeros_like(acc_w)
        acc_dd[...] = jnp.zeros_like(acc_dd)

    blk = r_ref[0].astype(_F32)
    dblk = delta_ref[0].astype(_F32)
    acc_w[...] += _dot(blk, dblk, ((1,), (1,)))
    acc_dd[...] += jnp.sum(dblk * dblk)

    @pl.when(k == nk - 1)
    def _flush():
        minv_new, s_new, u_eff, stats = _commit_finalize(
            minv_ref[0].astype(_F32), s_ref[0].astype(_F32),
            pars_ref[0].astype(_F32), acc_w[...], acc_dd[...])
        minv_out[0] = minv_new
        s_out[0] = s_new
        u_out[0] = u_eff
        stats_ref[0] = stats


def commit_sweep_pallas(r, delta, m_inv, s, pars, *, block_n: int = 2048,
                        interpret: bool = True):
    """r: (B, Dp, Np), delta: (B, 8, Np), m_inv: (B, Dp, Dp), s: (B, Dp, 8),
    pars: (B, 8, 128) with pars[:, 0, :7] = (i, m, eta, diag_keep, diag_add,
    threshold, can_tx).  Returns fp32 (m_inv' (B, Dp, Dp), s' (B, Dp, 8),
    u_eff (B, Dp, 8), stats (B, 8, 128)) with stats[:, 0, :2] = (obj_post,
    accept)."""
    b, dp, np_ = r.shape
    assert np_ % block_n == 0, (np_, block_n)
    nk = np_ // block_n
    return pl.pallas_call(
        functools.partial(_commit_batch_kernel, nk=nk),
        grid=(b, nk),
        in_specs=[pl.BlockSpec((1, dp, block_n), lambda i, k: (i, 0, k)),
                  pl.BlockSpec((1, 8, block_n), lambda i, k: (i, 0, k)),
                  pl.BlockSpec((1, dp, dp), lambda i, k: (i, 0, 0)),
                  pl.BlockSpec((1, dp, 8), lambda i, k: (i, 0, 0)),
                  pl.BlockSpec((1, 8, 128), lambda i, k: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1, dp, dp), lambda i, k: (i, 0, 0)),
                   pl.BlockSpec((1, dp, 8), lambda i, k: (i, 0, 0)),
                   pl.BlockSpec((1, dp, 8), lambda i, k: (i, 0, 0)),
                   pl.BlockSpec((1, 8, 128), lambda i, k: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, dp, dp), _F32),
                   jax.ShapeDtypeStruct((b, dp, 8), _F32),
                   jax.ShapeDtypeStruct((b, dp, 8), _F32),
                   jax.ShapeDtypeStruct((b, 8, 128), _F32)],
        scratch_shapes=[pltpu.VMEM((dp, 8), _F32),
                        pltpu.VMEM((8, 128), _F32)],
        compiler_params=_VMEM,
        interpret=interpret,
    )(r, delta, m_inv, s, pars)
