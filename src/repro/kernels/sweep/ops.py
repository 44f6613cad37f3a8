"""jit'd public wrappers for the fused sweep kernels: padding, dtype and
batching — the layout, packing contract and batching rule of
kernels.runtime, as in kernels.gram.ops.

Kernel outputs are fp32 (the accumulation dtype) cast back to the residual
dtype, like covstate.row_product.  The jnp oracle (ref.py) is the fused
engine's path when ``use_kernel`` is off; core calls it directly.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.runtime import (batch_gridded, col_pack, pad2,
                                   pad_geometry, plate, row_pack)
from repro.kernels.sweep.kernel import commit_sweep_pallas, probe_sweep_pallas

__all__ = ["probe_sweep", "commit_sweep"]


@partial(jax.jit, static_argnames=("block_n",))
def probe_sweep(r: jnp.ndarray, m_inv: jnp.ndarray, s: jnp.ndarray,
                eta: jnp.ndarray, i, steps: jnp.ndarray, block_n: int = 2048):
    """alpha=1 fused probe pass for agent i: one pass over r (D, N) yields
    (etas (K,), cross (N,), p (D,), gnorm ()) — the whole back-search
    schedule plus the gradient pieces (g_unit = (2 s_i / m / gnorm) * cross).
    See kernels.sweep.ref.probe_sweep_ref for semantics.
    """
    d, n = r.shape
    k = steps.shape[0]
    assert k <= 128, f"probe schedule ({k}) exceeds the 128-lane plate"
    dp, np_, bn = pad_geometry(d, n, block_n)
    etas, cross, p, stats = batch_gridded(probe_sweep_pallas, bn)(
        pad2(r, dp, np_), pad2(m_inv, dp, dp), col_pack(s, dp),
        plate(i, n, eta), row_pack(steps, 128, jnp.float32))
    return (etas[0, :k].astype(r.dtype), cross[0, :n].astype(r.dtype),
            p[:d, 0].astype(r.dtype), stats[0, 0].astype(r.dtype))


@partial(jax.jit, static_argnames=("block_n",))
def commit_sweep(r: jnp.ndarray, m_inv: jnp.ndarray, s: jnp.ndarray,
                 eta: jnp.ndarray, i, delta: jnp.ndarray, diag_keep,
                 diag_add, threshold, can_tx, block_n: int = 2048):
    """Fused accept/commit for agent i after its residual row moves by delta:
    one pass over r (D, N) yields (m_inv' (D, D), s' (D,), u_eff (D,),
    accept (bool), obj_post ()) with accept/reject folded in (rejection is
    an exact no-op).  See kernels.sweep.ref.commit_sweep_ref for semantics.
    """
    d, n = r.shape
    dp, np_, bn = pad_geometry(d, n, block_n)
    minv_new, s_new, u_eff, stats = batch_gridded(commit_sweep_pallas, bn)(
        pad2(r, dp, np_), row_pack(delta, np_), pad2(m_inv, dp, dp),
        col_pack(s, dp),
        plate(i, n, eta, diag_keep, diag_add, threshold, can_tx))
    return (minv_new[:d, :d].astype(m_inv.dtype),
            s_new[:d, 0].astype(s.dtype), u_eff[:d, 0].astype(s.dtype),
            stats[0, 1] > 0.5, stats[0, 0].astype(s.dtype))
