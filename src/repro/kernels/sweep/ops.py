"""jit'd public wrappers for the fused sweep kernels: padding, dtype,
batching, fallback — the same discipline as kernels.gram.ops.

`use_pallas=False` (the default) runs the jnp oracle (ref.py), which is also
the fast CPU path of the fused sweep engine.  `use_pallas=True` routes to
the Pallas kernels; `interpret=None` auto-selects compiled-vs-interpreter
from the JAX backend via kernels.runtime.resolve_interpret (compiled on TPU,
interpreter elsewhere), overridable per call.

Packing contract (see kernel.py): D-vectors ride as (Dp, 8) column packs,
N-vectors as (8, Np) row packs, scalars on an (8, 128) parameter plate; all
padding is zeros so full-array reductions equal payload reductions, and the
wrappers slice the payload back out.  Kernel outputs are fp32 (accumulation
dtype) cast back to the residual dtype, like covstate.row_product.

Batching: `pallas_call` has no vmap rule, so the Pallas paths are wrapped in
`jax.custom_batching.custom_vmap` lowering to the `*_batched` kernels; the
rule re-enters a custom-vmap function so nested vmaps flatten into one batch
grid axis, and unbatched operands are broadcast.
"""
from __future__ import annotations

import functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.kernels.runtime import resolve_interpret
from repro.kernels.sweep import ref
from repro.kernels.sweep.kernel import (commit_sweep_pallas,
                                        commit_sweep_pallas_batched,
                                        probe_sweep_pallas,
                                        probe_sweep_pallas_batched)

__all__ = ["probe_sweep", "commit_sweep"]

_LANE = 128


def _pad_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _broadcast_unbatched(axis_size, in_batched, args):
    return tuple(a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                 for b, a in zip(in_batched, args))


@functools.lru_cache(maxsize=None)
def _probe_vmappable(block_n: int, interpret: bool):
    """Padded single-agent probe call with a vmap rule that reroutes batches
    (of any nesting depth) to the batch-gridded kernel."""

    @custom_vmap
    def call(rp, mp, sp, pars, steps):
        return tuple(probe_sweep_pallas(rp, mp, sp, pars, steps,
                                        block_n=block_n, interpret=interpret))

    @call.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = _broadcast_unbatched(axis_size, in_batched, args)
        return batched(*args), (True,) * 4

    @custom_vmap
    def batched(rp, mp, sp, pars, steps):
        return tuple(probe_sweep_pallas_batched(
            rp, mp, sp, pars, steps, block_n=block_n, interpret=interpret))

    @batched.def_vmap
    def _nested(axis_size, in_batched, *args):
        args = _broadcast_unbatched(axis_size, in_batched, args)
        lead = args[0].shape[:2]
        outs = batched(*(a.reshape((-1,) + a.shape[2:]) for a in args))
        return (tuple(o.reshape(lead + o.shape[1:]) for o in outs),
                (True,) * 4)

    return call


@functools.lru_cache(maxsize=None)
def _commit_vmappable(block_n: int, interpret: bool):
    """Batching wrapper for the fused commit call (same scheme as above)."""

    @custom_vmap
    def call(rp, dp_, mp, sp, pars):
        return tuple(commit_sweep_pallas(rp, dp_, mp, sp, pars,
                                         block_n=block_n, interpret=interpret))

    @call.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = _broadcast_unbatched(axis_size, in_batched, args)
        return batched(*args), (True,) * 4

    @custom_vmap
    def batched(rp, dp_, mp, sp, pars):
        return tuple(commit_sweep_pallas_batched(
            rp, dp_, mp, sp, pars, block_n=block_n, interpret=interpret))

    @batched.def_vmap
    def _nested(axis_size, in_batched, *args):
        args = _broadcast_unbatched(axis_size, in_batched, args)
        lead = args[0].shape[:2]
        outs = batched(*(a.reshape((-1,) + a.shape[2:]) for a in args))
        return (tuple(o.reshape(lead + o.shape[1:]) for o in outs),
                (True,) * 4)

    return call


def _pad_geometry(d: int, n: int, block_n: int):
    bn = min(block_n, _pad_to(n, _LANE))
    return _pad_to(d, _LANE), _pad_to(n, bn), bn


def _plate(*vals) -> jnp.ndarray:
    """(8, 128) f32 parameter plate with `vals` along row 0."""
    row = jnp.stack([jnp.asarray(v, jnp.float32) for v in vals])
    return jnp.zeros((8, 128), jnp.float32).at[0, :len(vals)].set(row)


@partial(jax.jit, static_argnames=("use_pallas", "interpret", "block_n"))
def probe_sweep(r: jnp.ndarray, m_inv: jnp.ndarray, s: jnp.ndarray,
                eta: jnp.ndarray, i, steps: jnp.ndarray,
                use_pallas: bool = False, interpret: Optional[bool] = None,
                block_n: int = 2048):
    """alpha=1 fused probe pass for agent i: one pass over r (D, N) yields
    (etas (K,), cross (N,), p (D,), gnorm ()) — the whole back-search
    schedule plus the gradient pieces (g_unit = (2 s_i / m / gnorm) * cross).

    Kernel path: fp32 accumulation cast back to the residual dtype; safe
    under `jax.vmap` (any depth) via the batch-gridded kernel.
    """
    d, n = r.shape
    k = steps.shape[0]
    if not use_pallas:
        return ref.probe_sweep_ref(r, m_inv, s, eta, i, steps)
    assert k <= 128, f"probe schedule ({k}) exceeds the 128-lane plate"
    dp, np_, bn = _pad_geometry(d, n, block_n)
    rp = jnp.zeros((dp, np_), r.dtype).at[:d, :n].set(r)
    mp = jnp.zeros((dp, dp), m_inv.dtype).at[:d, :d].set(m_inv)
    sp = jnp.zeros((dp, 8), s.dtype).at[:d, 0].set(s)
    pars = _plate(i, n, eta)
    stp = jnp.zeros((8, 128), jnp.float32).at[0, :k].set(steps)
    etas, cross, p, stats = _probe_vmappable(bn, resolve_interpret(interpret))(
        rp, mp, sp, pars, stp)
    return (etas[0, :k].astype(r.dtype), cross[0, :n].astype(r.dtype),
            p[:d, 0].astype(r.dtype), stats[0, 0].astype(r.dtype))


@partial(jax.jit, static_argnames=("use_pallas", "interpret", "block_n"))
def commit_sweep(r: jnp.ndarray, m_inv: jnp.ndarray, s: jnp.ndarray,
                 eta: jnp.ndarray, i, delta: jnp.ndarray, diag_keep,
                 diag_add, threshold, can_tx, use_pallas: bool = False,
                 interpret: Optional[bool] = None, block_n: int = 2048):
    """Fused accept/commit for agent i after its residual row moves by delta:
    one pass over r (D, N) yields (m_inv' (D, D), s' (D,), u_eff (D,),
    accept (bool), obj_post ()) with accept/reject folded in (rejection is
    an exact no-op).  See kernels.sweep.ref.commit_sweep_ref for semantics.
    """
    d, n = r.shape
    if not use_pallas:
        return ref.commit_sweep_ref(r, m_inv, s, eta, i, delta,
                                    diag_keep, diag_add, threshold, can_tx)
    dp, np_, bn = _pad_geometry(d, n, block_n)
    rp = jnp.zeros((dp, np_), r.dtype).at[:d, :n].set(r)
    dlt = jnp.zeros((8, np_), delta.dtype).at[0, :n].set(delta)
    mp = jnp.zeros((dp, dp), m_inv.dtype).at[:d, :d].set(m_inv)
    sp = jnp.zeros((dp, 8), s.dtype).at[:d, 0].set(s)
    pars = _plate(i, n, eta, diag_keep, diag_add, threshold, can_tx)
    minv_new, s_new, u_eff, stats = _commit_vmappable(
        bn, resolve_interpret(interpret))(rp, dlt, mp, sp, pars)
    return (minv_new[:d, :d].astype(m_inv.dtype),
            s_new[:d, 0].astype(s.dtype), u_eff[:d, 0].astype(s.dtype),
            stats[0, 1] > 0.5, stats[0, 0].astype(s.dtype))
