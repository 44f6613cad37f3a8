"""Incremental covariance engine: rank-2 row updates of the ICOA solve state.

ICOA's inner loop is "reshape the covariance matrix of the training residuals"
(paper Sec 3.1): only agent i's residual row changes per update, so A moves by
a symmetric rank-2 perturbation

    A' = A + e_i u^T + u e_i^T,

and the cached inverse action follows by Sherman-Morrison-Woodbury in O(D^2)
instead of a fresh O(N*D^2) Gram + O(D^3) solve.  `CovState` is the immutable
carrier of everything a sweep needs:

    r_sub      (D, m) transmitted residual rows (m = N or N/alpha)
    a0         (D, D) covariance estimate, exact-diagonal split included
                      (Sec 4.1: off-diagonals from the subsample, local
                      diagonal exact)
    m_inv      (D, D) inverse of (a0 + jitter I) — same jitter as
                      ensemble._solve_ones, so the dense path is the oracle
    s          (D,)   m_inv @ 1, the cached solve the closed-form gradient and
                      eta_tilde both read
    eta_tilde  ()     1^T (a0 + jitter I)^{-1} 1, the ICOA objective

`eta_probe`/`s_probe` evaluate a hypothetical row change WITHOUT committing
(the back-search's objective probes); `replace_row`/`apply_row_update` commit
one.  The single O(N*D) product per update (delta row against every residual
row) is served by the fused `row_gram` Pallas op when `use_kernel=True`.

The streaming subsystem (repro.stream) moves along the OTHER axis: one
*instance* (one column of r_sub) arrives or is evicted, so A0 = R R^T / m
moves by the symmetric difference (c c^T - c' c'^T)/m — two rank-ONE
Sherman–Morrison updates of the cached inverse action.  `replace_col`
commits one such column swap in O(D^2); a zero outgoing column makes it a
pure append (the ring buffer's warm-up regime).

Numerical contract: m_inv/s drift by O(eps) per committed update, so callers
refresh once per sweep (rebuilding the state at sweep start — see
core.icoa/_sweep_incremental) to bound the drift; `refresh` re-solves in
place for long-lived states.  DESIGN.md §5 has the complexity table.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.analysis import sanitize
from repro.core import covariance as cov
from repro.core.ensemble import _JITTER

__all__ = ["CovState", "build", "refresh", "row_product", "row_update_vector",
           "eta_probe", "s_probe", "robust_eta_probe", "apply_inverse_update",
           "apply_row_update", "replace_row", "replace_col"]

# contractions over the instances at full f32 precision: the TPU default
# rounds f32 products through bf16 passes (DESIGN.md §10.2)
_HIGHEST = jax.lax.Precision.HIGHEST


class CovState(NamedTuple):
    """Immutable covariance solve state (a pytree — jit/shard_map friendly)."""

    r_sub: jnp.ndarray       # (D, m) residual matrix view (transmitted rows)
    a0: jnp.ndarray          # (D, D) covariance with the Sec 4.1 diag split
    m_inv: jnp.ndarray       # (D, D) = (a0 + jitter I)^{-1}
    s: jnp.ndarray           # (D,)   = m_inv @ 1
    eta_tilde: jnp.ndarray   # ()     = sum(s)


def row_product(vec: jnp.ndarray, r_sub: jnp.ndarray,
                use_kernel: bool = False) -> jnp.ndarray:
    """(m,), (D, m) -> (D,) = R @ vec — the engine's one O(N*D) product.

    Kernel path: fp32 accumulation, cast back to the residual dtype (same
    dtype discipline as covariance.gram)."""
    if use_kernel:
        from repro.kernels.gram import ops as gram_ops

        return gram_ops.row_gram(vec, r_sub).astype(r_sub.dtype)
    return jnp.matmul(r_sub, vec, precision=_HIGHEST)


def _with_solve(r_sub: jnp.ndarray, a0: jnp.ndarray) -> CovState:
    d = a0.shape[0]
    m_inv = jnp.linalg.inv(a0 + _JITTER * jnp.eye(d, dtype=a0.dtype))
    m_inv = 0.5 * (m_inv + m_inv.T)   # the SMW update assumes exact symmetry
    s = m_inv @ jnp.ones((d,), a0.dtype)
    return CovState(r_sub=r_sub, a0=a0, m_inv=m_inv, s=s, eta_tilde=jnp.sum(s))


def build(r_sub: jnp.ndarray, exact_diag: Optional[jnp.ndarray] = None,
          use_kernel: bool = False) -> CovState:
    """Full O(N*D^2 + D^3) construction — the once-per-sweep refresh.

    `exact_diag` (sum(r_i^2)/N over the FULL residuals) activates the Sec 4.1
    split: off-diagonals from the transmitted subsample, diagonal exact.
    """
    if exact_diag is not None:
        a0 = cov.spliced_gram(r_sub, exact_diag, use_kernel=use_kernel)
    else:
        a0 = cov.gram(r_sub, use_kernel=use_kernel)
    return _with_solve(r_sub, a0)


def refresh(state: CovState) -> CovState:
    """Re-solve m_inv/s from a0, discarding accumulated SMW drift."""
    return _with_solve(state.r_sub, state.a0)


def row_update_vector(state: CovState, i, delta_sub: jnp.ndarray,
                      ddiag: Optional[jnp.ndarray] = None,
                      use_kernel: bool = False) -> jnp.ndarray:
    """u with A0' = A0 + e_i u^T + u e_i^T after row i's residual moves by
    delta_sub.  `ddiag=None` means the diagonal comes from the same Gram as
    the off-diagonals (alpha = 1); otherwise it is the change of the exact
    local diagonal (pass 0.0 to hold the diagonal fixed, as the distributed
    objective does during probes).  One row_gram product — O(N*D)."""
    m = state.r_sub.shape[1]
    w = row_product(delta_sub, state.r_sub, use_kernel=use_kernel) / m
    if ddiag is None:
        return w.at[i].add(jnp.vdot(delta_sub, delta_sub,
                                  precision=_HIGHEST) / (2.0 * m))
    return w.at[i].set(0.5 * ddiag)


def _smw_pieces(state: CovState, i, u: jnp.ndarray):
    """Shared algebra of (A0' + jitter I)^{-1} = M - Z K^{-1} Z^T with
    Z = M [e_i, u] and K = C^{-1} + [e_i, u]^T M [e_i, u], C = [[0,1],[1,0]]."""
    z1 = state.m_inv[i]                    # M e_i (M symmetric)
    z2 = state.m_inv @ u
    k11 = state.m_inv[i, i]
    k12 = 1.0 + z2[i]
    k22 = jnp.vdot(u, z2)
    det = k11 * k22 - k12 * k12
    det = sanitize.check_nonzero(
        det, "covstate._smw_pieces: SMW pivot determinant "
        "(eta_probe / s_probe / apply_row_update divide by it)")
    return z1, z2, k11, k12, k22, det


def eta_probe(state: CovState, i, u: jnp.ndarray) -> jnp.ndarray:
    """eta_tilde after a hypothetical row-i update u — O(D^2), no commit."""
    _, z2, k11, k12, k22, det = _smw_pieces(state, i, u)
    t1, t2 = state.s[i], jnp.vdot(u, state.s)
    return state.eta_tilde - (k22 * t1 * t1 - 2.0 * k12 * t1 * t2
                              + k11 * t2 * t2) / det


def s_probe(state: CovState, i, u: jnp.ndarray) -> jnp.ndarray:
    """(A0' + jitter I)^{-1} 1 after a hypothetical row-i update u — O(D^2)."""
    z1, z2, k11, k12, k22, det = _smw_pieces(state, i, u)
    t1, t2 = state.s[i], jnp.vdot(u, state.s)
    c1 = (k22 * t1 - k12 * t2) / det
    c2 = (k11 * t2 - k12 * t1) / det
    return state.s - c1 * z1 - c2 * z2


def robust_eta_probe(state: CovState, i, u: jnp.ndarray, delta: float,
                     steps: int, lr: float) -> jnp.ndarray:
    """Minimax-protected objective (-zeta, paper eq. 24) after a hypothetical
    row-i update u — the protected twin of `eta_probe`, shared by both sweep
    engines so their Danskin surrogates cannot drift apart.  a* is re-solved
    on the perturbed A0 exactly as the dense objective does, warm-started from
    the SMW solve instead of a fresh O(D^3) factorisation."""
    from repro.core import minimax   # lazy: minimax -> ensemble/covariance only

    a0p = state.a0.at[i, :].add(u).at[:, i].add(u)
    sp = s_probe(state, i, u)
    ap = minimax.robust_weights(a0p, delta, steps=steps, lr=lr,
                                a_init=sp / jnp.sum(sp))
    return -minimax.robust_objective(ap, a0p, delta)


def apply_inverse_update(state: CovState, i, u: jnp.ndarray):
    """The solve-state half of a commit: (m_inv', s', eta_tilde') after the
    rank-2 row-i perturbation u — O(D^2), no residual/a0 bookkeeping.

    Split out of `apply_row_update` so the fused sweep engine (and the Pallas
    commit kernel's reference path, kernels.sweep.ref) can fold accept/reject
    into the SAME pieces it used for the post-projection objective probe:
    both read one `_smw_pieces` evaluation, so a rejected candidate is an
    exact no-op and an accepted one bit-matches the incremental engine.
    """
    z1, z2, k11, k12, k22, det = _smw_pieces(state, i, u)
    m_inv = state.m_inv - (k22 * jnp.outer(z1, z1)
                           - k12 * (jnp.outer(z1, z2) + jnp.outer(z2, z1))
                           + k11 * jnp.outer(z2, z2)) / det
    t1, t2 = state.s[i], jnp.vdot(u, state.s)
    c1 = (k22 * t1 - k12 * t2) / det
    c2 = (k11 * t2 - k12 * t1) / det
    s = state.s - c1 * z1 - c2 * z2
    return m_inv, s, jnp.sum(s)


def apply_row_update(state: CovState, i, r_new_sub: jnp.ndarray,
                     u: jnp.ndarray) -> CovState:
    """Commit a row change whose update vector u is already in hand — O(D^2)."""
    a0 = state.a0.at[i, :].add(u).at[:, i].add(u)   # (i,i) gains 2 u_i: correct
    m_inv, s, eta = apply_inverse_update(state, i, u)
    return CovState(r_sub=state.r_sub.at[i].set(r_new_sub), a0=a0,
                    m_inv=m_inv, s=s, eta_tilde=eta)


def _rank1_inverse_update(m_inv: jnp.ndarray, s: jnp.ndarray, v: jnp.ndarray,
                          sign: float):
    """(m_inv', s') after A0 += sign * v v^T — one Sherman–Morrison step.

    m_inv is symmetric, so w = M v serves both sides of the correction and
    s' = M' 1 follows from the same pieces without a fresh solve.  sign is a
    STATIC +/-1 (update vs downdate), so it folds into the trace."""
    w = m_inv @ v
    denom = 1.0 + sign * jnp.vdot(v, w)
    denom = sanitize.check_nonzero(
        denom, "covstate._rank1_inverse_update: Sherman-Morrison pivot "
        "(replace_col divides by it; an exactly-singular downdate means the "
        "evicted instance carried the whole window's mass)")
    coef = sign / denom
    return m_inv - coef * jnp.outer(w, w), s - (coef * jnp.vdot(v, s)) * w


def replace_col(state: CovState, j, c_new: jnp.ndarray) -> CovState:
    """Replace instance column j of r_sub — the streaming ring buffer's
    per-arrival commit (repro.stream), O(D^2) with NO pass over the window.

    A0' = A0 + (c_new c_new^T - c_old c_old^T)/m: one rank-1 update for the
    arriving instance, one rank-1 downdate for the evicted one.  A zero
    outgoing column (the ring's empty-slot placeholder during warm-up) makes
    the downdate an exact no-op, so append and evict-replace are the same
    operation.  m_inv/s/eta_tilde drift by O(eps) per commit like the row
    path; the stream's once-per-resweep `build` refresh bounds it.

    Only the alpha = 1 state shape is supported: the Sec 4.1 spliced
    diagonal tracks FULL-residual row norms that a window column swap cannot
    see, so streaming states are built without `exact_diag`.
    """
    m = state.r_sub.shape[1]
    inv_sqrt_m = 1.0 / math.sqrt(m)
    c_old = state.r_sub[:, j]
    m_inv, s = _rank1_inverse_update(state.m_inv, state.s,
                                     c_new * inv_sqrt_m, 1.0)
    m_inv, s = _rank1_inverse_update(m_inv, s, c_old * inv_sqrt_m, -1.0)
    a0 = state.a0 + (jnp.outer(c_new, c_new) - jnp.outer(c_old, c_old)) / m
    return CovState(r_sub=state.r_sub.at[:, j].set(c_new), a0=a0,
                    m_inv=m_inv, s=s, eta_tilde=jnp.sum(s))


def replace_row(state: CovState, i, r_new_sub: jnp.ndarray,
                new_diag: Optional[jnp.ndarray] = None,
                use_kernel: bool = False) -> CovState:
    """Replace residual row i, updating a0/m_inv/s/eta_tilde in
    O(N*D + D^2) — the engine's public commit operation."""
    delta = r_new_sub - state.r_sub[i]
    ddiag = None if new_diag is None else new_diag - state.a0[i, i]
    u = row_update_vector(state, i, delta, ddiag=ddiag, use_kernel=use_kernel)
    return apply_row_update(state, i, r_new_sub, u)
