"""Pure-jnp oracle for the fused sweep kernels (probe schedule + commit).

These are the mathematical contracts the Pallas kernels in kernel.py
implement; the fused sweep engine (core.icoa._sweep_fused) runs this exact
algebra on CPU and routes through the kernels on TPU.  Everything here is a
closed form of operations the incremental engine (core.covstate) performs
sequentially:

  * `probe_etas_closed` — the whole back-search schedule at once.  The probe
    direction is fixed, so u(step) = -step * p_hat + beta(step) * e_i and
    every `covstate.eta_probe` of the back-search collapses to ONE cached
    matvec q = m_inv @ p_hat plus scalar algebra per step:

        beta = c2h*step^2 + c1h*step          (alpha=1: c1h=0, c2h=gg/2m;
                                               Sec 4.1 split: c1h=-c1/n,
                                               c2h=0.5/n, p_hat_i = 0)
        k12  = 1 - step*b + beta*c            b = q_i, c = m_inv_ii
        k22  = step^2*a - 2*step*beta*b + beta^2*c      a = <p_hat, q>
        t2   = -step*e + beta*t1              e = <p_hat, s>, t1 = s_i
        det  = c*k22 - k12^2
        eta' = eta - (k22*t1^2 - 2*k12*t1*t2 + c*t2^2) / det

  * `probe_sweep_ref` — the alpha=1 probe pass: gradient cross-product,
    row product p and gradient norm out of ONE conceptual read of r_sub
    (cross = s @ R; p and ||cross||^2 accumulate from cross blockwise, and
    the normalisation scalar factors out — this is what lets the Pallas
    kernel fuse both contractions into a single VMEM-resident pass).

  * `commit_sweep_ref` — row-Gram + accept/reject + symmetric rank-2 SMW
    fold in one evaluation of the `covstate._smw_pieces` algebra.  The
    accept gate multiplies into the update coefficients, so a rejected
    candidate leaves (m_inv, s) bitwise untouched (x - 0.0 == x) and an
    accepted one matches `covstate.apply_inverse_update` bit for bit — no
    double-buffered jnp.where over the whole state.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.analysis import sanitize

__all__ = ["probe_etas_closed", "probe_sweep_ref", "commit_sweep_ref"]

# N-length contractions at full f32 precision (the TPU default rounds
# through bf16 passes), matching the kernels they validate
_HIGHEST = jax.lax.Precision.HIGHEST


def probe_etas_closed(m_inv: jnp.ndarray, s: jnp.ndarray, eta: jnp.ndarray,
                      i, steps: jnp.ndarray, p_hat: jnp.ndarray,
                      c1h, c2h) -> jnp.ndarray:
    """eta_tilde after u(step) = -step*p_hat + (c2h*step^2 + c1h*step)*e_i,
    for every step in the schedule at once — (K,) from one O(D^2) matvec."""
    q = m_inv @ p_hat
    a = jnp.vdot(p_hat, q)
    b = q[i]
    c = m_inv[i, i]
    e = jnp.vdot(p_hat, s)
    t1 = s[i]
    beta = c2h * steps * steps + c1h * steps
    k12 = 1.0 - steps * b + beta * c
    k22 = steps * steps * a - 2.0 * steps * beta * b + beta * beta * c
    t2 = -steps * e + beta * t1
    det = c * k22 - k12 * k12
    det = sanitize.check_nonzero(
        det, "kernels.sweep probe_etas_closed: SMW pivot determinant "
        "(the whole back-search schedule divides by it)")
    return eta - (k22 * t1 * t1 - 2.0 * k12 * t1 * t2 + c * t2 * t2) / det


def probe_sweep_ref(r_sub: jnp.ndarray, m_inv: jnp.ndarray, s: jnp.ndarray,
                    eta: jnp.ndarray, i, steps: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                               jnp.ndarray]:
    """alpha=1 fused probe pass: (etas (K,), cross (m,), p (D,), gnorm ()).

    cross = s @ R is the unnormalised gradient direction (the caller forms
    g_unit = (scale/gnorm) * cross); p = R @ g_unit / m feeds the closed-form
    schedule.  All products with r_sub happen here — the Pallas twin does
    them in one pass with r_sub resident in VMEM.
    """
    m = r_sub.shape[1]
    cross = jnp.matmul(s, r_sub, precision=_HIGHEST)
    p_acc = jnp.matmul(r_sub, cross, precision=_HIGHEST)  # = m * A0 @ s
    gg_cross = jnp.vdot(cross, cross, precision=_HIGHEST)
    scale = (2.0 / m) * s[i]
    gnorm = jnp.sqrt(gg_cross) * jnp.abs(scale) + 1e-30
    p = (scale / (m * gnorm)) * p_acc          # R @ g_unit / m
    gg = (scale / gnorm) ** 2 * gg_cross       # <g_unit, g_unit>
    etas = probe_etas_closed(m_inv, s, eta, i, steps, p,
                             jnp.zeros((), p.dtype), gg / (2.0 * m))
    return etas, cross, p, gnorm


def commit_sweep_ref(r_sub: jnp.ndarray, m_inv: jnp.ndarray, s: jnp.ndarray,
                     eta: jnp.ndarray, i, delta: jnp.ndarray,
                     diag_keep, diag_add, threshold, can_tx
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                jnp.ndarray, jnp.ndarray]:
    """Fused accept/commit: returns (m_inv', s', u_eff, accept, obj_post).

    u_i = diag_keep * (w_i + <delta,delta>/2m) + diag_add covers both diagonal
    regimes (alpha=1: keep=1/add=0; Sec 4.1 split: keep=0/add=0.5*ddiag).
    `threshold` is the accept bar (eta0, or -inf to disable accept/reject);
    `can_tx` the transport-budget gate.  The same `_smw_pieces` evaluation
    serves the post-projection objective probe AND the commit, with accept
    folded into the coefficients — rejection is an exact no-op.
    """
    m = r_sub.shape[1]
    w = jnp.matmul(r_sub, delta, precision=_HIGHEST) / m
    dd_auto = jnp.vdot(delta, delta, precision=_HIGHEST) / (2.0 * m)
    u = w.at[i].set(diag_keep * (w[i] + dd_auto) + diag_add)

    z1 = m_inv[i]
    z2 = m_inv @ u
    k11 = m_inv[i, i]
    k12 = 1.0 + z2[i]
    k22 = jnp.vdot(u, z2)
    det = k11 * k22 - k12 * k12
    det = sanitize.check_nonzero(
        det, "kernels.sweep commit_sweep_ref: SMW pivot determinant "
        "(the accept probe and the rank-2 commit divide by it)")
    t1 = s[i]
    t2 = jnp.vdot(u, s)
    obj_post = eta - (k22 * t1 * t1 - 2.0 * k12 * t1 * t2
                      + k11 * t2 * t2) / det
    accept = jnp.logical_and(obj_post > threshold, can_tx)

    zero = jnp.zeros((), m_inv.dtype)
    corr = (k22 * jnp.outer(z1, z1)
            - k12 * (jnp.outer(z1, z2) + jnp.outer(z2, z1))
            + k11 * jnp.outer(z2, z2)) / det
    m_inv_new = m_inv - jnp.where(accept, corr, zero)
    c1 = jnp.where(accept, (k22 * t1 - k12 * t2) / det, zero)
    c2 = jnp.where(accept, (k11 * t2 - k12 * t1) / det, zero)
    s_new = s - c1 * z1 - c2 * z2
    u_eff = jnp.where(accept, u, jnp.zeros_like(u))
    return m_inv_new, s_new, u_eff, accept, obj_post
