"""Gradient of the ICOA objective eta_tilde = 1^T A^{-1} 1 w.r.t. one agent's
prediction vector f_i.

The paper (Sec 3.1) derives a closed form through the adjoint matrix A* and
auxiliary B(k) matrices, and notes that numerical perturbation is an equally
valid estimator. We use exact reverse-mode autodiff through the covariance
assembly and the linear solve — mathematically identical to the closed form,
without the adjoint bookkeeping. `closed_form_gradient` implements the clean
matrix-calculus derivation below and is used by tests to cross-check autodiff
(the paper's printed formula contains an ambiguous index k; deriving from
scratch is safer than transcribing a likely typo):

    d eta / d A = -A^{-1} 1 1^T A^{-1}          (eta = 1^T A^{-1} 1)
    dA/df_i     = -(e_i r^T + r e_i^T)/N   component-wise through r_i = y - f_i

    => d eta / d f_i = (2/N) * [ (s s^T R)_i  ]   with s = A^{-1} 1, R = y - F
       i.e. grad_i = (2/N) * s_i * (s^T R)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.ensemble import eta_tilde_from_predictions

__all__ = ["agent_gradient", "all_agent_gradients", "closed_form_gradient",
           "cached_row_gradient"]


def agent_gradient(f: jnp.ndarray, y: jnp.ndarray, i: int) -> jnp.ndarray:
    """d eta_tilde / d f_i via autodiff; f: (D, N), returns (N,)."""

    def obj(fi: jnp.ndarray) -> jnp.ndarray:
        return eta_tilde_from_predictions(f.at[i].set(fi), y)

    return jax.grad(obj)(f[i])


def all_agent_gradients(f: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """d eta_tilde / d F for all agents at once; (D, N)."""
    return jax.grad(eta_tilde_from_predictions, argnums=0)(f, y)


def closed_form_gradient(f: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Matrix-calculus closed form (see module docstring); (D, N).

    grad_i = (2/N) * s_i * (s^T R),  s = A^{-1} 1,  R = y - F, A = R R^T / N.
    """
    d, n = f.shape
    r = y[None, :] - f
    a_mat = (r @ r.T) / n
    s = jnp.linalg.solve(a_mat + 1e-10 * jnp.eye(d, dtype=a_mat.dtype), jnp.ones((d,), a_mat.dtype))
    # d eta / d r_i = -2/N * s_i * (s^T R);  d r_i / d f_i = -1  => sign cancels
    return (2.0 / n) * s[:, None] * (s @ r)[None, :]


def cached_row_gradient(v: jnp.ndarray, r_sub: jnp.ndarray, i,
                        exclude_self: bool = False) -> jnp.ndarray:
    """Closed-form probe gradient off a CACHED inverse action (no solve).

    The incremental engine's form of the gradient above: v is the cached
    s = (A0 + jitter I)^{-1} 1 carried by core.covstate.CovState (or the
    robust weights a* under Minimax Protection — the Danskin term has the
    same shape with s -> a*), and r_sub the (D, m) transmitted residual rows.
    Returns d obj / d f_i over the transmitted positions,

        grad_i = (2/m) * v_i * (v^T R_sub),

    with `exclude_self=True` dropping the k = i term — required when the
    diagonal of A0 is maintained exactly from the full residuals (Sec 4.1
    split), because then A0_ii does not depend on the transmitted subsample
    and the caller adds the exact-diagonal term (2/N) v_i^2 r_i separately.
    """
    cross = jnp.matmul(v, r_sub, precision=jax.lax.Precision.HIGHEST)
    if exclude_self:
        cross = cross - v[i] * r_sub[i]
    return (2.0 / r_sub.shape[1]) * v[i] * cross
