"""Residual covariance estimation (paper eq. 14) — full and alpha-compressed.

Residuals are held as R in R^{D x N} (one row per agent). The covariance used
throughout the paper is the *uncentered* second moment of the residuals,

    A_ij = (1/N) (y - f_i)^T (y - f_j) = (1/N) r_i^T r_j,

consistent with eq. 14 and the unbiasedness assumption E[r_i] = 0.

`subsampled_covariance` implements the Minimax-Protection transport: only
N/alpha instances are exchanged between agents, so off-diagonal entries are
estimated from the subsample while diagonal entries (local, free) stay exact —
this is the paper's delta_ii = 0 assumption (Sec 4.1).

The O(N D^2) inner product is the per-sweep compute hot-spot; `gram` may be
served by the Pallas kernel in `repro.kernels.gram` (see ops.py there).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["gram", "residual_covariance", "spliced_gram", "subsample_size",
           "subsample_indices", "subsampled_gram", "subsampled_covariance"]


def gram(r: jnp.ndarray, use_kernel: bool = False) -> jnp.ndarray:
    """(D, N) -> (D, D) Gram matrix R R^T / N.

    The kernel accumulates in fp32 (MXU contract) and the result is cast
    back to the residual dtype, so downstream scatters/solves stay
    dtype-stable under jax_enable_x64."""
    if use_kernel:
        from repro.kernels.gram import ops as gram_ops

        return (gram_ops.gram(r) / r.shape[1]).astype(r.dtype)
    # full f32 precision: the TPU default rounds through bf16 passes
    return jnp.matmul(r, r.T, precision=jax.lax.Precision.HIGHEST) / r.shape[1]


def residual_covariance(residuals: jnp.ndarray, use_kernel: bool = False) -> jnp.ndarray:
    """Full-data covariance estimate A (paper eq. 14)."""
    return gram(residuals, use_kernel=use_kernel)


def subsample_size(n: int, alpha: float) -> int:
    """ceil(N / alpha), floored at 2 so a covariance is defined. The single
    source of truth for how many instances rate alpha transmits (the api
    layer's wire-byte accounting uses the same function)."""
    return max(2, int(-(-n // alpha)))


def subsample_indices(key: jax.Array, n: int, alpha: float) -> jnp.ndarray:
    """Randomly sample ceil(N / alpha) instance indices (without replacement)."""
    return jax.random.permutation(key, n)[: subsample_size(n, alpha)]


def spliced_gram(sub: jnp.ndarray, exact_diag: jnp.ndarray,
                 use_kernel: bool = False) -> jnp.ndarray:
    """The Sec 4.1 splice in one place: off-diagonals from the (possibly
    coded) subsample rows, diagonal replaced by the exact local variances —
    shared by `subsampled_gram`, the transport-aware objectives
    (core.icoa._transported_a0) and core.covstate.build, so the delta_ii = 0
    convention cannot drift between the engines."""
    a0 = gram(sub, use_kernel=use_kernel)
    return a0 - jnp.diag(jnp.diag(a0)) + jnp.diag(exact_diag)


def subsampled_gram(residuals: jnp.ndarray, idx: Optional[jnp.ndarray],
                    use_kernel: bool = False) -> jnp.ndarray:
    """A0 from given subsample indices: off-diagonals estimated from the
    subsample, diagonal (local, free) kept exact — the paper's delta_ii = 0
    assumption (Sec 4.1). `idx is None` means full transmission: exact A."""
    if idx is None:
        return gram(residuals, use_kernel=use_kernel)
    exact_diag = jnp.sum(residuals * residuals, axis=1) / residuals.shape[1]
    return spliced_gram(residuals[:, idx], exact_diag, use_kernel=use_kernel)


def subsampled_covariance(
    key: jax.Array,
    residuals: jnp.ndarray,
    alpha: float,
    use_kernel: bool = False,
    idx: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """A0: off-diagonals from an N/alpha subsample, exact local diagonal.

    This is the compressed estimate the agents can actually afford to share:
    each agent transmits only the subsampled slice of its residual vector
    (N/alpha numbers instead of N), shrinking the all-gather payload by alpha.
    """
    if idx is None:
        idx = subsample_indices(key, residuals.shape[1], alpha)
    return subsampled_gram(residuals, idx, use_kernel=use_kernel)
