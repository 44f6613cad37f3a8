"""Degree-d polynomial ridge regression agents.

This is the estimator family of the paper's Table 2 ("4th order polynomial").
The ICOA projection step — "train f_i with f_hat_i as the outcome" — is an
exact closed-form least-squares solve here, which makes the projection onto
H_i literal (an orthogonal projection under the ridge metric).

Features for agent columns x in R^{N x C}: all per-column powers x_c^k,
k = 1..degree, plus (for C > 1) pairwise products x_a * x_b, plus a bias.
For the paper's C = 1 setup this is exactly [1, x, x^2, .., x^d].
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["PolynomialFamily"]

# The normal equations are ill-conditioned (powers up to x^(2*degree)); at the
# default precision a TPU rounds f32 products through bf16 passes, which moved
# a four-party fit 2.7e-2 off the f32 result on a v5e.
_HIGHEST = jax.lax.Precision.HIGHEST


def _features(x: jnp.ndarray, degree: int) -> jnp.ndarray:
    """(N, C) -> (N, P) polynomial feature map."""
    n, c = x.shape
    feats = [jnp.ones((n, 1), dtype=x.dtype)]
    for k in range(1, degree + 1):
        feats.append(x**k)
    if c > 1:
        # pairwise interaction terms (a < b)
        prods = []
        for a in range(c):
            for b in range(a + 1, c):
                prods.append((x[:, a] * x[:, b])[:, None])
        if prods:
            feats.append(jnp.concatenate(prods, axis=1))
    return jnp.concatenate(feats, axis=1)


@dataclasses.dataclass(frozen=True)
class PolynomialFamily:
    n_cols: int
    degree: int = 4
    ridge: float = 1e-6

    @property
    def n_features(self) -> int:
        return 1 + self.n_cols * self.degree + self.n_cols * (self.n_cols - 1) // 2

    def init(self, key: jax.Array) -> jnp.ndarray:
        del key  # deterministic zero init — first fit() overwrites it anyway
        return jnp.zeros((self.n_features,), dtype=jnp.float32)

    def fit(self, params: jnp.ndarray, x: jnp.ndarray, target: jnp.ndarray) -> jnp.ndarray:
        """Closed-form ridge solve: the projection of `target` onto H_i."""
        del params  # closed form — no warm start needed
        phi = _features(x, self.degree)
        gram = (jnp.matmul(phi.T, phi, precision=_HIGHEST)
                + self.ridge * jnp.eye(phi.shape[1], dtype=phi.dtype))
        rhs = jnp.matmul(phi.T, target, precision=_HIGHEST)
        return jnp.linalg.solve(gram, rhs)

    def predict(self, params: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
        return jnp.matmul(_features(x, self.degree), params, precision=_HIGHEST)

    def fit_predict(
        self, params: jnp.ndarray, x: jnp.ndarray, target: jnp.ndarray
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        p = self.fit(params, x, target)
        return p, self.predict(p, x)
