"""The benchmark's own data generators, made on the device from a seed.

These are copies of the program's `cosine`, `friedman1` and
`correlated_linear` draws, kept here so that a change to the program cannot
move the yardstick.  The harness registers them into the program's source
registry under `bench_<name>`, so the program generates its training data
by calling this code, and the reference regenerates the same data from the
same seed through `make_split` without importing the program.

Every generator maps `(key, n, n_attrs, noise, **options) -> (x, y)` with
`y` normalised to [0, 1]; `make_split` draws train and test from one split
of `PRNGKey(seed)` and standardises both on the train statistics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def _normalise(y):
    lo, hi = jnp.min(y), jnp.max(y)
    return (y - lo) / jnp.maximum(hi - lo, 1e-12)


def friedman1(key, n: int, n_attrs: int, noise: float):
    """10 sin(pi x1 x2) + 20 (x3 - 1/2)^2 + 10 x4 + 5 x5, x ~ U[0, 1]^5."""
    del n_attrs
    kx, kw = jax.random.split(key)
    x = jax.random.uniform(kx, (n, 5))
    y = (10.0 * jnp.sin(jnp.pi * x[:, 0] * x[:, 1])
         + 20.0 * (x[:, 2] - 0.5) ** 2 + 10.0 * x[:, 3] + 5.0 * x[:, 4])
    y = y + noise * jax.random.normal(kw, (n,))
    return x, _normalise(y)


def correlated_linear(key, n: int, n_attrs: int, noise: float,
                      rho: float = 0.6, snr: float = 10.0):
    """x ~ N(0, Sigma), Sigma_ij = rho^|i-j|; y = x w + noise at the given
    signal-to-noise ratio (Hellkvist et al. 2021)."""
    kx, kw, ke, kd = jax.random.split(key, 4)
    j = jnp.arange(n_attrs)
    sigma = rho ** jnp.abs(j[:, None] - j[None, :])
    chol = jnp.linalg.cholesky(sigma + 1e-9 * jnp.eye(n_attrs))
    x = jnp.matmul(jax.random.normal(kx, (n, n_attrs)), chol.T,
                   precision=_HIGHEST)
    w = jax.random.normal(kw, (n_attrs,)) / jnp.sqrt(float(n_attrs))
    y = jnp.matmul(x, w, precision=_HIGHEST)
    sig2 = jnp.dot(w, jnp.matmul(sigma, w, precision=_HIGHEST),
                   precision=_HIGHEST)
    y = y + jnp.sqrt(sig2 / snr) * jax.random.normal(ke, (n,))
    y = y + noise * jax.random.normal(kd, (n,))
    return x, _normalise(y)


def cosine(key, n: int, n_attrs: int, noise: float, freq: float = 1.0):
    """sum_j cos(2 pi freq (j+1) x_j) / (j+1), x ~ U[0, 1]^D (Zheng and
    Kulkarni 2008)."""
    kx, kw = jax.random.split(key)
    x = jax.random.uniform(kx, (n, n_attrs))
    j = jnp.arange(n_attrs, dtype=x.dtype)
    comps = jnp.cos(2.0 * jnp.pi * freq * (j + 1.0) * x) / (j + 1.0)
    y = comps.sum(axis=1) + noise * jax.random.normal(kw, (n,))
    return x, _normalise(y)


GENERATORS = {"friedman1": friedman1, "correlated_linear": correlated_linear,
              "cosine": cosine}


def make_split(source: str, n_train: int, n_test: int, seed, n_attrs: int,
               noise: float = 0.0, options=()):
    """(x_train, y_train, x_test, y_test), standardised on train stats."""
    gen = GENERATORS[source]
    kw = dict(options)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    xtr, ytr = gen(k1, n_train, n_attrs, noise, **kw)
    xte, yte = gen(k2, n_test, n_attrs, noise, **kw)
    mu = xtr.mean(axis=0)
    sd = xtr.std(axis=0) + 1e-12
    return (xtr - mu) / sd, ytr, (xte - mu) / sd, yte


def register_all(register_source) -> None:
    """Register every generator as `bench_<name>` through the program's
    `register_source(name, n_attrs=..., default_n_attrs=...)` decorator."""
    register_source("bench_friedman1", n_attrs=5)(friedman1)
    register_source("bench_correlated_linear",
                    default_n_attrs=8)(correlated_linear)
    register_source("bench_cosine", default_n_attrs=5)(cosine)
