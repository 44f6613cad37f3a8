"""Plain float32 reference of ICOA, written from the paper and imports
nothing of the program.

One sweep visits the agents in order.  For agent i, with residuals
R = y - F and A = R R^T / N:

    s = (A + 1e-10 I)^{-1} 1,  eta_tilde = 1^T s       (objective, maximised)
    g = (2/N) s_i (s^T R),     g_hat = g / |g|          (gradient w.r.t. f_i)
    step = the first of sqrt(N) * 0.5^k, k = 0..15, whose candidate
           f_i + step g_hat raises eta_tilde; 0 when none does
    f_i' = the agent's ridge least-squares fit of f_i + step g_hat
    keep f_i' only if it raises eta_tilde.

Each objective is evaluated by a fresh solve of the whole D x D system: no
rank-1 or rank-2 updates, no kernels, no cached inverse.  A record after the
non-cooperative start (every agent fits y) and after each sweep holds the
optimal combination weights w = s / 1^T s, the training MSE of w @ F, the
test MSE of the weighted test predictions and eta = 1 / eta_tilde.

Agents are degree-`degree` polynomial ridge regressions over their own
columns: features [1, x^1 .. x^degree per column, pairwise products], ridge
1e-6.

Precision: every contraction over the N instances (the residual Gram, the
gradient's products with R, the agents' normal equations and predictions)
goes through `dot`, at the precision `prec` names: "highest", exact float32
products (HIGHEST), for the reference; "high", the three-pass bfloat16
product that the TPU runs at Precision.HIGH, for its control.  "high" is
written out here, so that it reads the same on any backend.  The D x D
solves run at the caller's `jax.default_matmul_precision`.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

JITTER = 1e-10
RIDGE = 1e-6
MAX_PROBES = 16
HIGHEST = jax.lax.Precision.HIGHEST


def _split(x):
    """x = hi + lo + O(2^-16 x), with hi and lo representable in bfloat16."""
    hi = x.astype(jnp.bfloat16).astype(x.dtype)
    return hi, (x - hi).astype(jnp.bfloat16).astype(x.dtype)


def dot(a, b, prec: str = "highest"):
    """jnp.matmul(a, b) in float32 ("highest") or in three bfloat16 passes
    ("high": hi*hi + hi*lo + lo*hi, each product exact, summed in float32)."""
    if prec == "highest":
        return jnp.matmul(a, b, precision=HIGHEST)
    (ah, al), (bh, bl) = _split(a), _split(b)
    mm = partial(jnp.matmul, precision=HIGHEST)
    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


def features(x, degree: int):
    """(N, C) -> (N, P): bias, per-column powers, pairwise products."""
    n, c = x.shape
    cols = [jnp.ones((n, 1), x.dtype)] + [x ** k for k in range(1, degree + 1)]
    pairs = [(x[:, a] * x[:, b])[:, None]
             for a in range(c) for b in range(a + 1, c)]
    return jnp.concatenate(cols + pairs, axis=1)


def ls_fit(x, target, degree: int, prec="highest"):
    phi = features(x, degree)
    gram = (dot(phi.T, phi, prec)
            + RIDGE * jnp.eye(phi.shape[1], dtype=phi.dtype))
    return jnp.linalg.solve(gram, dot(phi.T, target, prec))


def predict(params, x, degree: int, prec="highest"):
    return dot(features(x, degree), params, prec)


def gram(r, prec="highest"):
    """R R^T / N over the instances."""
    return dot(r, r.T, prec) / r.shape[1]


def _solve_ones(a):
    d = a.shape[-1]
    eye = jnp.eye(d, dtype=a.dtype)
    ones = jnp.ones(a.shape[:-1], a.dtype)
    return jnp.linalg.solve(a + JITTER * eye, ones[..., None])[..., 0]


def record(params, f, y, xcols_test, y_test, degree: int, prec="highest"):
    """(train_mse, test_mse, eta) of the optimally weighted F."""
    a = gram(y[None, :] - f, prec)
    s = _solve_ones(a)
    w = s / jnp.sum(s)
    train = jnp.mean((y - w @ f) ** 2)
    ft = jax.vmap(partial(predict, degree=degree, prec=prec))(params,
                                                              xcols_test)
    test = jnp.mean((y_test - w @ ft) ** 2)
    return train, test, 1.0 / jnp.sum(s)


def sweep(params, f, xcols, y, degree: int, prec="highest"):
    """One round-robin sweep over every agent; returns (params, f)."""
    d, n = f.shape
    steps = jnp.sqrt(jnp.asarray(n, f.dtype)) * 0.5 ** jnp.arange(
        MAX_PROBES, dtype=f.dtype)

    def with_row(a, i, row, diag):
        a = a.at[i, :].set(row).at[:, i].set(row)
        return a.at[i, i].set(diag)

    def update(i, carry):
        params, f = carry
        r = y[None, :] - f
        a = gram(r, prec)
        s = _solve_ones(a)
        eta = jnp.sum(s)
        g = (2.0 / n) * s[i] * dot(s, r, prec)
        g_hat = g / (jnp.sqrt(dot(g, g, prec)) + 1e-30)
        gr = dot(r, g_hat, prec) / n
        gg = dot(g_hat, g_hat, prec) / n
        # candidate covariances for every step: r_i -> r_i - step * g_hat
        rows = a[i][None, :] - steps[:, None] * gr[None, :]
        diags = a[i, i] - 2.0 * steps * gr[i] + steps * steps * gg
        cands = jax.vmap(lambda row, dg: with_row(a, i, row, dg))(rows, diags)
        etas = jnp.sum(_solve_ones(cands), axis=-1)
        better = etas > eta
        step = jnp.where(jnp.any(better), steps[jnp.argmax(better)], 0.0)
        p_new = ls_fit(xcols[i], f[i] + step * g_hat, degree, prec)
        f_new = predict(p_new, xcols[i], degree, prec)
        r_new = y - f_new
        row = dot(r, r_new, prec) / n
        post = with_row(a, i, row, dot(r_new, r_new, prec) / n)
        keep = jnp.sum(_solve_ones(post)) > eta
        params = params.at[i].set(jnp.where(keep, p_new, params[i]))
        f = f.at[i].set(jnp.where(keep, f_new, f[i]))
        return params, f

    return jax.lax.fori_loop(0, d, update, (params, f))


@partial(jax.jit, static_argnames=("degree", "n_sweeps", "prec"))
def fit_records(xcols, y, xcols_test, y_test, degree: int, n_sweeps: int,
                prec="highest"):
    """Records 0..n_sweeps of a run from the non-cooperative start:
    (train, test, eta), each of shape (n_sweeps + 1,)."""
    params = jax.vmap(lambda x: ls_fit(x, y, degree, prec))(xcols)
    f = jax.vmap(partial(predict, degree=degree, prec=prec))(params, xcols)
    out = [record(params, f, y, xcols_test, y_test, degree, prec)]
    for _ in range(n_sweeps):
        params, f = sweep(params, f, xcols, y, degree, prec)
        out.append(record(params, f, y, xcols_test, y_test, degree, prec))
    return tuple(jnp.stack(v) for v in zip(*out))


def records(data, seeds, *, agent: dict, solver: dict, n_sweeps: int,
            prec: str = "highest"):
    """Records 0..n_sweeps of every trial from `fit_records`, vmapped over
    the leading trial axis of `data` (the interface bench/drivers.py
    calls).  This ICOA draws nothing, so the seeds go unused; it sends
    every residual and weights the ensemble in closed form, so the
    configuration's solver has to run alpha 1 and delta 0."""
    del seeds
    if float(solver["alpha"]) != 1.0 or float(solver["delta"]) != 0.0:
        raise ValueError("bench/reference.py is ICOA at alpha 1 and delta 0; "
                         "name another reference in the configuration")
    return jax.vmap(lambda *a: fit_records(
        *a, degree=agent["degree"], n_sweeps=n_sweeps, prec=prec))(*data)
