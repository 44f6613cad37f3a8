"""Property tests for the incremental covariance engine (core.covstate):
rank-2 SMW row updates must match a dense rebuild across D, dtype and the
Sec 4.1 subsampled-diagonal split, and drift must stay bounded over a full
sweep of commits without a refresh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import covariance, covstate, ensemble


def _residuals(seed, d, n, dtype):
    r = jax.random.normal(jax.random.PRNGKey(seed), (d, n))
    return r.astype(dtype)


def _rebuild(r_full, idx, dtype):
    """Dense oracle state from full residuals (+ optional subsample split)."""
    if idx is None:
        return covstate.build(r_full)
    diag = jnp.sum(r_full * r_full, axis=1) / r_full.shape[1]
    return covstate.build(r_full[:, idx], exact_diag=diag)


@pytest.mark.parametrize("d,n,seed", [(3, 120, 0), (5, 400, 1), (8, 96, 2),
                                      (16, 512, 3)])
@pytest.mark.parametrize("split", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_replace_row_matches_dense_rebuild(d, n, seed, split, dtype):
    with jax.enable_x64(dtype == jnp.float64):
        r = _residuals(seed, d, n, dtype)
        idx = jnp.arange(0, n, 4) if split else None
        cs = _rebuild(r, idx, dtype)
        i = seed % d
        r_new = r[i] + 0.5 * _residuals(seed + 99, 1, n, dtype)[0]
        if split:
            new_diag = jnp.vdot(r_new, r_new) / n
            got = covstate.replace_row(cs, i, r_new[idx], new_diag=new_diag)
        else:
            got = covstate.replace_row(cs, i, r_new)
        want = _rebuild(r.at[i].set(r_new), idx, dtype)
        tol = dict(rtol=5e-4, atol=5e-5) if dtype == jnp.float32 \
            else dict(rtol=1e-9, atol=1e-11)
        for name in ("r_sub", "a0", "m_inv", "s"):
            np.testing.assert_allclose(np.asarray(getattr(got, name)),
                                       np.asarray(getattr(want, name)), **tol)
        assert float(got.eta_tilde) == pytest.approx(float(want.eta_tilde),
                                                     rel=tol["rtol"])


@pytest.mark.parametrize("split", [False, True])
def test_probe_matches_commit_and_leaves_state_unchanged(split):
    d, n = 6, 300
    r = _residuals(7, d, n, jnp.float32)
    idx = jnp.arange(0, n, 3) if split else None
    cs = _rebuild(r, idx, jnp.float32)
    i = 4
    r_new = r[i] * 0.3 + _residuals(8, 1, n, jnp.float32)[0]
    delta = (r_new[idx] if split else r_new) - cs.r_sub[i]
    ddiag = (jnp.vdot(r_new, r_new) / n - cs.a0[i, i]) if split else None
    u = covstate.row_update_vector(cs, i, delta, ddiag=ddiag)
    committed = covstate.apply_row_update(cs, i, r_new[idx] if split else r_new, u)
    # probes predict exactly what a commit produces, without committing
    assert float(covstate.eta_probe(cs, i, u)) == pytest.approx(
        float(committed.eta_tilde), rel=1e-5)
    np.testing.assert_allclose(np.asarray(covstate.s_probe(cs, i, u)),
                               np.asarray(committed.s), rtol=1e-5, atol=1e-6)
    # the probed state is untouched (CovState is immutable)
    np.testing.assert_array_equal(np.asarray(cs.a0),
                                  np.asarray(_rebuild(r, idx, jnp.float32).a0))


def test_eta_matches_ensemble_solve():
    """CovState's cached eta_tilde is ensemble.eta_tilde of the same A0 (same
    jitter, so the dense path is a true oracle)."""
    r = _residuals(11, 5, 256, jnp.float32)
    cs = covstate.build(r)
    a0 = covariance.gram(r)
    assert float(cs.eta_tilde) == pytest.approx(
        float(ensemble.eta_tilde(a0)), rel=1e-5)


@pytest.mark.parametrize("split", [False, True])
def test_full_sweep_of_updates_without_refresh_stays_bounded(split):
    """Drift bound: after D successive committed row replacements (one whole
    sweep) with NO refresh, the SMW-carried inverse still matches a dense
    rebuild to f32 working accuracy."""
    d, n = 10, 400
    r = _residuals(21, d, n, jnp.float32)
    idx = jnp.arange(0, n, 5) if split else None
    cs = _rebuild(r, idx, jnp.float32)
    for i in range(d):
        r_new = 0.7 * r[i] + 0.3 * _residuals(100 + i, 1, n, jnp.float32)[0]
        r = r.at[i].set(r_new)
        if split:
            cs = covstate.replace_row(cs, i, r_new[idx],
                                      new_diag=jnp.vdot(r_new, r_new) / n)
        else:
            cs = covstate.replace_row(cs, i, r_new)
    want = _rebuild(r, idx, jnp.float32)
    np.testing.assert_allclose(np.asarray(cs.a0), np.asarray(want.a0),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(cs.m_inv), np.asarray(want.m_inv),
                               rtol=2e-3, atol=2e-4)
    assert float(cs.eta_tilde) == pytest.approx(float(want.eta_tilde), rel=2e-3)
    # and a refresh snaps the solve state back to the dense answer exactly
    refreshed = covstate.refresh(cs)
    np.testing.assert_allclose(np.asarray(refreshed.m_inv),
                               np.asarray(want.m_inv), rtol=1e-5, atol=1e-6)
