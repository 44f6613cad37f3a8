"""Kernel-level parity for the fused sweep kernels (PR 7 tentpole).

The Pallas probe/back-search and accept/commit kernels (interpret=True on
this CPU box) against the jnp oracle kernels.sweep.ref, over the padding
grid, both accept regimes, and the custom_vmap batching path — the same
discipline as test_kernels.py applies to the Gram kernels."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypcompat import given, settings, st

from repro.core import covstate
from repro.kernels.gram.ops import row_gram
from repro.kernels.gram.ref import row_gram_ref
from repro.kernels.runtime import pad_geometry
from repro.kernels.sweep.ops import commit_sweep, probe_sweep
from repro.kernels.sweep.ref import commit_sweep_ref, probe_sweep_ref


def _scene(d, n, seed=0, dtype=jnp.float32):
    """A well-conditioned covariance scene: residual rows + SPD m_inv."""
    key = jax.random.PRNGKey(seed)
    kr, km, kd = jax.random.split(key, 3)
    r = jax.random.normal(kr, (d, n), dtype)
    m = jax.random.normal(km, (d, 2 * d), dtype)
    m_inv = (m @ m.T / (2 * d) + jnp.eye(d, dtype=dtype)).astype(dtype)
    s = jnp.sum(m_inv, axis=1)
    eta = jnp.sum(s)
    delta = (0.05 * jax.random.normal(kd, (n,))).astype(dtype)
    return r, m_inv, s, eta, delta


# ------------------------------------------------------------------- probe


@settings(max_examples=15, deadline=None)
@given(d=st.integers(2, 40), n=st.integers(8, 700), k=st.integers(1, 12),
       block=st.sampled_from([128, 256]))
def test_probe_kernel_matches_ref(d, n, k, block):
    r, m_inv, s, eta, _ = _scene(d, n, seed=d * 1000 + n)
    steps = 0.7 ** jnp.arange(1, k + 1, dtype=jnp.float32)
    i = d // 2
    out = probe_sweep(r, m_inv, s, eta, i, steps, block_n=block)
    ref = probe_sweep_ref(r, m_inv, s, eta, i, steps)
    for got, want, name in zip(out, ref, ("etas", "cross", "p", "gnorm")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4 * n ** 0.5,
                                   err_msg=name)


def test_probe_kernel_paper_shape_exact_schedule():
    """D=100/N=2000 (the paper-scale sweep shape): the closed-form
    schedule computed in-core must match the oracle essentially exactly —
    both evaluate the same fp32 closed form off the same accumulated
    scalars."""
    r, m_inv, s, eta, _ = _scene(100, 2000, seed=7)
    steps = 0.5 ** jnp.arange(1, 9, dtype=jnp.float32)
    out = probe_sweep(r, m_inv, s, eta, 13, steps)
    ref = probe_sweep_ref(r, m_inv, s, eta, 13, steps)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                               rtol=1e-5, atol=1e-5)


def test_probe_vmap_routes_to_batched_kernel():
    b, d, n, k = 3, 10, 300, 5
    rs = jnp.stack([_scene(d, n, seed=s_)[0] for s_ in range(b)])
    r0, m_inv, s, eta, _ = _scene(d, n, seed=0)
    steps = 0.6 ** jnp.arange(1, k + 1, dtype=jnp.float32)
    def fn(r):
        return probe_sweep(r, m_inv, s, eta, 2, steps)
    batched = jax.vmap(fn)(rs)
    for j in range(b):
        single = fn(rs[j])
        for got, want in zip(batched, single):
            np.testing.assert_allclose(np.asarray(got[j]), np.asarray(want),
                                       rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------ commit


@settings(max_examples=15, deadline=None)
@given(d=st.integers(2, 40), n=st.integers(8, 700),
       block=st.sampled_from([128, 256]),
       accept=st.booleans(), gated=st.booleans())
def test_commit_kernel_matches_ref(d, n, block, accept, gated):
    r, m_inv, s, eta, delta = _scene(d, n, seed=d * 991 + n)
    i = d - 1
    # drive the accept decision from the threshold side: obj_post is data-
    # dependent, so force accept with -inf and reject with +inf
    threshold = jnp.asarray(-jnp.inf if accept else jnp.inf, r.dtype)
    can_tx = jnp.asarray(0.0 if gated else 1.0, r.dtype)
    args = (r, m_inv, s, eta, i, delta, jnp.asarray(1.0, r.dtype),
            jnp.asarray(0.0, r.dtype), threshold, can_tx)
    out = commit_sweep(*args, block_n=block)
    ref = commit_sweep_ref(*args)
    names = ("m_inv", "s", "u_eff", "accept", "obj_post")
    for got, want, name in zip(out, ref, names):
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-4, atol=2e-4 * n ** 0.5, err_msg=name)
    assert bool(out[3]) == (accept and not gated)


def test_commit_reject_is_exact_noop():
    """Rejection must leave (m_inv, s) BITWISE unchanged — the engine relies
    on x - 0.0 == x so a rejected probe can't drift the carried state."""
    r, m_inv, s, eta, delta = _scene(17, 400, seed=3)
    out = commit_sweep(r, m_inv, s, eta, 4, delta, 1.0, 0.0,
                       jnp.asarray(jnp.inf, r.dtype), 1.0)
    assert not bool(out[3])
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(m_inv))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(s))


def test_commit_vmap_routes_to_batched_kernel():
    b, d, n = 3, 12, 256
    r, m_inv, s, eta, _ = _scene(d, n, seed=0)
    deltas = jnp.stack([_scene(d, n, seed=s_)[4] for s_ in range(b)])
    def fn(dl):
        return commit_sweep(r, m_inv, s, eta, 5, dl, 1.0, 0.0,
                            jnp.asarray(-jnp.inf, r.dtype), 1.0)
    batched = jax.vmap(fn)(deltas)
    for j in range(b):
        single = fn(deltas[j])
        for got, want in zip(batched, single):
            np.testing.assert_allclose(
                np.asarray(got[j], np.float32), np.asarray(want, np.float32),
                rtol=1e-5, atol=1e-5)


# --------------------------------------------------- packing edge geometry


@pytest.mark.parametrize("d,dp", [(1, 8), (4, 8), (5, 8), (8, 8), (9, 16),
                                  (100, 104), (128, 128), (129, 136),
                                  (512, 512)])
def test_pad_geometry_rounds_parties_to_the_sublane_multiple(d, dp):
    """The party axis pads to the smallest multiple of 8 (the fp32 sublane
    width) that holds D; the instance axis keeps its lane blocking."""
    assert pad_geometry(d, 2000, 2048) == (dp, 2048, 2048)


@pytest.mark.parametrize("n,block_n,np_,bn", [
    (7, 2048, 128, 128), (2000, 2048, 2048, 2048), (2049, 2048, 4096, 2048),
    (300, 256, 512, 256), (65536, 2048, 65536, 2048)])
def test_pad_geometry_keeps_the_instance_blocking(n, block_n, np_, bn):
    """N pads to a multiple of the N-block, which is the requested block cut
    to N's lane multiple; D does not enter it."""
    for d in (5, 100):
        assert pad_geometry(d, n, block_n)[1:] == (np_, bn)


@pytest.mark.parametrize("d,n", [(5, 2000), (4, 8192)])
def test_benchmark_widths_run_on_eight_rows(d, n):
    """The benchmark's party counts (D=5 at N=2000, D=4 at a multi-block N)
    reach the kernels as 8-row operands, and probe, commit and row_gram
    there agree with their oracles and with covstate's jnp row product."""
    r, m_inv, s, eta, delta = _scene(d, n, seed=d * 31 + n)
    np_ = pad_geometry(d, n, 2048)[1]
    steps = 0.5 ** jnp.arange(1, 9, dtype=jnp.float32)
    text = jax.jit(probe_sweep).lower(r, m_inv, s, eta, 1, steps).as_text()
    assert f"tensor<8x{np_}xf32>" in text
    assert f"tensor<128x{np_}xf32>" not in text
    tol = dict(rtol=2e-4, atol=2e-4 * n ** 0.5)
    out = probe_sweep(r, m_inv, s, eta, 1, steps)
    ref = probe_sweep_ref(r, m_inv, s, eta, 1, steps)
    for got, want, name in zip(out, ref, ("etas", "cross", "p", "gnorm")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   err_msg=name, **tol)
    args = (r, m_inv, s, eta, d - 1, delta, jnp.asarray(1.0, r.dtype),
            jnp.asarray(0.0, r.dtype), jnp.asarray(-jnp.inf, r.dtype),
            jnp.asarray(1.0, r.dtype))
    out = commit_sweep(*args)
    ref = commit_sweep_ref(*args)
    names = ("m_inv", "s", "u_eff", "accept", "obj_post")
    for got, want, name in zip(out, ref, names):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   err_msg=name, **tol)
    got = np.asarray(row_gram(delta, r))
    for want in (row_gram_ref(delta, r), covstate.row_product(delta, r)):
        np.testing.assert_allclose(got, np.asarray(want), rtol=1e-3,
                                   atol=1e-2 * n ** 0.5)
    np.testing.assert_array_equal(
        np.asarray(covstate.row_product(delta, r, use_kernel=True)), got)


@pytest.mark.parametrize("d,n", [(1, 7), (8, 2048), (9, 2000), (128, 128),
                                 (129, 2049), (3, 4096)])
def test_kernels_on_padding_boundaries(d, n):
    """Exact sublane and lane multiples, one-over, and tiny shapes all pad
    correctly (zero padding is load-bearing: full-array reductions ==
    payload)."""
    r, m_inv, s, eta, delta = _scene(d, n, seed=d + n)
    steps = jnp.asarray([0.5, 0.25], jnp.float32)
    out = probe_sweep(r, m_inv, s, eta, 0, steps)
    ref = probe_sweep_ref(r, m_inv, s, eta, 0, steps)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                               rtol=2e-4, atol=2e-4)
    out = commit_sweep(r, m_inv, s, eta, 0, delta, 1.0, 0.0,
                       jnp.asarray(-jnp.inf, r.dtype), 1.0)
    ref = commit_sweep_ref(r, m_inv, s, eta, 0, delta, 1.0, 0.0,
                           jnp.asarray(-jnp.inf, r.dtype), 1.0)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                               rtol=2e-4, atol=2e-4 * n ** 0.5)
