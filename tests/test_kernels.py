"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracles,
including hypothesis shape/dtype sweeps (assignment deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypcompat import given, settings, st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_decode.ops import flash_decode
from repro.kernels.flash_decode.ref import decode_ref
from repro.kernels.gram.ops import gram, row_gram
from repro.kernels.gram.ref import gram_ref, row_gram_ref


def _tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


# -------------------------------------------------------------------- gram


@settings(max_examples=20, deadline=None)
@given(d=st.integers(2, 40), n=st.integers(3, 700),
       dt=st.sampled_from([jnp.float32, jnp.bfloat16]),
       block=st.sampled_from([128, 256]))
def test_gram_matches_ref(d, n, dt, block):
    r = (jax.random.normal(jax.random.PRNGKey(d * 1000 + n), (d, n))).astype(dt)
    out = gram(r, block_n=block)
    ref = gram_ref(r)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3 if dt == jnp.float32 else 2e-2,
                               atol=1e-2 * n ** 0.5)


def test_gram_paper_shape():
    """The paper's D=5, N=4000 configuration."""
    r = jax.random.normal(jax.random.PRNGKey(0), (5, 4000))
    np.testing.assert_allclose(np.asarray(gram(r)),
                               np.asarray(gram_ref(r)), rtol=1e-4, atol=1e-2)


# ---------------------------------------------------------------- row gram


@settings(max_examples=20, deadline=None)
@given(d=st.integers(2, 40), n=st.integers(3, 700),
       dt=st.sampled_from([jnp.float32, jnp.bfloat16]),
       block=st.sampled_from([128, 256]))
def test_row_gram_matches_ref(d, n, dt, block):
    r = (jax.random.normal(jax.random.PRNGKey(d * 991 + n), (d, n))).astype(dt)
    v = (jax.random.normal(jax.random.PRNGKey(n * 7 + d), (n,))).astype(dt)
    out = row_gram(v, r, block_n=block)
    ref = row_gram_ref(v, r)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3 if dt == jnp.float32 else 2e-2,
                               atol=1e-2 * n ** 0.5)


def test_row_gram_is_one_gram_row():
    """row_gram(r_i, R) is exactly row i of the full Gram — the fused product
    the incremental engine's rank-2 update is built on."""
    r = jax.random.normal(jax.random.PRNGKey(1), (7, 2048))
    full = gram_ref(r)
    np.testing.assert_allclose(np.asarray(row_gram(r[3], r)),
                               np.asarray(full[3]), rtol=1e-4, atol=1e-2)


# -------------------------------------------------------------- flash attn


_ATTN_CASES = [
    # b, sq, hq, hkv, dh, window, dtype
    (2, 256, 4, 2, 64, 0, jnp.float32),
    (1, 128, 4, 4, 32, 0, jnp.float32),
    (2, 100, 6, 2, 64, 0, jnp.float32),      # non-multiple seq (padding path)
    (1, 256, 4, 1, 64, 64, jnp.bfloat16),    # sliding window + max GQA
    (1, 320, 2, 2, 128, 128, jnp.float32),
    (1, 64, 8, 2, 16, 0, jnp.float32),
]


@pytest.mark.parametrize("b,sq,hq,hkv,dh,window,dt", _ATTN_CASES)
def test_flash_attention_matches_ref(b, sq, hq, hkv, dh, window, dt):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(sq + hq), 3)
    q = jax.random.normal(k1, (b, sq, hq, dh)).astype(dt)
    k = jax.random.normal(k2, (b, sq, hkv, dh)).astype(dt)
    v = jax.random.normal(k3, (b, sq, hkv, dh)).astype(dt)
    out = flash_attention(q, k, v, causal=True, window=window,
                          use_pallas=True, bq=64, bk=64)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               **_tol(dt))


@settings(max_examples=12, deadline=None)
@given(sq=st.integers(16, 200), hkv=st.sampled_from([1, 2, 4]),
       g=st.sampled_from([1, 2, 3]), dh=st.sampled_from([16, 32, 64]))
def test_flash_attention_hypothesis_sweep(sq, hkv, g, dh):
    hq = hkv * g
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(sq * 7 + hq), 3)
    q = jax.random.normal(k1, (1, sq, hq, dh))
    k = jax.random.normal(k2, (1, sq, hkv, dh))
    v = jax.random.normal(k3, (1, sq, hkv, dh))
    out = flash_attention(q, k, v, causal=True, use_pallas=True, bq=32, bk=32)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ flash decode


_DECODE_CASES = [
    # b, s, hq, hkv, dh, idx, window, dtype
    (2, 1024, 4, 2, 64, 700, 0, jnp.float32),
    (1, 512, 8, 1, 64, 511, 0, jnp.float32),
    (2, 1000, 4, 4, 32, 37, 0, jnp.float32),  # padding path
    (1, 2048, 8, 2, 128, 1500, 256, jnp.bfloat16),
    (1, 256, 4, 2, 64, 0, 0, jnp.float32),    # idx=0: only first position
]


@pytest.mark.parametrize("b,s,hq,hkv,dh,idx,window,dt", _DECODE_CASES)
def test_flash_decode_matches_ref(b, s, hq, hkv, dh, idx, window, dt):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(s + idx), 3)
    q = jax.random.normal(k1, (b, hq, dh)).astype(dt)
    k = jax.random.normal(k2, (b, s, hkv, dh)).astype(dt)
    v = jax.random.normal(k3, (b, s, hkv, dh)).astype(dt)
    out = flash_decode(q, k, v, idx, window=window, use_pallas=True, bk=256)
    ref = decode_ref(q, k, v, idx, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               **_tol(dt))


@settings(max_examples=12, deadline=None)
@given(s=st.integers(32, 600), idx_frac=st.floats(0.0, 1.0),
       hkv=st.sampled_from([1, 2]), g=st.sampled_from([1, 2, 4]))
def test_flash_decode_hypothesis_sweep(s, idx_frac, hkv, g):
    hq, dh = hkv * g, 32
    idx = int(idx_frac * (s - 1))
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(s * 3 + idx), 3)
    q = jax.random.normal(k1, (1, hq, dh))
    k = jax.random.normal(k2, (1, s, hkv, dh))
    v = jax.random.normal(k3, (1, s, hkv, dh))
    out = flash_decode(q, k, v, idx, use_pallas=True, bk=128)
    ref = decode_ref(q, k, v, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5)


# ------------------------------------------------------------- chunked WKV


from repro.kernels.wkv.ops import wkv_chunked
from repro.kernels.wkv.ref import wkv_ref


_WKV_CASES = [
    # b, s, h, dh, chunk
    (2, 128, 4, 32, 32),
    (1, 100, 2, 64, 32),   # padding path
    (1, 256, 1, 16, 64),
    (2, 64, 3, 8, 16),
]


@pytest.mark.parametrize("b,s,h,dh,chunk", _WKV_CASES)
def test_wkv_kernel_matches_ref(b, s, h, dh, chunk):
    ks = jax.random.split(jax.random.PRNGKey(s + dh), 5)
    r = jax.random.normal(ks[0], (b, s, h, dh))
    k = jax.random.normal(ks[1], (b, s, h, dh))
    v = jax.random.normal(ks[2], (b, s, h, dh))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, h, dh))) * 0.98 + 0.01
    u = jax.random.normal(ks[4], (h, dh)) * 0.1
    out = wkv_chunked(r, k, v, w, u, chunk=chunk, use_pallas=True)
    ref = wkv_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


@settings(max_examples=8, deadline=None)
@given(s=st.integers(16, 200), dh=st.sampled_from([8, 16, 32]),
       chunk=st.sampled_from([16, 32]))
def test_wkv_kernel_hypothesis_sweep(s, dh, chunk):
    ks = jax.random.split(jax.random.PRNGKey(s * 31 + dh), 5)
    r = jax.random.normal(ks[0], (1, s, 2, dh))
    k = jax.random.normal(ks[1], (1, s, 2, dh))
    v = jax.random.normal(ks[2], (1, s, 2, dh))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (1, s, 2, dh))) * 0.98 + 0.01
    u = jax.random.normal(ks[4], (2, dh)) * 0.1
    out = wkv_chunked(r, k, v, w, u, chunk=chunk, use_pallas=True)
    ref = wkv_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)
