"""repro.api facade: spec validation, parity with the core entry points,
sweep enumeration, and save/load round-trips."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.agents import PolynomialFamily
from repro.core import baselines, icoa
from repro.data.friedman import make_dataset
from repro.data.partition import one_per_agent

_N = 500


@pytest.fixture(scope="module")
def base_spec():
    return api.ExperimentSpec(
        data=api.DataSpec(source="friedman1", n_train=_N, n_test=_N, seed=0),
        agent=api.AgentSpec(family="polynomial", options=(("degree", 4),)),
        solver=api.SolverSpec(name="icoa", n_sweeps=4),
    )


@pytest.fixture(scope="module")
def friedman_manual():
    """The hand-rolled wiring the api replaces — ground truth for parity."""
    xtr, ytr, xte, yte = make_dataset(1, n_train=_N, n_test=_N, seed=0)
    groups = one_per_agent(5)
    return (jnp.stack([xtr[:, g] for g in groups]), ytr,
            jnp.stack([xte[:, g] for g in groups]), yte)


# ---------------------------------------------------------------- validation


def test_bad_solver_name_raises(base_spec):
    spec = api.spec_with(base_spec, "solver.name", "gradient_descent")
    with pytest.raises(api.SpecError, match="unknown solver"):
        api.fit(spec)


def test_bad_family_name_raises(base_spec):
    spec = api.replace(base_spec, agent=api.AgentSpec(family="cart_tree"))
    with pytest.raises(api.SpecError, match="unknown agent family"):
        api.fit(spec)


def test_bad_family_option_raises(base_spec):
    spec = api.replace(base_spec,
                       agent=api.AgentSpec(family="polynomial",
                                           options=(("depth", 3),)))
    with pytest.raises(api.SpecError, match="no option"):
        api.fit(spec)


def test_bad_source_partition_and_backend_raise(base_spec):
    with pytest.raises(api.SpecError, match="unknown data source"):
        api.spec_with(base_spec, "data.source", "friedman9").validate()
    with pytest.raises(api.SpecError, match="unknown partition"):
        api.spec_with(base_spec, "data.partition", "striped").validate()
    with pytest.raises(api.SpecError, match="unknown backend"):
        api.spec_with(base_spec, "backend.name", "tpu_pod").validate()


def test_shard_map_rejects_mismatched_device_count(base_spec):
    """One agent per device is a hard assumption of the collective bodies —
    any other mesh size must be an error, not silently wrong results."""
    spec = api.replace(base_spec,
                       backend=api.BackendSpec(name="shard_map", n_devices=3))
    with pytest.raises(api.SpecError, match="one agent per device"):
        api.fit(spec)


def test_protection_knobs_rejected_for_baselines(base_spec):
    spec = api.replace(base_spec,
                       solver=api.SolverSpec(name="averaging", alpha=100.0))
    with pytest.raises(api.SpecError, match="no residual-compression knob"):
        api.fit(spec)


def test_specs_are_frozen_and_hashable(base_spec):
    with pytest.raises(dataclasses.FrozenInstanceError):
        base_spec.solver.alpha = 2.0
    assert hash(base_spec) == hash(api.replace(base_spec))


def test_spec_json_roundtrip(base_spec):
    spec = api.spec_with(base_spec, "solver.alpha", 20.0)
    assert api.spec_from_dict(api.spec_to_dict(spec)) == spec


# -------------------------------------------------------------------- parity


def test_icoa_parity_bit_for_bit(base_spec, friedman_manual):
    """api.fit reproduces core.icoa.run exactly (same data, seeds, wiring)."""
    xc, y, xct, yt = friedman_manual
    fam = PolynomialFamily(n_cols=1, degree=4)
    state, w, hist = icoa.run(fam, icoa.ICOAConfig(n_sweeps=4), xc, y, xct, yt)
    res = api.fit(base_spec)
    assert res.history.train_mse == hist["train_mse"]
    assert res.history.test_mse == hist["test_mse"]
    assert res.history.eta == hist["eta"]
    np.testing.assert_array_equal(np.asarray(res.weights), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(res.f), np.asarray(state.f))


def test_averaging_parity(base_spec, friedman_manual):
    xc, y, xct, yt = friedman_manual
    fam = PolynomialFamily(n_cols=1, degree=4)
    _, out = baselines.averaging(fam, xc, y, xct, yt)
    res = api.fit(api.spec_with(base_spec, "solver.name", "averaging"))
    assert res.test_mse == pytest.approx(out["test_mse"], abs=1e-7)
    assert res.history.bytes_transmitted == [0.0]


def test_refit_parity(base_spec, friedman_manual):
    xc, y, xct, yt = friedman_manual
    fam = PolynomialFamily(n_cols=1, degree=4)
    _, f, hist = baselines.residual_refitting(fam, xc, y, xct, yt, n_cycles=4)
    res = api.fit(api.spec_with(base_spec, "solver.name", "residual_refitting"))
    assert res.history.test_mse == hist["test_mse"]
    np.testing.assert_array_equal(np.asarray(res.f), np.asarray(f))
    # sum-combination is expressed as literal ones weights
    np.testing.assert_array_equal(np.asarray(res.weights), np.ones(5))


def test_history_is_uniform_across_solvers(base_spec):
    """Every solver emits the same History schema: train/test/eta/bytes."""
    for name in ("icoa", "averaging", "residual_refitting"):
        res = api.fit(api.spec_with(base_spec, "solver.name", name))
        h = res.history
        assert len(h.train_mse) == len(h.eta) == len(h.bytes_transmitted) > 0
        assert h.test_mse, name
        assert all(np.isfinite(v) for v in h.eta)


def test_predict_matches_recorded_test_mse(base_spec):
    res = api.fit(base_spec)
    xte = jnp.concatenate([res.data.xcols_test[i] for i in range(5)], axis=1)
    assert res.mse(xte, res.data.y_test) == pytest.approx(res.test_mse, rel=1e-6)


def test_compression_shrinks_wire_bytes(base_spec):
    full = api.fit(base_spec)
    mm = api.fit(api.replace(base_spec, solver=api.replace(
        base_spec.solver, alpha=50.0, delta=0.01)))
    assert mm.history.total_bytes < 0.1 * full.history.total_bytes


def test_minimax_upper_bound_positive(base_spec):
    res = api.fit(base_spec)
    b1, b100 = res.minimax_upper_bound(1.0), res.minimax_upper_bound(100.0)
    assert 0 < b1 <= b100 + 1e-6   # eq. 28 bound loosens with compression


# --------------------------------------------------------------------- sweep


def test_grid_specs_product_order(base_spec):
    specs = list(api.grid_specs(base_spec, {"solver.alpha": [1.0, 10.0],
                                            "solver.delta": [0.0, 0.01]}))
    assert [(s.solver.alpha, s.solver.delta) for s in specs] == [
        (1.0, 0.0), (1.0, 0.01), (10.0, 0.0), (10.0, 0.01)]


def test_zip_specs_paired_and_length_checked(base_spec):
    specs = list(api.zip_specs(base_spec, {"solver.alpha": [1.0, 10.0],
                                           "solver.delta": [0.0, 0.01]}))
    assert [(s.solver.alpha, s.solver.delta) for s in specs] == [
        (1.0, 0.0), (10.0, 0.01)]
    with pytest.raises(api.SpecError, match="equal-length"):
        list(api.zip_specs(base_spec, {"solver.alpha": [1.0], "seed": [1, 2]}))


def test_spec_with_rejects_unknown_path(base_spec):
    with pytest.raises(api.SpecError, match="no field"):
        api.spec_with(base_spec, "optimizer.lr", 0.1)


# ----------------------------------------------------------------- save/load


def test_save_load_roundtrip(tmp_path, base_spec):
    res = api.fit(base_spec)
    res.save(str(tmp_path))
    back = api.load(str(tmp_path))
    assert back.spec == res.spec
    assert back.history.as_dict() == res.history.as_dict()
    np.testing.assert_allclose(np.asarray(back.weights), np.asarray(res.weights),
                               rtol=1e-6)
    xte = jnp.concatenate([res.data.xcols_test[i] for i in range(5)], axis=1)
    assert back.mse(xte, res.data.y_test) == pytest.approx(res.test_mse, rel=1e-5)


def test_save_load_roundtrip_of_a_batch_result(tmp_path, base_spec):
    """A compiled-batch Result holds host arrays; they save and restore
    exactly, and predict and mse work on both sides."""
    res = api.batch_fit(base_spec, 2)[1]
    res.save(str(tmp_path))
    back = api.load(str(tmp_path), with_data=False)
    assert back.spec == res.spec
    assert back.history.as_dict() == res.history.as_dict()
    for a, b in zip(jax.tree.leaves(back.params), jax.tree.leaves(res.params)):
        np.testing.assert_array_equal(np.asarray(a), b)
    np.testing.assert_array_equal(np.asarray(back.weights), res.weights)
    np.testing.assert_array_equal(np.asarray(back.f), res.f)
    data = res.spec.data.build()
    x = jnp.concatenate(list(data.xcols_test), axis=1)
    np.testing.assert_array_equal(np.asarray(back.predict(x)),
                                  np.asarray(res.predict(x)))
    assert back.mse(x, data.y_test) == pytest.approx(res.test_mse, rel=1e-5)
