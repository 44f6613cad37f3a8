"""api v2: open scenario registries (sources x partitions), strict spec
round-trips, the dataset-cache bound, and the compiled Monte-Carlo batch
runner (batch_fit == serial fit, one jitted vmap)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypcompat import HAVE_HYPOTHESIS, given, settings, st

from repro import api
from repro.api import specs as specs_mod
from repro.data.partition import PARTITIONS
from repro.data.sources import SOURCES

_N = 220


def _spec(**data_kw):
    data_kw.setdefault("n_train", _N)
    data_kw.setdefault("n_test", _N)
    return api.ExperimentSpec(
        data=api.DataSpec(**data_kw),
        agent=api.AgentSpec(family="polynomial", options=(("degree", 3),)),
        solver=api.SolverSpec(n_sweeps=2))


# ------------------------------------------------- registry property tests


def _compatible_combos():
    """Every registered source x partition, at a compatible (n_attrs,
    n_agents); skips nothing — new registrations are picked up automatically."""
    combos = []
    for sname, src in sorted(SOURCES.items()):
        m = src.n_attrs or 6
        for pname in sorted(PARTITIONS):
            if pname in ("one_per_agent", "overlapping"):
                # overlapping needs room past each block: one column per agent
                d = m
            else:
                # the largest PROPER divisor exercises multi-column agents
                d = max(k for k in range(1, m) if m % k == 0)
            combos.append((sname, pname, m, d))
    return combos


@pytest.mark.parametrize("sname,pname,m,d", _compatible_combos())
def test_every_source_x_partition_builds_validates_roundtrips(sname, pname, m, d):
    spec = _spec(source=sname, n_attrs=None if SOURCES[sname].n_attrs else m,
                 partition=pname, n_agents=d)
    spec.validate()
    ds = spec.data.build()
    assert ds.xcols.shape[0] == d and ds.y.shape == (_N,)
    assert ds.xcols.shape == (d, _N, len(ds.groups[0]))
    assert len({len(g) for g in ds.groups}) == 1          # stacked runtime
    back = api.spec_from_dict(api.spec_to_dict(spec))
    assert back == spec


def test_source_and_partition_options_roundtrip_and_validate():
    spec = _spec(source="correlated_linear", n_attrs=6,
                 source_options=(("rho", 0.3), ("snr", 5.0)),
                 partition="overlapping", n_agents=3,
                 partition_options=(("overlap", 1),))
    spec.validate()
    assert api.spec_from_dict(api.spec_to_dict(spec)) == spec
    with pytest.raises(api.SpecError, match="no option"):
        _spec(source="correlated_linear",
              source_options=(("bandwidth", 1.0),)).validate()
    with pytest.raises(api.SpecError, match="no option"):
        _spec(partition="overlapping", n_agents=5,
              partition_options=(("stride", 2),)).validate()
    # wrong-typed option VALUES must surface as SpecError too, not TypeError
    with pytest.raises(api.SpecError, match="overlapping"):
        _spec(source="correlated_linear", n_attrs=6, partition="overlapping",
              n_agents=3, partition_options=(("overlap", "2"),)).validate()


def test_unequal_groups_and_empty_agents_are_spec_errors():
    # 7 attrs over 3 agents: covers, but group sizes differ -> cannot stack
    with pytest.raises(api.SpecError, match="unequal group sizes"):
        _spec(source="correlated_linear", n_attrs=7, partition="round_robin",
              n_agents=3).validate()
    # more agents than attributes: the round_robin guard surfaces as SpecError
    with pytest.raises(api.SpecError, match="no attributes"):
        _spec(source="correlated_linear", n_attrs=3, partition="round_robin",
              n_agents=5).validate()
    with pytest.raises(api.SpecError, match="fixed attribute count"):
        _spec(source="friedman1", n_attrs=7).validate()


def test_third_party_registration_flows_through_fit():
    @api.register_source("_test_quadratic", default_n_attrs=4)
    def _quad(key, n, n_attrs, noise):
        x = jax.random.uniform(key, (n, n_attrs))
        y = (x ** 2).sum(axis=1)
        return x, y / n_attrs

    @api.register_partition("_test_reversed")
    def _rev(n_attrs, n_agents):
        return [[n_attrs - 1 - j] for j in range(n_attrs)]

    try:
        spec = _spec(source="_test_quadratic", partition="_test_reversed")
        res = api.fit(spec)
        assert res.test_mse is not None
        assert res.data.groups == [[3], [2], [1], [0]]
        assert api.spec_from_dict(api.spec_to_dict(spec)) == spec
    finally:
        del SOURCES["_test_quadratic"], PARTITIONS["_test_reversed"]


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(m=st.integers(2, 12), frac=st.integers(1, 4),
           pname=st.sampled_from(["round_robin", "blocks", "random"]))
    def test_partition_spec_property(m, frac, pname):
        """Any divisor agent count validates, builds equal groups, and
        round-trips; the spec layer never lets an invalid grouping through."""
        divisors = [k for k in range(1, m + 1) if m % k == 0]
        d = divisors[min(frac, len(divisors) - 1)]
        spec = _spec(source="correlated_linear", n_attrs=m, partition=pname,
                     n_agents=d, n_train=32, n_test=8)
        spec.validate()
        groups = spec.data.groups
        assert len(groups) == d
        assert len({len(g) for g in groups}) == 1
        assert api.spec_from_dict(api.spec_to_dict(spec)) == spec


# ------------------------------------------------------- strict round-trips


def test_spec_from_dict_rejects_unknown_keys_everywhere():
    good = api.spec_to_dict(_spec())
    for section, key in [("data", "n_trian"), ("solver", "alhpa"),
                         ("agent", "famly"), ("backend", "nmae")]:
        d = api.spec_to_dict(_spec())
        d[section][key] = 1
        with pytest.raises(api.SpecError) as e:
            api.spec_from_dict(d)
        assert key in str(e.value) and section in str(e.value)
    top = dict(good, extra_section={})
    with pytest.raises(api.SpecError, match="extra_section"):
        api.spec_from_dict(top)
    # the happy path still round-trips strictly
    assert api.spec_from_dict(good) == _spec()


# ------------------------------------------------------------ dataset cache


def test_dataset_cache_bounded_and_clearable():
    api.clear_dataset_cache()
    info = specs_mod._build_dataset.cache_info()
    assert info.currsize == 0
    assert info.maxsize == specs_mod._DATASET_CACHE_SIZE   # sized in ONE place
    built = _spec().data.build()
    assert specs_mod._build_dataset.cache_info().currsize == 1
    assert _spec().data.build() is built                   # memo hit
    api.clear_dataset_cache()
    assert specs_mod._build_dataset.cache_info().currsize == 0


# ------------------------------------------------- compiled batch execution


@pytest.fixture(scope="module")
def mc_spec():
    # eps=0 disables early stopping: the compiled schedule is static, so the
    # serial reference must run the same number of sweeps
    return api.ExperimentSpec(
        data=api.DataSpec(n_train=_N, n_test=_N, seed=7),
        agent=api.AgentSpec(family="polynomial", options=(("degree", 4),)),
        solver=api.SolverSpec(n_sweeps=3, eps=0.0))


def test_batch_fit_matches_serial_fit_per_trial(mc_spec):
    k = 4
    rs = api.batch_fit(mc_spec, k)
    assert isinstance(rs, api.ResultSet) and len(rs) == k
    serial = [api.fit(api.trial_spec(mc_spec, t)) for t in range(k)]
    for t in range(k):
        assert rs[t].spec == serial[t].spec
        for field in ("train_mse", "test_mse", "eta"):
            np.testing.assert_allclose(
                getattr(rs[t].history, field), getattr(serial[t].history, field),
                rtol=5e-4, err_msg=f"trial {t} {field}")   # f32; f64 below
        assert rs[t].history.bytes_transmitted == serial[t].history.bytes_transmitted
    # trials are genuinely independent (fresh data + solver streams)
    assert rs[0].history.test_mse != rs[1].history.test_mse


def test_batch_fit_f64_machine_precision(mc_spec):
    """The acceptance bar: one compiled program, per-trial histories equal to
    8 serial fit() calls at machine precision in f64."""
    with jax.enable_x64(True):
        api.clear_dataset_cache()      # drop any f32-built datasets
        try:
            rs = api.batch_fit(mc_spec, 8)
            serial = [api.fit(api.trial_spec(mc_spec, t)) for t in range(8)]
            for t in range(8):
                for field in ("train_mse", "test_mse", "eta"):
                    np.testing.assert_allclose(
                        getattr(rs[t].history, field),
                        getattr(serial[t].history, field),
                        rtol=1e-10, err_msg=f"trial {t} {field}")
        finally:
            api.clear_dataset_cache()  # don't leak f64 datasets to other tests


def test_batch_fit_per_trial_arrays_match_serial_f64(mc_spec):
    """Each trial's params, weights and f (not only its histories) are the
    serial fit()'s, and so is what predict makes of them."""
    with jax.enable_x64(True):
        api.clear_dataset_cache()
        try:
            rs = api.batch_fit(mc_spec, 4)
            data = api.trial_spec(mc_spec, 0).data.build()
            x = jnp.concatenate(list(data.xcols_test), axis=1)
            for t in range(4):
                ser = api.fit(api.trial_spec(mc_spec, t))
                for a, b in zip(jax.tree.leaves(rs[t].params),
                                jax.tree.leaves(ser.params)):
                    np.testing.assert_allclose(a, b, rtol=1e-10)
                np.testing.assert_allclose(rs[t].weights, ser.weights,
                                           rtol=1e-10)
                np.testing.assert_allclose(rs[t].f, ser.f, rtol=1e-10)
                np.testing.assert_allclose(rs[t].predict(x), ser.predict(x),
                                           rtol=1e-10)
        finally:
            api.clear_dataset_cache()


def test_batch_fit_results_are_host_views_of_one_fetch(mc_spec):
    rs = api.batch_fit(mc_spec, 3)
    for field in ("params", "weights", "f"):
        leaves = [jax.tree.leaves(getattr(r, field)) for r in rs]
        for per_trial in zip(*leaves):
            assert all(type(a) is np.ndarray for a in per_trial), field
            # the trials' rows of one fetched array, not copies of their own
            fetched = per_trial[0].base
            assert isinstance(fetched, np.ndarray), field
            assert fetched.shape == (3, *per_trial[0].shape), field
            assert all(a.base is fetched and np.shares_memory(a, fetched)
                       for a in per_trial), field


def test_batch_fit_baselines_and_forced_serial(mc_spec):
    for name in ("averaging", "residual_refitting"):
        spec = api.spec_with(mc_spec, "solver.name", name)
        rs = api.batch_fit(spec, 3)
        ser = api.batch_fit(spec, 3, compiled=False)
        for t in range(3):
            np.testing.assert_allclose(rs[t].history.test_mse,
                                       ser[t].history.test_mse, rtol=5e-4)
            assert rs[t].history.bytes_transmitted == ser[t].history.bytes_transmitted


def test_build_runner_rejects_shard_map(mc_spec):
    spec = api.replace(mc_spec, backend=api.BackendSpec(name="shard_map"))
    with pytest.raises(api.SpecError, match="local backend only"):
        api.build_runner(spec)


def test_resultset_aggregates(mc_spec):
    rs = api.batch_fit(mc_spec, 4)
    stack = rs.stack("test_mse")
    assert stack.shape == (4, 4)                     # 4 trials, 3 sweeps + init
    np.testing.assert_allclose(rs.mean("test_mse"), stack.mean(0))
    np.testing.assert_allclose(rs.std("test_mse"), stack.std(0))
    b, m, s = rs.curve("test_mse")
    assert b.shape == m.shape == s.shape == (4,)
    assert b[0] == 0.0 and np.all(np.diff(b) > 0)    # init free, then paid
    assert rs.test_mse_mean == pytest.approx(float(stack[:, -1].mean()))


def test_sweep_trials_returns_resultsets(mc_spec):
    out = api.sweep(mc_spec, {"solver.alpha": [1.0, 30.0]}, trials=2)
    assert [type(x) for x in out] == [api.ResultSet, api.ResultSet]
    assert out[0].spec.solver.alpha == 1.0 and out[1].spec.solver.alpha == 30.0
    assert len(out[0]) == 2
    # compression shrinks the mean trade-off curve's byte axis
    assert out[1].cumulative_bytes[-1] < 0.1 * out[0].cumulative_bytes[-1]


def test_batch_fit_nondefault_scenario_all_solvers():
    """A registered non-Friedman source with n_attrs != 5 end-to-end (local
    backend) through every solver — the scenario layer is genuinely open."""
    for name in ("icoa", "averaging", "residual_refitting"):
        spec = api.ExperimentSpec(
            data=api.DataSpec(source="correlated_linear", n_train=_N,
                              n_test=_N, n_attrs=6, partition="blocks",
                              n_agents=3, source_options=(("rho", 0.4),)),
            agent=api.AgentSpec(family="polynomial", options=(("degree", 2),)),
            solver=api.SolverSpec(name=name, n_sweeps=2))
        rs = api.batch_fit(spec, 2)
        assert len(rs) == 2 and np.isfinite(rs.test_mse_mean)


# --------------------------------------------- shard_map backend (5 devices)

_SHARD_SCRIPT = r"""
import numpy as np
from repro import api

for name in ("icoa", "averaging", "residual_refitting"):
    spec = api.ExperimentSpec(
        data=api.DataSpec(source="cosine", n_train=400, n_test=400, n_attrs=8,
                          partition="blocks", n_agents=4),
        agent=api.AgentSpec(family="polynomial", options=(("degree", 2),)),
        solver=api.SolverSpec(name=name, n_sweeps=2),
        backend=api.BackendSpec(name="shard_map"))
    res = api.fit(spec)
    assert res.test_mse is not None and np.isfinite(res.test_mse), name
    local = api.fit(api.replace(spec, backend=api.BackendSpec(name="local")))
    np.testing.assert_allclose(res.history.train_mse[-1],
                               local.history.train_mse[-1], rtol=2e-2,
                               err_msg=name)
# batch_fit transparently falls back to the serial path on shard_map
rs = api.batch_fit(api.replace(spec, backend=api.BackendSpec(name="shard_map")), 2)
assert len(rs) == 2 and np.isfinite(rs.test_mse_mean)
print("SHARD_SCENARIO_OK")
"""


@pytest.mark.slow
def test_shard_map_runs_nondefault_scenario():
    """The acceptance bar's other half: a non-Friedman source with
    n_attrs != 5 through all three solvers on the shard_map backend."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARD_SCENARIO_OK" in out.stdout
