"""A configuration names its reference.

  * the harness calls the module a configuration names with the trial
    seeds, its agent and solver blocks, the traffic's check_sweeps and the
    precision, so a deployment with its own mathematics needs new files
    only;
  * the two cells on `bench/reference.py` read, through that hook, the
    records the harness computed before it had the hook, to every digit.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, run

# (configuration, traffic mix) at their rehearsal sizes
BATCH = ("friedman1-d5", "batch_closed")
MESH = ("corrlin-p4", "batch_closed_mesh")
SEED = 2147483659


def _rehearsal(path: str) -> dict:
    d = run._load_json(run.BENCH, path)
    return run._merge(d, d.get("rehearsal", {}))


def _driver(pair, config=None):
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    from bench import drivers
    from repro import api

    name, traffic = pair
    cell = types.SimpleNamespace(
        config=config or _rehearsal(f"configs/{name}.json"),
        traffic=_rehearsal(f"traffic/{traffic}.json"))
    drv = drivers.make(cell, SEED, api)
    drv.trials = cell.traffic["trials"]
    return drv


def test_a_configuration_names_its_own_reference(monkeypatch):
    seen = {}

    def records(data, seeds, *, agent, solver, n_sweeps, prec):
        seen.update(data=data, seeds=seeds, agent=agent, solver=solver,
                    n_sweeps=n_sweeps, prec=prec)
        k = seeds.shape[0]
        rec = jnp.arange(k * (n_sweeps + 1), dtype=jnp.float32).reshape(
            k, n_sweeps + 1)
        return rec, rec + 1.0, rec + 2.0

    monkeypatch.setitem(sys.modules, "bench.own_reference",
                        types.SimpleNamespace(records=records))
    config = _rehearsal("configs/friedman1-d5.json")
    config["reference"] = "own_reference"
    drv = _driver(BATCH, config)
    for prec in ("highest", "high"):
        out = drv.reference(precision=prec)
        k = drv.traffic["check_sweeps"]
        assert seen["prec"] == prec and seen["n_sweeps"] == k
        assert seen["agent"] == config["agent"]
        assert seen["solver"] == config["solver"]
        np.testing.assert_array_equal(seen["seeds"],
                                      drv.base + np.arange(drv.trials))
        xcols, y, xcols_test, y_test = seen["data"]
        n, n_test = config["data"]["n_train"], config["data"]["n_test"]
        assert xcols.shape == (drv.trials, 5, n, 1)
        assert y.shape == (drv.trials, n)
        assert xcols_test.shape == (drv.trials, 5, n_test, 1)
        assert y_test.shape == (drv.trials, n_test)
        assert len(out) == drv.trials
        np.testing.assert_array_equal(out[1][2],
                                      np.arange(k + 1, 2 * k + 2) + 2.0)


@pytest.mark.parametrize("pair", [BATCH, MESH], ids=lambda p: p[0])
@pytest.mark.parametrize("prec", ["highest", "high"])
def test_unprotected_cells_read_as_before_the_hook(pair, prec):
    drv = _driver(pair)
    assert "reference" not in drv.cfg
    # the harness's computation before a configuration named its reference
    seeds = drv.base + jnp.arange(drv.trials)
    dat = jax.vmap(drv.reference_data)(seeds)
    with jax.default_matmul_precision(prec):
        before = jax.vmap(lambda *a: reference.fit_records(
            *a, degree=drv.cfg["agent"]["degree"],
            n_sweeps=drv.traffic["check_sweeps"], prec=prec))(*dat)
    after = drv.reference(precision=prec)
    for t in range(drv.trials):
        for q in range(3):
            np.testing.assert_array_equal(after[t][q],
                                          np.asarray(before[q][t]))


@pytest.mark.parametrize("alpha,delta", [(100.0, 0.0), (1.0, 0.01)])
def test_unprotected_reference_refuses_a_protected_solver(alpha, delta):
    drv = _driver(BATCH)
    solver = dict(drv.cfg["solver"], alpha=alpha, delta=delta)
    with pytest.raises(ValueError, match="alpha 1 and delta 0"):
        reference.records(None, None, agent=drv.cfg["agent"],
                          solver=solver, n_sweeps=1)
