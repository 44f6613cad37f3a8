"""shard_map distributed ICOA: needs 5 host devices, so it runs in a
subprocess with its own XLA_FLAGS (the main test process keeps 1 device)."""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.data.friedman import make_dataset
from repro.data.partition import one_per_agent
from repro.agents import PolynomialFamily
from repro.core import icoa
from repro.core.distributed import run_distributed

assert len(jax.devices()) == 5, jax.devices()
xtr, ytr, xte, yte = make_dataset(1, n_train=1000, n_test=1000, seed=0)
xcols = jnp.stack([xtr[:, g] for g in one_per_agent(5)])
xcols_te = jnp.stack([xte[:, g] for g in one_per_agent(5)])
fam = PolynomialFamily(n_cols=1, degree=4)

cfg = icoa.ICOAConfig(n_sweeps=6)
params, w, hist = run_distributed(fam, cfg, xcols, ytr, xcols_te, yte)
assert abs(float(jnp.sum(w)) - 1.0) < 1e-3, w
assert hist["test_mse"][-1] < 0.5 * hist["test_mse"][0], hist["test_mse"]

# compressed variant still converges with protection
cfg2 = icoa.ICOAConfig(n_sweeps=6, alpha=20.0, delta=0.01)
_, w2, hist2 = run_distributed(fam, cfg2, xcols, ytr, xcols_te, yte)
assert hist2["test_mse"][-1] < hist2["test_mse"][0], hist2["test_mse"]
print("DISTRIBUTED_OK")
"""

# dense-vs-incremental engine parity under shard_map, in float64 so the only
# admissible difference is the algorithm itself (the two engines are
# mathematically identical; fp32 accumulation noise would obscure that)
_PARITY_SCRIPT = r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.data.friedman import make_dataset
from repro.data.partition import one_per_agent
from repro.agents import PolynomialFamily
from repro.core import icoa
from repro.core.distributed import run_distributed

assert len(jax.devices()) == 5, jax.devices()
xtr, ytr, xte, yte = make_dataset(1, n_train=600, n_test=600, seed=0)
xcols = jnp.stack([xtr[:, g] for g in one_per_agent(5)])
xcols_te = jnp.stack([xte[:, g] for g in one_per_agent(5)])
fam = PolynomialFamily(n_cols=1, degree=4)

for alpha, delta in [(1.0, 0.0), (20.0, 0.0), (1.0, 0.02), (20.0, 0.01)]:
    kw = dict(n_sweeps=3, alpha=alpha, delta=delta, minimax_steps=60)
    _, w_d, h_d = run_distributed(fam, icoa.ICOAConfig(engine="dense", **kw),
                                  xcols, ytr, xcols_te, yte)
    _, w_i, h_i = run_distributed(fam, icoa.ICOAConfig(engine="incremental", **kw),
                                  xcols, ytr, xcols_te, yte)
    for k in ("train_mse", "test_mse", "eta"):
        np.testing.assert_allclose(h_i[k], h_d[k], rtol=1e-5, atol=1e-12,
                                   err_msg=f"alpha={alpha} delta={delta} {k}")
    np.testing.assert_allclose(np.asarray(w_i), np.asarray(w_d), rtol=1e-5,
                               err_msg=f"alpha={alpha} delta={delta} weights")
print("ENGINE_PARITY_OK")
"""


# alpha 1 moves the full-length rows with no gather or scatter; the parent
# path indexed them by arange(N).  Forcing that index back through the
# subsample helper must leave every result as it was, on both engines: the
# gathers by arange bit for bit; the scatter-add to within one rounding per
# update, since XLA's CPU backend contracts the elementwise multiply-add
# into a fused multiply-add, where the scatter rounds the step first
_IDENTITY_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.data.friedman import make_dataset
from repro.data.partition import one_per_agent
from repro.agents import PolynomialFamily
from repro.core import distributed as dist
from repro.core import icoa

assert len(jax.devices()) == 5, jax.devices()


def gathered(cfg, key, n):
    # the parent's gathers by arange, with the elementwise step
    assert cfg.alpha == 1.0
    idx = jnp.arange(n)
    return None, n, lambda a: a[idx]


def indexed(cfg, key, n):
    # the parent's path: gathers by arange and the scatter-add
    assert cfg.alpha == 1.0
    idx = jnp.arange(n)
    return idx, n, lambda a: a[idx]


def run(engine, helper=None):
    xtr, ytr, xte, yte = make_dataset(1, n_train=600, n_test=600, seed=0)
    xcols = jnp.stack([xtr[:, g] for g in one_per_agent(5)])
    xcols_te = jnp.stack([xte[:, g] for g in one_per_agent(5)])
    fam = PolynomialFamily(n_cols=1, degree=4)
    plain = dist._subsample
    dist._subsample = helper or plain
    try:
        params, w, hist = dist.run_distributed(
            fam, icoa.ICOAConfig(n_sweeps=3, engine=engine),
            xcols, ytr, xcols_te, yte)
    finally:
        dist._subsample = plain
    f = jax.vmap(fam.predict)(params, xcols)
    hist = {k: np.asarray(hist[k])
            for k in ("train_mse", "test_mse", "eta", "bytes")}
    return jax.device_get((params, f, w)), hist


for engine in ("dense", "incremental"):
    direct = run(engine)
    assert direct[1]["eta"][-1] < direct[1]["eta"][0]
    via = run(engine, gathered)
    for a, b in zip(jax.tree.leaves(direct), jax.tree.leaves(via)):
        assert np.array_equal(a, b), (engine, a, b)
    with jax.enable_x64(True):
        direct, via = run(engine), run(engine, indexed)
    assert direct[1]["eta"].dtype == np.float64
    np.testing.assert_array_equal(direct[1]["bytes"], via[1]["bytes"])
    for a, b in zip(jax.tree.leaves(direct), jax.tree.leaves(via)):
        np.testing.assert_allclose(a, b, rtol=1e-10, atol=1e-12,
                                   err_msg=engine)
print("IDENTITY_PARITY_OK")
"""


def _run_in_subprocess(script, extra_env=()):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=5"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.update(extra_env)
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)


@pytest.mark.slow
def test_distributed_icoa_five_agents():
    out = _run_in_subprocess(_SCRIPT)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DISTRIBUTED_OK" in out.stdout


@pytest.mark.slow
def test_distributed_engine_parity_all_protection_settings():
    out = _run_in_subprocess(_PARITY_SCRIPT, extra_env=(("JAX_ENABLE_X64", "1"),))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ENGINE_PARITY_OK" in out.stdout


def test_distributed_alpha1_matches_the_indexed_path():
    out = _run_in_subprocess(_IDENTITY_SCRIPT)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "IDENTITY_PARITY_OK" in out.stdout
