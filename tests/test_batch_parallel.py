"""Device-parallel Monte Carlo (PR 4): trial-axis sharding, batched Pallas
kernels, the shard_map compiled trial loop, converged-sweep reporting,
and the BackendSpec execution knobs."""
import inspect
import os
import subprocess
import sys
import typing

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import minimax
from repro.kernels.gram import gram, row_gram
from repro.kernels.sweep import commit_sweep, probe_sweep
from repro.launch.mesh import make_trial_mesh

_N = 160


def _spec(**solver_kw):
    solver_kw.setdefault("n_sweeps", 2)
    solver_kw.setdefault("eps", 0.0)
    return api.ExperimentSpec(
        data=api.DataSpec(n_train=_N, n_test=_N, seed=11),
        agent=api.AgentSpec(family="polynomial", options=(("degree", 3),)),
        solver=api.SolverSpec(**solver_kw))


# ------------------------------------------------ batched Pallas gram kernels


_F32 = dict(rtol=1e-4, atol=1e-4)    # fp32 kernel accumulation vs f32 einsum


def test_gram_batches_under_vmap():
    r = jax.random.normal(jax.random.PRNGKey(0), (4, 5, 300))
    got = jax.jit(jax.vmap(gram))(r)
    np.testing.assert_allclose(got, jnp.einsum("bdn,ben->bde", r, r), **_F32)


def test_row_gram_batches_under_vmap_including_mixed_batching():
    r = jax.random.normal(jax.random.PRNGKey(1), (4, 5, 300))
    v = jax.random.normal(jax.random.PRNGKey(2), (4, 300))
    got = jax.vmap(row_gram)(v, r)
    np.testing.assert_allclose(got, jnp.einsum("bdn,bn->bd", r, v), **_F32)
    # r batched, v shared: the rule broadcasts the unbatched operand
    got2 = jax.vmap(lambda rr: row_gram(v[0], rr))(r)
    np.testing.assert_allclose(got2, jnp.einsum("bdn,n->bd", r, v[0]), **_F32)


def test_gram_nested_vmap_flattens():
    r = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 5, 300))
    got = jax.vmap(jax.vmap(gram))(r)
    np.testing.assert_allclose(got, jnp.einsum("abdn,aben->abde", r, r),
                               **_F32)


def _kernel_op(name):
    """One main-path kernel op over (r (D, N), v (N,)), every other operand
    shared: a multi-block grid (block_n=128 over 300 instances)."""
    d = 6
    m = jax.random.normal(jax.random.PRNGKey(4), (d, 2 * d))
    m_inv = m @ m.T / (2 * d) + jnp.eye(d)
    s = jnp.sum(m_inv, axis=1)
    eta = jnp.sum(s)
    steps = 0.5 ** jnp.arange(1, 5, dtype=jnp.float32)
    return d, {
        "gram": lambda r, v: gram(r, block_n=128),
        "row_gram": lambda r, v: row_gram(v, r, block_n=128),
        "probe_sweep": lambda r, v: probe_sweep(r, m_inv, s, eta, 2, steps,
                                                block_n=128),
        "commit_sweep": lambda r, v: commit_sweep(
            r, m_inv, s, eta, 2, 0.05 * v, 1.0, 0.0, -jnp.inf, 1.0,
            block_n=128),
    }[name]


@pytest.mark.parametrize("name", ["gram", "row_gram", "probe_sweep",
                                  "commit_sweep"])
def test_unbatched_kernel_call_is_row_zero_of_the_batch(name):
    """An unbatched op call runs the batch-gridded kernel at B=1, so it
    equals row 0 of the vmapped call bit for bit."""
    d, op = _kernel_op(name)
    r = jax.random.normal(jax.random.PRNGKey(5), (3, d, 300))
    v = jax.random.normal(jax.random.PRNGKey(6), (3, 300))
    single = jax.tree.leaves(op(r[0], v[0]))
    batched = jax.tree.leaves(jax.vmap(op)(r, v))
    assert len(single) == len(batched)
    for one, many in zip(single, batched):
        np.testing.assert_array_equal(np.asarray(one), np.asarray(many[0]))


def test_use_kernel_spec_compiles_in_batch_fit():
    """The PR's acceptance bar: no serial fit() fallback for use_kernel."""
    spec = _spec(use_kernel=True)
    rs = api.batch_fit(spec, 2)                    # compiled by default now
    ser = api.batch_fit(spec, 2, compiled=False)
    for t in range(2):
        for field in ("train_mse", "test_mse", "eta"):
            np.testing.assert_allclose(
                getattr(rs[t].history, field), getattr(ser[t].history, field),
                rtol=5e-4, err_msg=f"trial {t} {field}")


# -------------------------------------------------- trial-axis device sharding


def test_sharded_batch_matches_vmap_and_serial():
    """Runs at whatever device count the host exposes (8 in CI): the sharded
    program, the single-device vmap, and serial fit() must agree."""
    spec = _spec()
    n_trials = 2 * len(jax.devices()) + 1          # non-divisible when k > 1
    rs = api.batch_fit(spec, n_trials)             # trial_devices=None: all
    vm = api.batch_fit(
        api.replace(spec, backend=api.BackendSpec(trial_devices=1)), n_trials)
    for t in range(n_trials):
        for field in ("train_mse", "test_mse", "eta"):
            np.testing.assert_allclose(
                getattr(rs[t].history, field), getattr(vm[t].history, field),
                rtol=5e-4, err_msg=f"trial {t} {field}")   # f32; f64 below
    ser = api.fit(api.trial_spec(spec, n_trials - 1))      # a padded-tail trial
    np.testing.assert_allclose(rs[n_trials - 1].history.test_mse,
                               ser.history.test_mse, rtol=5e-4)


def test_make_trial_mesh_validates():
    with pytest.raises(ValueError, match="host device"):
        make_trial_mesh(len(jax.devices()) + 1)
    assert make_trial_mesh(1).axis_names == ("trials",)


def test_backend_spec_knobs_validate():
    with pytest.raises(api.SpecError, match="trial_devices"):
        api.BackendSpec(trial_devices=0).validate()
    with pytest.raises(api.SpecError, match="compute_dtype"):
        api.BackendSpec(compute_dtype="f16").validate()
    with pytest.raises(api.SpecError, match="host device"):
        api.batch_fit(api.replace(_spec(), backend=api.BackendSpec(
            trial_devices=len(jax.devices()) + 1)), 2)
    # knobs round-trip through the strict dict serialisation
    spec = api.replace(_spec(), backend=api.BackendSpec(
        trial_devices=1, compute_dtype="float32", donate=False))
    assert api.spec_from_dict(api.spec_to_dict(spec)) == spec


def test_compute_dtype_casts_the_solve():
    spec = api.replace(_spec(), backend=api.BackendSpec(compute_dtype="float32"))
    rs = api.batch_fit(spec, 2)
    assert np.isfinite(rs.test_mse_mean)
    assert rs[0].f.dtype == jnp.float32


# ----------------------------------------------------- converged-sweep record


def test_converged_at_matches_serial_early_stop():
    # big eps: the serial run stops after the first comparable record pair
    spec = _spec(n_sweeps=6, eps=1e6)
    ser = api.fit(spec)
    rs = api.batch_fit(spec, 2)
    assert len(rs[0].history.train_mse) == spec.solver.n_sweeps + 1  # static
    assert len(ser.history.train_mse) == 3                          # truncated
    assert ser.history.converged_at == len(ser.history.train_mse) - 1
    assert rs[0].history.converged_at == ser.history.converged_at
    assert rs.converged_sweeps == [2, 2]
    # eps that never fires: the compiled record points at the last sweep
    rs2 = api.batch_fit(_spec(n_sweeps=2, eps=0.0), 1)
    assert rs2[0].history.converged_at == 2


def test_history_round_trips_converged_at(tmp_path):
    rs = api.batch_fit(_spec(), 1)
    h = rs[0].history
    back = api.History.from_dict(h.as_dict())
    assert back.converged_at == h.converged_at is not None
    d = rs[0].save(str(tmp_path / "res"))
    assert api.load(d).history.converged_at == h.converged_at
    # histories without the field (pre-PR-4 saves) load as None
    legacy = {k: v for k, v in h.as_dict().items() if k != "converged_at"}
    assert api.History.from_dict(legacy).converged_at is None


# ------------------------------------------------------------ minimax batching


def test_robust_weights_signature_is_optional():
    hints = typing.get_type_hints(minimax.robust_weights)
    assert hints["a_init"] == typing.Optional[jnp.ndarray]
    sig = inspect.signature(minimax.robust_weights)
    assert sig.parameters["a_init"].default is None


def test_robust_weights_batches_under_vmap():
    """The PGD inner solver is pure lax.scan — vmapping the trial axis must
    give exactly the per-trial answers (no host sync, no cross-batch leak).
    f64 so only genuine semantic divergence could fail the bound (f32 shows
    harmless batched-matmul reduction-order noise ~1e-4)."""
    with jax.enable_x64(True):
        keys = jax.random.split(jax.random.PRNGKey(5), 3)
        r = jax.vmap(lambda k: jax.random.normal(k, (4, 50)))(keys)
        a0s = jnp.einsum("bdn,ben->bde", r, r) / 50.0
        batched = jax.jit(jax.vmap(
            lambda a0: minimax.robust_weights(a0, 0.05, steps=60, lr=0.05)))(a0s)
        for i in range(3):
            one = minimax.robust_weights(a0s[i], 0.05, steps=60, lr=0.05)
            np.testing.assert_allclose(batched[i], one, rtol=1e-10)


def test_minimax_steps_plumbed_into_upper_bound():
    spec = api.replace(_spec(), solver=api.SolverSpec(
        n_sweeps=1, alpha=10.0, delta=0.01, minimax_steps=7, minimax_lr=0.02))
    res = api.fit(spec)
    ub_spec = res.minimax_upper_bound()
    # a very different budget must change the PGD answer => the spec's knobs
    # genuinely reach the bound solver
    res_long = api.fit(api.spec_with(spec, "solver.minimax_steps", 900))
    assert ub_spec != pytest.approx(res_long.minimax_upper_bound(), rel=1e-12)


# --------------------------------------- 8-device subprocess (the full matrix)

_SCRIPT = r"""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro import api

spec = api.ExperimentSpec(
    data=api.DataSpec(n_train=120, n_test=120, seed=3),
    agent=api.AgentSpec(family="polynomial", options=(("degree", 3),)),
    solver=api.SolverSpec(n_sweeps=2, eps=0.0))

def check(a, b, what, rtol=1e-10):
    for f in ("train_mse", "test_mse", "eta"):
        np.testing.assert_allclose(getattr(a.history, f), getattr(b.history, f),
                                   rtol=rtol, err_msg=f"{what} {f}")

# 11 trials on 8 devices: padding/masking path, f64 machine-precision parity
rs = api.batch_fit(spec, 11)
vm = api.batch_fit(api.replace(spec, backend=api.BackendSpec(trial_devices=1)), 11)
ser = [api.fit(api.trial_spec(spec, t)) for t in range(11)]
for t in range(11):
    check(rs[t], vm[t], f"sharded-vs-vmap t={t}")
    check(rs[t], ser[t], f"sharded-vs-serial t={t}")

# Pallas-kernel path compiles and matches serial under the trial vmap.
# The kernel accumulates in fp32 BY DESIGN (MXU contract), so two
# differently-fused fp32 programs agree at fp32 resolution, not f64 —
# 1e-5 is the same bar the PR-2 engine-parity tests use for f32.
spec_k = api.spec_with(spec, "solver.use_kernel", True)
rk = api.batch_fit(spec_k, 3)
for t in range(3):
    check(rk[t], api.fit(api.trial_spec(spec_k, t)), f"kernel t={t}", rtol=1e-5)

# shard_map backend: compiled lax.scan trial loop == serial run_distributed
spec_sm = api.replace(spec, backend=api.BackendSpec(name="shard_map"))
rsm = api.batch_fit(spec_sm, 3)
for t in range(3):
    check(rsm[t], api.fit(api.trial_spec(spec_sm, t)), f"shard_map t={t}")
assert rsm.converged_sweeps == [2, 2, 2]

for name in ("averaging", "residual_refitting"):
    s = api.spec_with(spec_sm, "solver.name", name)
    r1 = api.batch_fit(s, 2)
    r2 = api.batch_fit(s, 2, compiled=False)
    for t in range(2):
        check(r1[t], r2[t], f"{name} t={t}")
print("BATCH_PARALLEL_OK")
"""


@pytest.mark.slow
def test_eight_device_parity_matrix():
    """ISSUE 4 acceptance: on 8 forced host devices, in f64, the sharded
    batch == single-device vmap == serial fit at 1e-10 relative (including a
    non-divisible n_trials), the Pallas-kernel path compiles under the trial
    vmap, and the shard_map backend's compiled scan replaces the serial
    fallback for every built-in solver."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "BATCH_PARALLEL_OK" in out.stdout
