"""The four-party agent mesh through `api.batch_fit` on the shard_map
backend, at a small size: eight correlated attributes in four parties of
two columns, one party per device, fused engine with kernels, against the
plain float32 reference (bench/reference.py) and against the local backend.

Runs in a subprocess with 4 forced host devices (the main test process
keeps one)."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import sys
import jax
import numpy as np
sys.path.insert(0, REPO)
from bench import data, drivers, reference
from repro import api

data.register_all(api.register_source)
cfg = {
    "data": {"source": "correlated_linear", "n_attrs": 8, "n_train": 512,
             "n_test": 512, "noise": 0.0,
             "options": {"rho": 0.6, "snr": 10.0}, "partition": "blocks",
             "n_agents": 4, "groups": [[0, 1], [2, 3], [4, 5], [6, 7]]},
    "agent": {"family": "polynomial", "degree": 1},
    "solver": {"name": "icoa", "n_sweeps": 3, "alpha": 1.0, "delta": 0.0,
               "engine": "fused", "use_kernel": True},
    "backend": {"name": "shard_map"},
}
spec = drivers.experiment_spec(api, cfg, seed=20260)
trials = 4

mesh = api.batch_fit(spec, trials)
local = api.batch_fit(api.replace(spec, backend=api.BackendSpec(
    name="local", trial_devices=1)), trials)
d = cfg["data"]
groups = drivers.groups_of(cfg)
for t in range(trials):
    # eta = 1 / (1^T A^-1 1) of the parties' strongly correlated residuals:
    # A's float32 rounding reaches eta amplified by its conditioning, and
    # the gaps measured on the CPU stay under 1.5e-5
    xtr, ytr, xte, yte = data.make_split(
        d["source"], d["n_train"], d["n_test"], spec.data.seed + t,
        d["n_attrs"], d["noise"], tuple(sorted(d["options"].items())))
    xc = np.stack([xtr[:, g] for g in groups])
    xct = np.stack([xte[:, g] for g in groups])
    with jax.default_matmul_precision("highest"):
        ref = reference.fit_records(xc, ytr, xct, yte, degree=1, n_sweeps=3)
    np.testing.assert_allclose(mesh[t].history.eta, ref[2], rtol=5e-5,
                               err_msg=f"mesh vs reference, trial {t}")
    for field in ("train_mse", "test_mse", "eta"):
        got = getattr(mesh[t].history, field)
        want = getattr(local[t].history, field)
        # record 0 weighs the parties uniformly on the mesh and optimally
        # on the local backend
        lo = 0 if field == "eta" else 1
        np.testing.assert_allclose(got[lo:], want[lo:], rtol=5e-5,
                                   err_msg=f"mesh vs local {field}, trial {t}")
print("MESH_BATCH_OK")
""".replace("REPO", repr(REPO))


def test_mesh_batch_fit_matches_reference_and_local_backend():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH_BATCH_OK" in out.stdout
