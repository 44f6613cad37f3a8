"""Monte-Carlo batch throughput: trials/sec vs device count and batch size.

The execution engine this PR adds (api.runner, DESIGN.md §7) has three
compiled paths; this suite measures each at the Fig. 1 scenario (friedman1,
5 polynomial agents) and records the curves in ``BENCH_batch.json`` at the
repo root — the perf-trajectory file CI diffs per PR:

  * ``vmap``     single-device jit(vmap(run_fn)) — the pre-PR-4 baseline
  * ``sharded``  trial axis sharded over K host devices (shard_map + vmap)
  * ``scan``     the shard_map backend's compiled per-device trial loop
                 (needs K >= D agent devices; runs at the largest K)

Device count cannot change after jax initialises, so on the CPU each K
runs in a subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=K``.
On an accelerator the parent process holds the chips, and a child could not
reach them, so the suite measures the real devices in-process instead.
Timings exclude compilation (one warm call first) and measure the compiled
program itself — built by the SAME `api.runner` program builders `batch_fit`
executes (`_local_batch_program` / `_shard_map_batch_program`), so the timed
geometry can never drift from production.  `batch_fit` itself re-jits per
call, so its per-call overhead is compile-bound, not execution-bound.

``BENCH_SMOKE=1`` shrinks sizes and device counts for CI smoke tracking.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmarks.common import row
from benchmarks import envelope

__all__ = ["run"]

_OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_batch.json")
_SMOKE = os.environ.get("BENCH_SMOKE", "") not in ("", "0")

# Fig. 1 scenario (poly family), sized for CPU benchmarking
_N_AGENTS = 5
_SCENARIO = dict(n_train=160, n_sweeps=2, n_trials=8) if _SMOKE else \
    dict(n_train=2000, n_sweeps=5, n_trials=32)
# smoke still ends on a scan-capable count (>= _N_AGENTS devices), so the
# CI artifact tracks all three paths, not just vmap/sharded
_DEVICE_COUNTS = (1, 5) if _SMOKE else (1, 2, 4, 8)
_TRIAL_COUNTS = (4, 8) if _SMOKE else (8, 32, 128)
_REPS = 1 if _SMOKE else 2


def _measure(cfg: dict) -> list:
    """Time every path available at this process's device count."""
    import jax

    from repro import api
    from repro.api import runner as runner_mod

    k = len(jax.devices())
    n_sweeps, n_train = cfg["n_sweeps"], cfg["n_train"]

    def spec(backend="local", trial_devices=None):
        return api.ExperimentSpec(
            data=api.DataSpec(source="friedman1", n_train=n_train,
                              n_test=n_train // 2, seed=0),
            agent=api.AgentSpec(family="polynomial", options=(("degree", 4),)),
            solver=api.SolverSpec(name="icoa", n_sweeps=n_sweeps, eps=0.0),
            backend=api.BackendSpec(name=backend, trial_devices=trial_devices))

    def compiled_path(name, n_trials):
        """The production batch program of one path (the same builders
        batch_fit uses), jitted and ready to call."""
        if name == "vmap":
            fn, trials = runner_mod._local_batch_program(
                spec(trial_devices=1), n_trials)
        elif name == "sharded":
            fn, trials = runner_mod._local_batch_program(spec(), n_trials)
        elif name == "scan":
            fn, trials = runner_mod._shard_map_batch_program(
                spec("shard_map"), n_trials)
        else:
            raise ValueError(name)
        return jax.jit(fn), trials

    def measure(name, n_trials):
        fn, trials = compiled_path(name, n_trials)
        out = fn(trials)
        jax.block_until_ready(out)          # compile + warm
        t0 = time.perf_counter()
        for _ in range(cfg["reps"]):
            jax.block_until_ready(fn(trials))
        dt = (time.perf_counter() - t0) / cfg["reps"]
        return {"path": name, "devices": k, "n_trials": n_trials,
                "trials_per_sec": round(n_trials / dt, 2),
                "ms_per_batch": round(dt * 1e3, 1)}

    paths = ["vmap"] if k == 1 else ["sharded"]
    if k >= cfg["n_agents"]:
        paths.append("scan")
    results = [measure(p, cfg["n_trials"]) for p in paths]
    if cfg.get("trial_scaling"):
        # batch-size curve for the parallel paths; the scan path is
        # sequential by construction (one trial at a time on the agent
        # mesh), so its throughput does not scale with batch size — skip
        for n in cfg["trial_counts"]:
            for p in paths:
                if n != cfg["n_trials"] and p != "scan":
                    results.append(measure(p, n))
    return results


def _worker(cfg: dict) -> None:
    """Runs in the subprocess (device count fixed by XLA_FLAGS): print the
    measured rows as one JSON line on stdout."""
    import contextlib

    from repro.analysis import recompile

    # when the parent is audited (DESIGN.md §9.3), count this worker's
    # compiles too and report them on stdout — the parent absorbs them, so
    # the bench_batch audit covers the forked per-device-count runs
    audit = (recompile.count_compilations()
             if os.environ.get("REPRO_RECOMPILE_AUDIT")
             else contextlib.nullcontext(None))
    with audit as compile_log:
        results = _measure(cfg)
    print("BENCH_JSON:" + json.dumps(results))
    if compile_log is not None:
        print("AUDIT_COUNTS:" + json.dumps(compile_log.counts))


def _config(trial_scaling: bool) -> dict:
    return dict(_SCENARIO, reps=_REPS, n_agents=_N_AGENTS,
                trial_scaling=trial_scaling, trial_counts=list(_TRIAL_COUNTS))


def _spawn(devices: int, trial_scaling: bool) -> list:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    root = os.path.join(os.path.dirname(__file__), "..")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root, env.get("PYTHONPATH", "")])
    cfg = _config(trial_scaling)
    code = ("import json,sys; from benchmarks.batch_bench import _worker; "
            "_worker(json.loads(sys.argv[1]))")
    out = subprocess.run([sys.executable, "-c", code, json.dumps(cfg)],
                         env=env, cwd=root, capture_output=True, text=True,
                         timeout=3600)
    if out.returncode != 0:
        raise RuntimeError(f"batch bench worker (devices={devices}) failed:\n"
                           + out.stderr[-2000:])
    rows = None
    for line in out.stdout.splitlines():
        if line.startswith("BENCH_JSON:"):
            rows = json.loads(line[len("BENCH_JSON:"):])
        elif line.startswith("AUDIT_COUNTS:"):
            from repro.analysis import recompile
            recompile.absorb_counts(json.loads(line[len("AUDIT_COUNTS:"):]))
    if rows is None:
        raise RuntimeError(
            f"no BENCH_JSON line from worker (devices={devices})")
    return rows


def run():
    import jax

    results = []
    on_cpu = jax.default_backend() == "cpu"
    device_counts = _DEVICE_COUNTS if on_cpu else (len(jax.devices()),)
    max_k = device_counts[-1]
    for k in device_counts:
        if on_cpu:
            rows = _spawn(k, trial_scaling=(k in (1, max_k)))
        else:
            # one process per chip: this process now holds the accelerator,
            # so the real devices are measured here and no child is started
            rows = _measure(_config(trial_scaling=True))
        results.extend(rows)
        for r in rows:
            us = 1e6 / r["trials_per_sec"]
            yield row(f"batch_{r['path']}_dev{k}_t{r['n_trials']}", us,
                      f"{r['trials_per_sec']}trials/s")

    base = [r for r in results
            if r["path"] == "vmap" and r["n_trials"] == _SCENARIO["n_trials"]]
    best = [r for r in results
            if r["path"] == "sharded" and r["devices"] == max_k
            and r["n_trials"] == _SCENARIO["n_trials"]]
    speedup = (best[0]["trials_per_sec"] / base[0]["trials_per_sec"]
               if base and best else None)
    if speedup is not None:
        yield row(f"batch_speedup_dev{max_k}_vs_vmap", 0, f"{speedup:.2f}x")

    payload = {
        "scenario": dict(_SCENARIO, source="friedman1", n_agents=_N_AGENTS,
                         family="polynomial(degree=4)"),
        "unit": "trials_per_sec",
        "smoke": _SMOKE,
        "host_cpu_count": os.cpu_count(),
        "backend": jax.default_backend(),
        "device_counts": list(device_counts),
        "results": results,
        f"sharded_dev{max_k}_speedup_over_vmap":
            None if speedup is None else round(speedup, 2),
    }
    envelope.write_bench(_OUT, "batch", payload)
    yield row("batch_json", 0, os.path.basename(_OUT))
