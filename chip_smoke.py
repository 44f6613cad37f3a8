"""On-chip smoke test: the ICOA main path, end to end, through its entry points.

    python chip_smoke.py              # one TPU chip: fit, minimax, batch,
                                      # stream+serve
    python chip_smoke.py --chips 4    # four chips: sharded batch_fit and the
                                      # shard_map backend vs one device
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal [--chips 4]
                                      # tiny sizes, interpreted kernels

Without a TPU (and without --cpu-rehearsal) it exits non-zero before any
phase runs.  Each phase prints one JSON line with its numbers and checks;
the last line is {"ok": ..., "device": {"platform", "kind", "count"}} and
the exit code is non-zero if any check failed.  Everything runs in f32 with
x64 off, in this one process.  Latencies and durations printed here are
smoke readings, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

REL_TOL = 1e-2      # fused/kernel vs plain reference, final test MSE


@dataclasses.dataclass(frozen=True)
class Sizes:
    d: int                 # cosine parties (one attribute each)
    n: int                 # train = test instances
    sweeps: int
    batch_n: int           # Fig. 1 scenario instances
    window: int            # stream ring window = resweep cadence
    stream_total: int
    chips4_n: int


CHIP = Sizes(d=100, n=65536, sweeps=10, batch_n=2000, window=16384,
             stream_total=65536, chips4_n=65536)
REHEARSAL = Sizes(d=8, n=1024, sweeps=3, batch_n=256, window=512,
                  stream_total=2048, chips4_n=1024)
BUCKETS = (1, 16, 128)


def _with(spec, changes: dict):
    """`spec` with each dotted field of `changes` replaced."""
    from repro import api

    for path, value in changes.items():
        spec = api.spec_with(spec, path, value)
    return spec


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def _specs(sz: Sizes):
    from repro import api

    fit = api.ExperimentSpec(
        data=api.DataSpec(source="cosine", n_attrs=sz.d, n_train=sz.n,
                          n_test=sz.n),
        agent=api.AgentSpec(family="polynomial", options=(("degree", 4),)),
        solver=api.SolverSpec(name="icoa", engine="fused", use_kernel=True,
                              n_sweeps=sz.sweeps))
    ref = _with(fit, {"solver.engine": "incremental",
                                "solver.use_kernel": False})
    return fit, ref


def phase_fit(sz: Sizes) -> dict:
    import jax
    import jax.numpy as jnp

    from repro import api

    fit, ref = _specs(sz)
    fused = api.fit(fit)
    ref_tpu = api.fit(ref)
    avg = api.fit(_with(ref, {"solver.name": "averaging"}))
    cpu = jax.devices("cpu")[0]
    api.clear_dataset_cache()           # regenerate the data on the host
    with jax.default_device(cpu):
        ref_cpu = api.fit(ref)
    api.clear_dataset_cache()
    host_ok = ref_cpu.weights.devices() == {cpu}
    # the compiled program of the same spec must carry the Mosaic kernels
    text = jax.jit(api.build_runner(fit)).lower(
        jnp.asarray(0, jnp.int32)).compile().as_text()
    kernels = text.count("tpu_custom_call")
    mse = {"fused_kernel": fused.test_mse, "incremental_xla": ref_tpu.test_mse,
           "incremental_host_cpu": ref_cpu.test_mse, "averaging": avg.test_mse}
    rel = {"fused_kernel": _rel(fused.test_mse, ref_cpu.test_mse),
           "incremental_xla": _rel(ref_tpu.test_mse, ref_cpu.test_mse)}
    checks = {
        "kernel_in_program": (kernels > 0 if jax.default_backend() == "tpu"
                              else None),
        "host_reference_on_cpu": host_ok,
        "agree_1e-2": all(r <= REL_TOL for r in rel.values()),
        "icoa_beats_averaging": fused.test_mse < avg.test_mse,
    }
    return {"d": sz.d, "n": sz.n, "sweeps": len(fused.history.eta) - 1,
            "test_mse": mse, "rel_to_host_reference": rel,
            "tpu_custom_calls": kernels, "checks": checks}


def phase_minimax(sz: Sizes) -> dict:
    from repro import api

    fit, _ = _specs(sz)
    spec = _with(fit, {"solver.engine": "incremental",
                       "solver.alpha": 100.0, "solver.delta": 0.01})
    res = api.fit(spec)
    bound = res.minimax_upper_bound()
    sweeps = len(res.history.eta) - 1
    expected = (api.comm_floats_per_sweep(spec.solver, sz.d, sz.n) * 8
                * sweeps)
    checks = {
        "finite": all(map(_finite, (res.test_mse, bound, res.history.total_bytes))),
        "test_mse_within_eq28_bound": res.test_mse <= bound,
        "ledger_matches_analytic": res.history.total_bytes == expected,
    }
    return {"alpha": 100.0, "delta": 0.01, "sweeps": sweeps,
            "test_mse": res.test_mse, "eq28_bound": bound,
            "total_bytes": res.history.total_bytes,
            "analytic_bytes": expected, "checks": checks}


def _finite(x: float) -> bool:
    return x == x and abs(x) != float("inf")


def _fig1_spec(n: int, sweeps: int):
    from repro import api

    return api.ExperimentSpec(
        data=api.DataSpec(source="friedman1", n_train=n, n_test=n),
        agent=api.AgentSpec(family="polynomial", options=(("degree", 4),)),
        solver=api.SolverSpec(name="icoa", engine="fused", use_kernel=True,
                              n_sweeps=sweeps))


def phase_batch(sz: Sizes) -> dict:
    import numpy as np

    from repro import api

    spec = _fig1_spec(sz.batch_n, sz.sweeps)
    ref = _with(spec, {"solver.engine": "incremental",
                       "solver.use_kernel": False})
    avg = _with(ref, {"solver.name": "averaging"})
    kern = api.batch_fit(spec, 8).stack("test_mse")[:, -1]
    xla = api.batch_fit(ref, 8).stack("test_mse")[:, -1]
    base = api.batch_fit(avg, 8).stack("test_mse")[:, -1]
    rel = np.abs(kern - xla) / np.abs(xla)
    checks = {"finite": bool(np.all(np.isfinite(kern))),
              "agree_1e-2": bool(np.all(rel <= REL_TOL)),
              "icoa_beats_averaging": bool(kern.mean() < base.mean())}
    return {"trials": 8, "d": 5, "n": sz.batch_n,
            "test_mse_kernel": kern.tolist(), "test_mse_xla": xla.tolist(),
            "test_mse_averaging_mean": float(base.mean()),
            "max_rel": float(rel.max()), "checks": checks}


def phase_stream(sz: Sizes) -> dict:
    import contextlib

    import numpy as np

    from repro import api
    from repro.analysis import recompile
    from repro.stream import PredictEngine

    fit, _ = _specs(sz)
    chunk = 64
    spec = api.StreamSpec(
        experiment=_with(fit, {"solver.n_sweeps": 1}),
        window=sz.window, chunk=chunk, total_instances=sz.stream_total,
        resweep_every=sz.window, serve_buckets=BUCKETS)
    groups = spec.experiment.data.groups
    family = spec.experiment.agent.resolve(n_cols=len(groups[0]))
    # every program has compiled once the first resweep is published: the
    # initial publish, one per chunk ingested, one per resweep
    warm_updates = 1 + sz.window // chunk + 1
    steady = contextlib.ExitStack()
    window = {}

    class Engine(PredictEngine):
        """Opens the compile counter on the first steady-state publish."""
        updates = 0

        def update(self, params, weights, alive=None):
            super().update(params, weights, alive=alive)
            self.updates += 1
            if self.updates == warm_updates:
                window["log"] = steady.enter_context(
                    recompile.count_compilations())

    engine = Engine(family, groups, sz.d, BUCKETS)
    stop = threading.Event()
    rng = np.random.default_rng(0)
    reqs = [rng.uniform(-1.0, 1.0, (b, sz.d)).astype(np.float32)
            for b in BUCKETS]

    def serve():
        while engine._params is None and not stop.is_set():
            time.sleep(0.001)
        i = 0
        while not stop.is_set():
            engine.predict(reqs[i % len(reqs)])
            i += 1

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    try:
        with steady:
            res = api.stream_fit(spec, engine=engine)
    finally:
        stop.set()
        thread.join(timeout=30.0)
    log = window.get("log")
    pct = {str(b): engine.latency[b].percentiles((50, 99)) for b in BUCKETS}
    checks = {
        "records": len(res.records) == sz.stream_total // sz.window,
        "finite": all(_finite(r["preq_mse"]) for r in res.records),
        "served_every_bucket": all(engine.latency[b].count > 0
                                   for b in BUCKETS),
        "steady_window_zero_compiles": log is not None and log.total == 0,
        "server_thread_stopped": not thread.is_alive(),
    }
    return {"window": sz.window, "chunk": chunk,
            "instances": sz.stream_total,
            "preq_mse": [r["preq_mse"] for r in res.records],
            "total_bytes": res.total_bytes,
            "smoke_requests": int(engine.requests.total),
            "smoke_latency_s": pct,
            "steady_compiles": None if log is None else log.counts,
            "checks": checks}


def _peak_bytes(devices) -> list:
    stats = [d.memory_stats() for d in devices]
    return [None if s is None else int(s.get("peak_bytes_in_use", 0))
            for s in stats]


def phase_chips4(sz: Sizes) -> dict:
    import jax
    import numpy as np

    from repro import api

    devices = jax.devices()
    spec = _fig1_spec(sz.batch_n, sz.sweeps)
    one = api.batch_fit(_with(spec, {"backend.trial_devices": 1}), 8)
    peak_one = _peak_bytes(devices)
    four = api.batch_fit(_with(spec, {"backend.trial_devices": 4}), 8)
    peak_four = _peak_bytes(devices)
    m1, m4 = one.stack("test_mse")[:, -1], four.stack("test_mse")[:, -1]
    batch_rel = float(np.max(np.abs(m4 - m1) / np.abs(m1)))

    local = api.ExperimentSpec(
        data=api.DataSpec(source="correlated_linear", n_attrs=8,
                          partition="blocks", n_agents=4,
                          n_train=sz.chips4_n, n_test=sz.chips4_n),
        solver=api.SolverSpec(name="icoa", n_sweeps=sz.sweeps))
    sharded = _with(local, {"backend.name": "shard_map"})
    res_local, res_shard = api.fit(local), api.fit(sharded)
    mesh_devices = {d for leaf in jax.tree.leaves(res_shard.params)
                    for d in leaf.devices()}
    shard_rel = _rel(res_shard.test_mse, res_local.test_mse)
    on_others = [p for p in peak_four[1:] if p is not None]
    checks = {
        "four_devices": len(devices) == 4,
        "batch_per_trial_match": batch_rel <= 1e-5,
        "shard_map_matches_local": shard_rel <= 1e-4,
        "mesh_spans_four": len(mesh_devices) == 4,
        "sharded_batch_reaches_devices_1_3": (
            all(p > 0 for p in on_others) if on_others else None),
    }
    return {"batch_test_mse_1dev": m1.tolist(),
            "batch_test_mse_4dev": m4.tolist(), "batch_max_rel": batch_rel,
            "shard_map_test_mse": res_shard.test_mse,
            "local_test_mse": res_local.test_mse, "shard_map_rel": shard_rel,
            "peak_bytes_after_1dev": peak_one,
            "peak_bytes_after_4dev": peak_four, "checks": checks}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip paths and their "
                         "one-device references")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU at tiny sizes with interpreted "
                         "kernels (the platform is reported as cpu)")
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache(os.path.join(ROOT, ".jax_cache"))
    devices = jax.devices()
    platform = devices[0].platform
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if platform != want:
        print(f"chip_smoke: needs platform {want!r}, JAX found {platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    if jax.config.jax_enable_x64:
        print("chip_smoke: runs in f32; unset JAX_ENABLE_X64", file=sys.stderr)
        return 2

    sz = REHEARSAL if args.cpu_rehearsal else CHIP
    phases = ([phase_chips4] if args.chips == 4 else
              [phase_fit, phase_minimax, phase_batch, phase_stream])
    ok = True
    for phase in phases:
        name = phase.__name__[len("phase_"):]
        t0 = time.perf_counter()
        try:
            out = phase(sz)
            passed = all(v is not False for v in out["checks"].values())
        except Exception as e:
            traceback.print_exc()
            out, passed = {"error": f"{type(e).__name__}: {e}"}, False
        ok &= passed
        print(json.dumps({"phase": name, "ok": passed,
                          "smoke_seconds": time.perf_counter() - t0, **out}),
              flush=True)
    print(json.dumps({"ok": ok, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
