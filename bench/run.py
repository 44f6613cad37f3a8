"""Run one cell of the on-chip benchmark and print its result line.

    python3 -m bench.run --workload batch.friedman1-d5 --seed 7 --seconds 10 --trace 0

The cell is looked up by name in BENCHMARK.json; its configuration
(bench/configs), traffic mix (bench/traffic), per-layer readers
(bench/metrics), limits (bench/limits) and reference (the module under
bench/ that the configuration's "reference" names, bench/reference.py by
default; see bench/drivers.py) are files found by name, so a cell is added
with new files and entries only, a deployment with mathematics of its own
included.  One run sets up and warms every shape the cell uses
(`setup_s`), measures for `--seconds` (with `--trace 1` for the traffic's
`trace_seconds` at most, under the profiler), then frees
the program's state, runs the reference on what the window produced and
prints one JSON line.  Without a TPU holding the cell's chips it exits 3
and prints no result; `--cpu-rehearsal` runs the tiny sizes of the
configuration on the CPU and prints no metric.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def _load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, rehearsal: bool) -> types.SimpleNamespace:
    """The cell's entry, configuration, traffic, metrics and limits."""
    bench = _load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = _load_json(ROOT, files[cell["config"]])
    traffic = _load_json(BENCH, "traffic", cell["traffic"] + ".json")
    if rehearsal:
        config = _merge(config, config.get("rehearsal", {}))
        traffic = _merge(traffic, traffic.get("rehearsal", {}))
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in moved
                                  else [])]
    limits_file = os.path.join(BENCH, "limits", name + ".json")
    limits = (_load_json(limits_file)["numbers"]
              if os.path.exists(limits_file) else {})
    return types.SimpleNamespace(name=name, chips=cell["chips"],
                                 config=config, traffic=traffic, e2e=e2e,
                                 per_layer=per_layer, limits=limits)


def _reader(metric_name: str):
    """bench/metrics/<name>.py, else the reader of the name's first part
    (device_idle.fit -> device_idle.py)."""
    for stem in (metric_name, metric_name.split(".")[0]):
        path = os.path.join(BENCH, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "bench_metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for per-layer metric {metric_name}")


class Compiles:
    """Counts XLA compile requests while `armed`, and how many of them the
    persistent cache served (a copy of the idea in the program's recompile
    counter, kept with the benchmark)."""

    def __init__(self, monitoring):
        self.armed = False
        self.count = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        del duration, kw
        if self.armed and event == COMPILE_EVENT:
            self.count += 1

    def _on_event(self, event, **kw):
        del kw
        if self.armed and event == CACHE_HIT_EVENT:
            self.hits += 1


def _device_info(jax, devices, chips: int) -> dict:
    peaks = []
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips, "memory_peak_bytes": max(peaks)}


def _compare(numbers: dict, limits: dict) -> tuple:
    """(correct, compared): every number that has a limit, beside it."""
    compared = {}
    ok = bool(limits)
    for key, lim in limits.items():
        value = numbers.get(key)
        good = (value is not None and math.isfinite(value)
                and value <= lim["limit"])
        ok = ok and good
        compared[key] = {"value": value, "limit": lim["limit"]}
    return ok, compared


def _traced_window(jax, trace_reduce, driver, seconds: float):
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            out = driver.window(seconds)
    finally:
        jax.profiler.stop_trace()
    try:
        trace = trace_reduce.load(tdir)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    return out, trace


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU; prints no metric")
    ap.add_argument("--keep-trace", metavar="PATH",
                    help="also write the flattened trace to PATH (.json)")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    cell = load_cell(args.workload, args.cpu_rehearsal)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # the configuration's stated precision, for every matmul the program
    # does not pin itself
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    devices = jax.devices()
    if not args.cpu_rehearsal and (devices[0].platform != "tpu"
                                   or len(devices) < cell.chips):
        print(f"bench: this cell needs {cell.chips} TPU chip(s); JAX finds "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3

    from bench import data, drivers, trace_reduce

    compiles = Compiles(jax.monitoring)
    from repro import api
    data.register_all(api.register_source)
    driver = drivers.make(cell, args.seed, api)
    driver.setup()
    gc.collect()
    setup_s = time.perf_counter() - t_start

    compiles.armed = True
    trace = None
    if args.trace:
        seconds = min(args.seconds, cell.traffic["trace_seconds"])
        out, trace = _traced_window(jax, trace_reduce, driver, seconds)
        if args.keep_trace:
            with open(args.keep_trace, "w") as f:
                json.dump(trace, f)
            print(json.dumps(trace_reduce.summarize(trace)), file=sys.stderr)
    else:
        out = driver.window(args.seconds)
    compiles.armed = False
    device = _device_info(jax, devices, cell.chips)
    print(f"bench: compiles inside the window: {compiles.count - compiles.hits}"
          f" (and {compiles.hits} programs loaded from the persistent cache)",
          file=sys.stderr)

    metrics = {}
    if args.trace:
        lo, hi = trace_reduce.window(trace, "bench.window")
        devs = trace["devices"][:cell.chips]
        busy = [trace_reduce.busy_ns(d, lo, hi) for d in devs]
        device["busy_s"] = sum(busy) / max(len(busy), 1) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        peaks = _load_json(BENCH, "peaks.json")
        if not args.cpu_rehearsal and device["kind"] not in peaks:
            raise KeyError(f"no peaks for device kind {device['kind']!r} "
                           f"in bench/peaks.json")
        ctx = types.SimpleNamespace(
            trace=trace, lo=lo, hi=hi, devices=devs, cell=cell,
            peaks=peaks.get(device["kind"]), work=out["work"])
        for m in cell.per_layer:
            value = _reader(m["name"])(ctx, m)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {
            "device_ops": trace_reduce.top(
                {k: v / len(devs) for k, v in _sum_dicts(
                    trace_reduce.time_by_name(d["ops"], lo, hi)
                    for d in devs).items()}),
            "idle_gaps": trace_reduce.top(
                {k: v / len(devs) for k, v in _sum_dicts(
                    trace_reduce.idle_gaps(d, trace["host"], lo, hi)
                    for d in devs).items()}),
        }
    else:
        for m in cell.e2e:
            value = setup_s if m["name"] == "setup_s" else out["e2e"][m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    driver.free()
    gc.collect()
    numbers = driver.check()
    correct, compared = _compare(numbers, cell.limits)
    for key, value in numbers.items():
        print(f"bench: reading {key} = {value!r}", file=sys.stderr)
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = breakdown
    if args.cpu_rehearsal:
        result["rehearsal_numbers"] = result.pop("metrics")
        result["metrics"] = {}
    result["compared"] = compared
    for key, c in compared.items():
        print(f"bench: {key} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def _sum_dicts(dicts) -> dict:
    out: dict = {}
    for d in dicts:
        for k, v in d.items():
            out[k] = out.get(k, 0.0) + v
    return out


if __name__ == "__main__":
    sys.exit(main())
