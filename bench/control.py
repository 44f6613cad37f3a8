"""Readings that set a cell's limits: the program against the reference on
many seeds, and on a few seeds each of `FAULTS`: the control (the
reference at the next lower precision, "high", in the program's place) and
faults planted in the program.

    python3 -m bench.control --workload batch.friedman1-d5 --seeds 12 \
        --faults control_high,frozen_sweep --fault-seeds 3 \
        --first-seed 1000 [--write-limits eta_rel_med,eta_rel0]

Runs in one process on the chip the cell asks for; each seed gets a fresh
driver, one short window and the cell's own check, and each reading is
judged against bench/limits/<cell>.json as a run would judge it.  Prints
one JSON line per seed and a summary with, for each number, the largest
program reading and the smallest reading under each fault.
`--write-limits` writes the limits file for the named numbers (see
`write_limits`); the limit lies two thirds of the way from the lower to
the upper reading on a log scale.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

from bench import run


def _frozen_sweep():
    """A sweep that returns its state unchanged (the batch programs already
    compiled are dropped, so that the next call traces the fault in)."""
    from repro.api import runner
    from repro.core import icoa
    from repro.transport import Ledger

    real = icoa.sweep

    def frozen(family, cfg, params, f, xcols, y, key, ledger=None,
               round_=None):
        return params, f, key, Ledger.empty() if ledger is None else ledger, {}

    icoa.sweep = frozen
    runner.clear_program_cache()
    return lambda: (setattr(icoa, "sweep", real),
                    runner.clear_program_cache())


def _half_batch():
    """Half of a Monte-Carlo batch left out: trial t computes trial t // 2."""
    import jax.numpy as jnp
    from repro.api import runner

    real = runner._local_trials
    runner._local_trials = lambda spec, n: jnp.arange(n) // 2
    runner.clear_program_cache()
    return lambda: (setattr(runner, "_local_trials", real),
                    runner.clear_program_cache())


def _frozen_trials():
    """The sweeps of one trial in sixteen (8 of 128) leave its records where
    the non-cooperative start put them."""
    from repro.api import runner

    real = runner._run_batch_program

    def run(fn, spec, trials):
        out = dict(real(fn, spec, trials))
        few = max(1, out["eta"].shape[0] // 16)
        for k in ("train_mse", "test_mse", "eta"):
            out[k] = out[k].at[:few, 1:].set(out[k][:few, :1])
        return out

    runner._run_batch_program = run
    return lambda: setattr(runner, "_run_batch_program", real)


def _control_high():
    """The control in the program's place: every record that the check
    reads comes from the configuration's own reference at "high" instead
    (`BatchDriver.reference`, the module the configuration names)."""
    from bench import drivers

    real = drivers.BatchDriver._call

    def call(self):
        hists = real(self)
        for h, low in zip(hists, self.reference(precision="high")):
            for name, v in zip(("train_mse", "test_mse", "eta"), low):
                getattr(h, name)[:len(v)] = [float(x) for x in v]
        return hists

    drivers.BatchDriver._call = call
    return lambda: setattr(drivers.BatchDriver, "_call", real)


FAULTS = {"frozen_sweep": _frozen_sweep, "half_batch": _half_batch,
          "frozen_trials": _frozen_trials, "control_high": _control_high}


def _readings(cell, api, seed: int, seconds: float):
    from bench import drivers

    drv = drivers.make(cell, seed, api)
    drv.setup()
    drv.window(seconds)
    drv.free()
    return drv.check()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faults", default="control_high",
                    help="comma-separated names of FAULTS")
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--write-limits", default="")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload, args.cpu_rehearsal)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(run.ROOT, ".jax_cache"))
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    devices = jax.devices()
    if not args.cpu_rehearsal and (devices[0].platform != "tpu"
                                   or len(devices) < cell.chips):
        print(f"control: needs {cell.chips} TPU chip(s)", file=sys.stderr)
        return 3
    from bench import data
    from repro import api

    data.register_all(api.register_source)
    seed = args.first_seed
    program, faulted = [], {}

    def show(line, nums):
        ok, _ = run._compare(nums, cell.limits)
        print(json.dumps({**line, "correct": ok, "numbers": nums}),
              flush=True)

    for _ in range(args.seeds):
        seed += 7919
        nums = _readings(cell, api, seed, args.seconds)
        program.append(nums)
        show({"seed": seed, "run": "program"}, nums)
    for name in filter(None, args.faults.split(",")):
        undo = FAULTS[name]()
        try:
            for _ in range(args.fault_seeds):
                seed += 7919
                nums = _readings(cell, api, seed, args.seconds)
                faulted.setdefault(name, []).append(nums)
                show({"seed": seed, "run": name}, nums)
        finally:
            undo()
    summary = {k: {"program_max": max(p[k] for p in program),
                   "fault_min": {f: min(r[k] for r in rs)
                                 for f, rs in faulted.items()}}
               for k in sorted(program[0])}
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "fault_seeds": args.fault_seeds, "summary": summary}),
          flush=True)
    if args.write_limits:
        write_limits(args.workload, summary, args.write_limits.split(","))
    return 0


def write_limits(workload: str, summary: dict, names) -> None:
    """The limit of each named number, from the program's largest reading
    (lower) and the least upper reading: the control's smallest where that
    is three times the lower or more, a fault's smallest where that is ten
    times the lower (three for a sweep that changes nothing).  The file
    also lists the faults that some limit catches on every seed."""
    numbers = {}
    for k in names:
        s = summary[k]
        lower = s["program_max"]
        uppers = {f: v for f, v in s["fault_min"].items()
                  if v >= (3 if f == "control_high" or "frozen" in f
                           else 10) * lower}
        if lower <= 0 or not uppers:
            print(f"control: {k} has no upper reading; not compared",
                  file=sys.stderr)
            continue
        upper = min(uppers.values())
        limit = math.exp((math.log(lower) + 2 * math.log(upper)) / 3)
        numbers[k] = {"limit": float("%.3g" % limit), "lower": lower,
                      "upper": upper,
                      "upper_from": min(uppers, key=uppers.get)}
    # the faults that read above some limit on every seed they ran:
    # bench/tests/test_faults.py plants each at the rehearsal size
    faults = sorted({f for k, lim in numbers.items()
                     for f, v in summary[k]["fault_min"].items()
                     if v > lim["limit"]})
    path = os.path.join(run.BENCH, "limits", workload + ".json")
    with open(path, "w") as f:
        json.dump({"numbers": numbers, "faults": faults}, f, indent=1)
    print(f"control: wrote {path}: {json.dumps(numbers)}", file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
