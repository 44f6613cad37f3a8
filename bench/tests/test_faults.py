"""The control and faults planted under the timed path must turn `correct`
false.

Each test drives a whole run of a cell at its configuration's rehearsal
size on the CPU (`--cpu-rehearsal` skips the look for a chip; a cell on
more chips gets as many forced host devices) with one of
`bench.control.FAULTS` planted, and checks that some compared number
passes its limit.  The cases come from the data: every cell whose limits
file (bench/limits/<cell>.json) lists `faults`, with each fault listed
there.  `bench.control --write-limits` lists the faults that read above
some limit on every seed of the chip run it takes the limits from, so a
new cell's cases come with its limits file.  The faults:

  * control_high: the configuration's reference at "high" (three
    bfloat16 passes) in the program's place;
  * frozen_sweep: a sweep that returns its state unchanged;
  * half_batch: half of a Monte-Carlo batch left out (trial t computes
    trial t // 2);
  * frozen_trials: one trial in sixteen keeps its non-cooperative start.

A sound run of every cell with a limits file must come out correct; for
a cell on the configuration's own reference this is the configured
program (kernels interpreted) against that reference, through the run's
own check.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
import json
import os
import subprocess
import sys

import pytest

from bench import run

ROOT = run.ROOT
CELLS = {w["name"]: w for w in
         run._load_json(ROOT, "BENCHMARK.json")["workloads"]}


LIMITS = {c: run._load_json(run.BENCH, "limits", c + ".json")
          for c in sorted(CELLS)
          if os.path.exists(os.path.join(run.BENCH, "limits", c + ".json"))}
CASES = [(c, f) for c, lim in LIMITS.items() for f in lim.get("faults", [])]

# one rehearsal run in a fresh process, with the fault planted (None: none)
_SCRIPT = r"""
import contextlib, io, sys
sys.path.insert(0, {root!r})
sys.path.insert(0, {root!r} + "/src")
from bench import control, run
undo = control.FAULTS[{fault!r}]() if {fault!r} else (lambda: None)
out = io.StringIO()
try:
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", {cell!r}, "--seed", "2147483659",
                       "--seconds", "1", "--cpu-rehearsal"])
finally:
    undo()
assert rc == 0
print("RESULT " + out.getvalue().strip().splitlines()[-1])
"""


def _run(workload: str, fault=None) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    chips = CELLS[workload]["chips"]
    if chips > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    script = _SCRIPT.format(root=ROOT, fault=fault, cell=workload)
    out = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_every_listed_fault_is_planted_by_the_control():
    from bench import control

    assert CASES
    assert {f for _, f in CASES} <= set(control.FAULTS)


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_turns_correct_false(workload, fault):
    result = _run(workload, fault)
    over = [k for k, c in result["compared"].items()
            if not (c["value"] is not None and c["value"] <= c["limit"])]
    assert result["compared"] and over and not result["correct"]


@pytest.mark.parametrize("workload", sorted(LIMITS))
def test_sound_run_is_correct(workload):
    result = _run(workload)
    assert result["compared"] and result["correct"]
