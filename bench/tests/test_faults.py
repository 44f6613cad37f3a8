"""The control and faults planted under the timed path must turn `correct`
false.

Each test drives a whole run of a cell at its configuration's rehearsal
size on the CPU (`--cpu-rehearsal` skips the look for a chip) with one of
`bench.control.FAULTS` planted, and checks that some compared number
passes its limit:

  * control_high: the reference at "high" (three bfloat16 passes) in the
    program's place;
  * frozen_sweep: a sweep that returns its state unchanged;
  * half_batch: half of a Monte-Carlo batch left out (trial t computes
    trial t // 2).

A sound run of each cell must come out correct.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
import contextlib
import io
import json
import os
import sys

import pytest

from bench import run

ROOT = run.ROOT
CELLS = {w["name"] for w in run._load_json(ROOT, "BENCHMARK.json")["workloads"]}
BATCH = "batch.friedman1-d5"


def _run(workload: str, fault=None) -> dict:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import control

    undo = control.FAULTS[fault]() if fault else (lambda: None)
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = run.main(["--workload", workload, "--seed", "2147483659",
                           "--seconds", "1", "--cpu-rehearsal"])
    finally:
        undo()
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("workload,fault", [
    (BATCH, "control_high"),
    (BATCH, "frozen_sweep"),
    (BATCH, "half_batch"),
])
def test_fault_turns_correct_false(workload, fault):
    assert workload in CELLS
    result = _run(workload, fault)
    over = [k for k, c in result["compared"].items()
            if not (c["value"] is not None and c["value"] <= c["limit"])]
    assert result["compared"] and over and not result["correct"]


@pytest.mark.parametrize("workload", [BATCH])
def test_sound_run_is_correct(workload):
    result = _run(workload)
    assert result["compared"] and result["correct"]
