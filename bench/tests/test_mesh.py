"""Checks of the four-chip agent mesh cell `batch.corrlin-p4.mesh`: its two
per-layer readers (`collective_exposed.mesh`, `ici_roofline.mesh`) on
hand-made four-device traces, and a whole run at the configuration's
rehearsal size on 4 forced CPU devices, where a planted frozen mesh sweep
(`distributed._sweep_shmap` returning its state unchanged) must turn
`correct` false (the untouched program's run is in test_faults.py, as
every cell's with a limits file).

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
import json
import os
import subprocess
import sys
import types

import pytest

from bench import run, trace_reduce as tr

MESH = "batch.corrlin-p4.mesh"
PEAKS = run._load_json(run.BENCH, "peaks.json")["TPU v5 lite"]


def _dev(i, ops):
    return {"name": f"/device:TPU:{i}", "ops": ops, "modules": []}


def _ctx(devices, work=None, lo=0, hi=100, peaks=PEAKS):
    cell = run.load_cell(MESH, rehearsal=False)
    trace = {"devices": devices, "host": [["bench.window", lo, hi - lo]]}
    return types.SimpleNamespace(
        trace=trace, lo=lo, hi=hi, devices=devices, cell=cell, peaks=peaks,
        work=work or {"calls": 1, "trials": 1, "sweeps": 1})


def _metric(name):
    return {"name": name}


# four chips: an async all-gather as start and done halves [10, 12] and
# [18, 20] with the wait between them on no op, a psum [40, 50] (HLO text,
# as the TPU names ops) that a fusion [45, 60] overlaps by 5, an op that
# reads the psum's result [70, 71] (no collective), compute elsewhere, and
# the while loop whose body they all are, over the whole window
PSUM = "%psum.9 = f32[65536]{0} all-reduce(f32[65536]{0} %x), to_apply=%add"
READS = ("%get-tuple-element.7 = f32[65536]{0} get-tuple-element("
         "(f32[65536]{0}) %all-reduce.31), index=0")
LOOP = "%while.144 = (s32[]) while((s32[]) %t), condition=%c, body=%b"
FOUR = [_dev(i, [[LOOP, 0, 100], ["fusion.1", 0, 10],
                 ["all-gather-start.2", 10, 2], ["all-gather-done.2", 18, 2],
                 [PSUM, 40, 10], ["fusion.4", 45, 15], [READS, 70, 1],
                 ["all-reduce.5", 200, 10]])       # after the window
        for i in range(4)]


def test_collective_exposed_on_async_halves_and_overlap():
    read = run._reader("collective_exposed.mesh")
    # exposed: [10, 12] + [18, 20] + [40, 45] = 9 of the window's 100
    assert read(_ctx(FOUR), _metric("collective_exposed.mesh")) == (
        pytest.approx(9.0))


def test_collective_exposed_averages_over_chips():
    devs = [dict(d) for d in FOUR]
    devs[3] = _dev(3, [["all-reduce.9", 0, 29]])    # 29 % exposed
    assert run._reader("collective_exposed.mesh")(
        _ctx(devs), _metric("collective_exposed.mesh")) == (
            pytest.approx((3 * 9.0 + 29.0) / 4))


def test_readers_give_none_without_collectives():
    devs = [_dev(i, [["fusion.1", 0, 50]]) for i in range(4)]
    for name in ("collective_exposed.mesh", "ici_roofline.mesh"):
        assert run._reader(name)(_ctx(devs), _metric(name)) is None
    assert run._reader("ici_roofline.mesh")(
        _ctx(FOUR, peaks=None), _metric("ici_roofline.mesh")) is None


def test_ici_roofline_counts_the_sweeps_bytes_over_collective_time():
    from importlib import import_module
    ici = import_module("bench.metrics.ici_roofline")
    cell = run.load_cell(MESH, rehearsal=False)
    d = len(cell.config["data"]["groups"])
    n = cell.config["data"]["n_train"]
    least = ici.least_receive_s(d, n, PEAKS)
    # 2 (D-1) N 4 bytes at 200 GB/s
    assert least == pytest.approx(2 * 3 * 65536 * 4 / 200e9)
    work = {"calls": 2, "trials": 16, "sweeps": 10}
    # collective busy [10, 12] [18, 20] [40, 50] = 14 ns a chip; scale the
    # window so the chips' collectives take 100x the least time
    scale = 320 * least * 100 / 14e-9
    devs = [_dev(i, [[n_, s * scale, dur * scale] for n_, s, dur in d_["ops"]])
            for i, d_ in enumerate(FOUR)]
    got = run._reader("ici_roofline.mesh")(
        _ctx(devs, work, hi=100 * scale), _metric("ici_roofline.mesh"))
    assert got == pytest.approx(1.0)


def test_ici_roofline_stays_below_100_when_the_wire_runs_at_peak():
    """A trace whose collectives move exactly the sweeps' bytes at the ICI
    peak, plus the record's gather, reads under 100 %."""
    cell = run.load_cell(MESH, rehearsal=False)
    from importlib import import_module
    ici = import_module("bench.metrics.ici_roofline")
    least_ns = ici.least_receive_s(len(cell.config["data"]["groups"]),
                                   cell.config["data"]["n_train"],
                                   PEAKS) * 1e9
    sweeps = 3
    ops = []
    t = 0.0
    for _ in range(sweeps):
        ops += [["all-gather.1", t, least_ns / 2],
                ["all-reduce.2", t + least_ns / 2, least_ns / 2]]
        t += least_ns
    ops.append(["all-gather.7", t, least_ns / 8])   # the record's gather
    devs = [_dev(i, ops) for i in range(4)]
    got = run._reader("ici_roofline.mesh")(
        _ctx(devs, {"calls": 1, "trials": 1, "sweeps": sweeps},
             hi=t + least_ns), _metric("ici_roofline.mesh"))
    assert 0 < got < 100


def test_cell_lists_both_readers():
    names = {m["name"] for m in run.load_cell(MESH, rehearsal=False).per_layer}
    assert {"collective_exposed.mesh", "ici_roofline.mesh"} <= names


# A whole rehearsal run of the mesh cell in a subprocess with 4 forced host
# devices, with a mesh sweep planted that returns its state unchanged.
_SCRIPT = r"""
import contextlib, io, json, sys
sys.path.insert(0, ROOT)
sys.path.insert(0, ROOT + "/src")
from bench import run
from repro.api import runner
from repro.core import distributed

real = distributed._sweep_shmap

def frozen(mesh, cfg, family):
    sweep = real(mesh, cfg, family)

    def same(xcols, y, f, params, key, ledger, round_=None):
        _, _, w, ledger, taps = sweep(xcols, y, f, params, key, ledger,
                                      round_)
        return f, params, w, ledger, taps
    return same

distributed._sweep_shmap = frozen
runner.clear_program_cache()
out = io.StringIO()
with contextlib.redirect_stdout(out):
    rc = run.main(["--workload", MESH, "--seed", "2147483659",
                   "--seconds", "1", "--cpu-rehearsal"])
assert rc == 0
print("RESULT " + out.getvalue().strip().splitlines()[-1])
"""


def test_frozen_mesh_sweep_turns_correct_false():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    script = _SCRIPT.replace("ROOT", repr(run.ROOT)).replace("MESH",
                                                            repr(MESH))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")]
    result = json.loads(line[-1][len("RESULT "):])
    over = [k for k, c in result["compared"].items()
            if not (c["value"] is not None and c["value"] <= c["limit"])]
    assert result["compared"] and over and not result["correct"]
