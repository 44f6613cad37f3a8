"""Checks of the trace reduction and the per-layer readers, on a hand-made
trace and on a small trace recorded on a TPU v5e (one jit_sweep program of
a fit with 100 one-attribute parties, N=65536, with the host spans around
it).

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
import os
import types

import pytest

from bench import run, trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "..", "testdata", "trace_fit_small.json")

HAND = {
    "devices": [{"name": "/device:TPU:0",
                 "ops": [["%a.1 = f32[] add(..)", 0, 10], ["b", 5, 10],
                         ["all-reduce.3", 30, 5], ["late", 60, 5]],
                 "modules": [["jit_sweep(7)", 0, 15], ["jit_record(9)", 30, 5]]}],
    "host": [["bench.window", 0, 50], ["outer", 0, 100], ["inner", 12, 20]],
}


def _sweep_union(events, lo, hi):
    """Union length by an endpoint sweep (independent of trace_reduce)."""
    points = []
    for _, s, d in events:
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            points += [(s, 1), (e, -1)]
    total, depth, last = 0.0, 0, None
    for t, step in sorted(points, key=lambda p: (p[0], -p[1])):
        if depth > 0:
            total += t - last
        depth += step
        last = t
    return total


def test_hand_made_trace():
    lo, hi = tr.window(HAND, "bench.window")
    dev = HAND["devices"][0]
    assert (lo, hi) == (0, 50)
    assert tr.busy_ns(dev, lo, hi) == 20            # [0, 15] and [30, 35]
    assert tr.collective_ns(dev, lo, hi) == 5
    assert tr.time_by_name(dev["ops"], lo, hi) == {"a.1": 10, "b": 10,
                                                   "all-reduce.3": 5}
    assert tr.time_by_name(dev["modules"], lo, hi) == {"jit_sweep": 15,
                                                       "jit_record": 5}
    # gap [15, 30] lies inside `inner`; gap [35, 50] inside `outer` and
    # the narrower window span
    assert tr.idle_gaps(dev, HAND["host"], lo, hi) == {"inner": 15,
                                                       "bench.window": 15}


def test_recorded_trace_union_and_gaps():
    trace = tr.load(RECORDED)
    lo, hi = tr.window(trace, "bench.window")
    dev = trace["devices"][0]
    busy = tr.busy_ns(dev, lo, hi)
    assert busy == pytest.approx(_sweep_union(dev["ops"], lo, hi), rel=1e-12)
    gaps = tr.idle_gaps(dev, trace["host"], lo, hi)
    assert sum(gaps.values()) == pytest.approx((hi - lo) - busy, rel=1e-9)
    sweeps = [m for m in dev["modules"] if tr.base_name(m[0]) == "jit_sweep"]
    assert len(sweeps) == 1


def _ctx(trace):
    """The recorded trace is of 100 one-attribute parties, N=65536."""
    cell = types.SimpleNamespace(
        config={"data": {"groups": "one_per_agent", "n_attrs": 100,
                         "n_train": 65536}},
        traffic={"entry": "fit"})
    lo, hi = tr.window(trace, "bench.window")
    peaks = run._load_json(run.BENCH, "peaks.json")["TPU v5 lite"]
    return types.SimpleNamespace(trace=trace, lo=lo, hi=hi,
                                 devices=trace["devices"], cell=cell,
                                 peaks=peaks, work={"trials": 1, "sweeps": 1})


def test_recorded_trace_readers():
    trace = tr.load(RECORDED)
    ctx = _ctx(trace)
    dev = trace["devices"][0]
    width = ctx.hi - ctx.lo
    idle = run._reader("device_idle.batch")(ctx, None)
    assert idle == pytest.approx(
        100 * (1 - _sweep_union(dev["ops"], ctx.lo, ctx.hi) / width))
    # one sweep of D=100, N=65536 in f32 is bound by HBM: 8 D N bytes; the
    # busiest program of the recorded window is its one jit_sweep
    least = 8 * 100 * 65536 / 819e9
    sweep = [m for m in dev["modules"] if tr.base_name(m[0]) == "jit_sweep"]
    roof = run._reader("sweep_roofline.batch")(ctx, None)
    assert roof == pytest.approx(100 * least / (sweep[0][2] * 1e-9))
    assert 0 < roof < 100


def test_peaks_table_has_the_v5e():
    peaks = run._load_json(run.BENCH, "peaks.json")
    row = peaks["TPU v5 lite"]
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["ici_bits_per_s"] == 1600e9
