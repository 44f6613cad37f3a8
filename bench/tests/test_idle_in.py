"""Checks of the `idle_in.<span>` reader (bench/metrics/idle_in.py): the
device's idle time inside a program span, on a hand-made trace and on the
small trace recorded on a TPU v5e (bench/testdata/trace_fit_small.json).

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
import types

import pytest

from bench import run, trace_reduce as tr
from bench.tests.test_trace_reduce import RECORDED, _ctx, _sweep_union

# a call [2, 48] split into three phases; the device runs [0, 15] and
# [30, 35] inside the window [0, 50], and [60, 65] after it
HAND = {
    "devices": [{"name": "/device:TPU:0",
                 "ops": [["a.1", 0, 10], ["b", 5, 10], ["c", 30, 5],
                         ["late", 60, 5]],
                 "modules": []}],
    "host": [["bench.window", 0, 50], ["call", 2, 46],
             ["phase.a", 2, 10], ["phase.b", 12, 20], ["phase.c", 32, 16]],
}
PHASES = ("phase.a", "phase.b", "phase.c")


def _hand_ctx(trace):
    lo, hi = tr.window(trace, "bench.window")
    return types.SimpleNamespace(trace=trace, lo=lo, hi=hi,
                                 devices=trace["devices"])


def _idle_in(ctx, span):
    return run._reader("idle_in." + span)(ctx, {"name": "idle_in." + span})


def _idle_by_grid(trace, span, lo, hi):
    """Idle share inside `span`, counted at every half-integer ns of the
    window (independent of trace_reduce; the hand trace's ends are whole
    ns)."""
    def covered(events, t):
        return any(s <= t < s + d for _, s, d in events)

    spans = [e for e in trace["host"] if e[0] == span]
    idle = [sum(1 for k in range(int(lo), int(hi))
                if covered(spans, k + 0.5) and not covered(dev["ops"], k + 0.5))
            for dev in trace["devices"]]
    return 100.0 * sum(idle) / len(idle) / (hi - lo)


def test_hand_made_trace_matches_a_grid_count():
    ctx = _hand_ctx(HAND)
    assert {p: _idle_in(ctx, p) for p in PHASES} == {
        "phase.a": 0.0, "phase.b": 30.0, "phase.c": 26.0}
    for span in PHASES + ("call",):
        assert _idle_in(ctx, span) == pytest.approx(
            _idle_by_grid(HAND, span, ctx.lo, ctx.hi))


def test_phases_and_the_idle_outside_sum_to_device_idle():
    ctx = _hand_ctx(HAND)
    idle = run._reader("device_idle.batch")(ctx, None)
    phases = sum(_idle_in(ctx, p) for p in PHASES)
    assert phases == pytest.approx(_idle_in(ctx, "call"))
    # outside the call: [0, 2] busy, [48, 50] idle
    assert idle == pytest.approx(60.0)
    assert phases + 100.0 * 2 / 50 == pytest.approx(idle)


def test_chips_are_averaged():
    two = dict(HAND, devices=HAND["devices"] + [
        {"name": "/device:TPU:1", "ops": [], "modules": []}])
    ctx = _hand_ctx(two)
    # chip 1 idles all of phase.b's 20 ns: (30 + 40) / 2
    assert _idle_in(ctx, "phase.b") == pytest.approx(35.0)
    assert _idle_in(ctx, "phase.b") == pytest.approx(
        _idle_by_grid(two, "phase.b", ctx.lo, ctx.hi))


def test_absent_span_or_no_device_plane_reads_none():
    ctx = _hand_ctx(HAND)
    assert _idle_in(ctx, "phase.renamed") is None
    assert _idle_in(_hand_ctx(dict(HAND, devices=[])), "phase.a") is None


def test_metric_names_find_the_reader():
    bench = run._load_json(run.ROOT, "BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]
             if m["name"].startswith("idle_in.")]
    assert names == ["idle_in.batch_fit." + p
                     for p in ("launch", "wait", "fetch", "assemble")]
    for name in names:
        assert run._reader(name).__module__ == "bench_metric_idle_in"


def test_recorded_trace_api_fit():
    trace = tr.load(RECORDED)
    ctx = _ctx(trace)
    dev = trace["devices"][0]
    inside = _idle_in(ctx, "api.fit")
    idle = run._reader("device_idle.batch")(ctx, None)
    assert 0 < inside < idle
    # idle inside = |spans u ops| - |ops|, by the endpoint sweep
    spans = [e for e in trace["host"] if e[0] == "api.fit"]
    width = ctx.hi - ctx.lo
    expect = 100.0 * (_sweep_union(spans + dev["ops"], ctx.lo, ctx.hi)
                      - _sweep_union(dev["ops"], ctx.lo, ctx.hi)) / width
    assert inside == pytest.approx(expect, rel=1e-9)
