"""repro.obs (DESIGN.md §13): statically-gated in-trace metric taps, the
host-side span tracer, and the runtime-health primitives.

The two contracts everything here leans on:

  * off mode (the default `ObsSpec()`) adds NOT ONE traced op — results are
    bit-identical with and without the obs layer selected, per engine and
    per backend;
  * the eta tap is read off the SAME Gram solve the history records, so
    `Result.metrics["eta"]` matches `History.eta[1:]` to 1e-10 relative in
    f64 under fit, batch_fit and stream_fit (in practice bitwise).
"""
import json
import math
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, obs
from repro.faults import FaultSpec
from repro.obs import ALL_TAPS, Counter, LatencyRing, ObsError, ObsSpec
from repro.obs import spec as obs_spec_mod
from repro.obs import taps as obs_taps
from repro.obs.health import prometheus_text
from repro.obs.trace import active, configure, disable, event, trace
from repro.stream import PredictEngine, stream_fit

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_N = 150


def _spec(taps=(), **kw):
    solver_kw = {"n_sweeps": kw.pop("n_sweeps", 3),
                 "eps": kw.pop("eps", 0.0),
                 "engine": kw.pop("engine", "incremental")}
    return api.ExperimentSpec(
        data=api.DataSpec(n_train=_N, n_test=_N, seed=7),
        agent=api.AgentSpec(family="polynomial", options=(("degree", 3),)),
        solver=api.SolverSpec(**solver_kw),
        obs=ObsSpec(taps=tuple(taps)), **kw)


def _stream_spec(taps=(), **kw):
    exp = api.ExperimentSpec(
        data=api.DataSpec(source="cosine", n_train=256, n_test=64),
        solver=api.SolverSpec(name="icoa", n_sweeps=3, eps=0.0),
        obs=ObsSpec(taps=tuple(taps)))
    kw.setdefault("window", 256)
    kw.setdefault("chunk", 64)
    kw.setdefault("total_instances", 256)
    kw.setdefault("resweep_every", 128)
    return api.StreamSpec(experiment=exp, **kw)


# ------------------------------------------------------------- spec contract


def test_unknown_tap_is_obs_error_and_spec_error():
    with pytest.raises(ObsError, match="unknown tap"):
        ObsSpec(taps=("eta", "nope")).validate()
    # ExperimentSpec.validate re-raises in its own dialect, field-named
    with pytest.raises(api.SpecError, match="obs.*nope"):
        _spec(taps=("nope",)).validate()


def test_taps_on_non_icoa_solver_is_spec_error():
    spec = api.replace(_spec(taps=("eta",)), solver=api.SolverSpec(
        name="averaging", n_sweeps=3))
    with pytest.raises(api.SpecError, match="ICOA sweep"):
        spec.validate()
    # the inert default rides every solver
    api.replace(spec, obs=ObsSpec()).validate()


def test_normalized_is_canonical_and_off_mode_is_none():
    assert ObsSpec().normalized() is None
    assert ObsSpec(taps=("s", "eta", "s")).normalized() == \
        ObsSpec(taps=("eta", "s"))
    # one retrace class for every spelling of the same selection
    assert hash(ObsSpec(taps=("s", "eta")).normalized()) == \
        hash(ObsSpec(taps=("eta", "s", "s")).normalized())


def test_registry_covers_engine_and_record_taps_exactly():
    assert set(ALL_TAPS) == set(obs_spec_mod.ENGINE_TAPS) | \
        set(obs_spec_mod.RECORD_TAPS)
    spec = _spec(taps=ALL_TAPS)
    spec.validate()
    assert api.spec_from_dict(api.spec_to_dict(spec)) == spec


# --------------------------------------------- off-mode bit-identity (local)


def test_off_mode_returns_no_metrics():
    res = api.fit(_spec())
    assert res.metrics is None


@pytest.mark.parametrize("engine", ["incremental", "fused", "dense"])
def test_taps_do_not_perturb_the_solution(engine):
    """Turning every tap on must leave params/weights/history BIT-identical
    to the off-mode run: taps only read values the sweep already computes."""
    off = api.fit(_spec(engine=engine))
    on = api.fit(_spec(taps=ALL_TAPS, engine=engine))
    assert off.history.eta == on.history.eta
    assert off.history.train_mse == on.history.train_mse
    assert off.history.test_mse == on.history.test_mse
    assert off.history.bytes_transmitted == on.history.bytes_transmitted
    assert np.array_equal(np.asarray(off.weights), np.asarray(on.weights))
    for a, b in zip(jax.tree.leaves(off.params), jax.tree.leaves(on.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert on.metrics is not None and off.metrics is None


def test_metrics_schema_shapes_and_dtypes():
    n_sweeps = 3
    res = api.fit(_spec(taps=ALL_TAPS, n_sweeps=n_sweeps))
    m = res.metrics
    d = len(res.spec.data.groups)
    assert m.names == sorted(ALL_TAPS)
    assert m.n_sweeps == n_sweeps
    assert "eta" in m and "missing" not in m
    assert m["eta"].shape == (n_sweeps,)
    assert m["s"].shape == (n_sweeps, d)
    assert m["accepts"].shape == (n_sweeps, d)
    assert m["budget_rejects"].shape == (n_sweeps,)
    assert m["budget_rejects"].dtype == np.int32
    assert m["fault_retries"].dtype == np.int32
    # fault-free unbudgeted run: both gate taps are structurally zero
    assert m["budget_rejects"].sum() == 0 and m["fault_retries"].sum() == 0
    # exact codec: the relay round-trip is lossless
    assert np.all(m["codec_error"] == 0.0)
    view = m.as_dict()
    for name in ALL_TAPS:
        assert view[name]["axes"][0] == "sweep"
        assert isinstance(view[name]["values"], list)
        assert view[name]["desc"]


# ----------------------------------------------------- eta-tap parity (f64)


def test_eta_tap_matches_history_fit_f64():
    with jax.enable_x64(True):
        api.clear_dataset_cache()
        res = api.fit(_spec(taps=("eta", "s")))
        eta_hist = np.asarray(res.history.eta[1:])
        np.testing.assert_allclose(res.metrics["eta"], eta_hist, rtol=1e-10)
        # record-side taps share the record's expression tree: bitwise equal
        assert np.array_equal(res.metrics["eta"], eta_hist)
        # sum(s) = eta_tilde = 1/eta of the same Gram
        np.testing.assert_allclose(res.metrics["s"].sum(axis=1),
                                   1.0 / eta_hist, rtol=1e-10)


def test_eta_tap_matches_history_batch_fit_vmap_f64():
    with jax.enable_x64(True):
        api.clear_dataset_cache()
        spec = _spec(taps=("eta", "accepts"))
        rs = api.batch_fit(spec, 3)
        for t in range(3):
            r = rs[t]
            assert r.metrics is not None
            np.testing.assert_allclose(r.metrics["eta"],
                                       np.asarray(r.history.eta[1:]),
                                       rtol=1e-10, err_msg=f"trial {t}")
            # trials are independent streams: taps must differ across trials
        assert not np.array_equal(rs[0].metrics["eta"], rs[1].metrics["eta"])


def test_off_vs_on_batch_fit_histories_identical():
    off = api.batch_fit(_spec(), 2)
    on = api.batch_fit(_spec(taps=("eta", "s", "accepts")), 2)
    for t in range(2):
        assert off[t].history.eta == on[t].history.eta
        assert off[t].history.train_mse == on[t].history.train_mse
        assert off[t].metrics is None and on[t].metrics is not None


_SHARD_SCRIPT = r"""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro import api
from repro.obs import ObsSpec

spec = api.ExperimentSpec(
    data=api.DataSpec(n_train=120, n_test=120, seed=3),
    agent=api.AgentSpec(family="polynomial", options=(("degree", 3),)),
    solver=api.SolverSpec(n_sweeps=2, eps=0.0),
    backend=api.BackendSpec(name="shard_map"))
on = api.replace(spec, obs=ObsSpec(taps=("eta", "s", "accepts")))

# off/on bit-identity through the distributed engine
r_off, r_on = api.fit(spec), api.fit(on)
assert r_off.history.eta == r_on.history.eta
assert r_off.history.train_mse == r_on.history.train_mse
assert np.array_equal(np.asarray(r_off.weights), np.asarray(r_on.weights))
assert r_off.metrics is None

# tap parity on the serial distributed run and the compiled trial scan
np.testing.assert_allclose(r_on.metrics["eta"],
                           np.asarray(r_on.history.eta[1:]), rtol=1e-10)
rs = api.batch_fit(on, 3)
for t in range(3):
    np.testing.assert_allclose(rs[t].metrics["eta"],
                               np.asarray(rs[t].history.eta[1:]), rtol=1e-10)
    d = len(on.data.groups)
    assert rs[t].metrics["s"].shape == (2, d)
print("OBS_SHARD_OK")
"""


@pytest.mark.slow
def test_shard_map_tap_parity_subprocess():
    """Taps ride shard_map's replicated D x D algebra: the stacked arrays are
    the single logical value, matching the recorded history at 1e-10 f64 on
    8 forced host devices (serial distributed run AND compiled trial scan)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OBS_SHARD_OK" in out.stdout


# ------------------------------------------------------------- stream taps


def test_stream_taps_concatenate_across_resweeps_f64():
    with jax.enable_x64(True):
        api.clear_dataset_cache()
        res = stream_fit(_stream_spec(taps=("eta", "accepts")))
        assert res.metrics is not None
        # one tap row per EXECUTED sweep, in record order
        per_record = [r["etas"] for r in res.records]
        want = np.concatenate([np.asarray(e) for e in per_record])
        np.testing.assert_allclose(res.metrics["eta"], want, rtol=1e-10)
        assert np.array_equal(res.metrics["eta"], want)
        d = len(res.spec.experiment.data.groups)
        total_sweeps = sum(r["sweeps"] for r in res.records)
        assert res.metrics["accepts"].shape == (total_sweeps, d)
        assert res.metrics.n_sweeps == total_sweeps


def test_stream_off_mode_is_bit_identical_and_metric_free():
    api.clear_dataset_cache()
    off = stream_fit(_stream_spec())
    on = stream_fit(_stream_spec(taps=("eta", "s")))
    assert off.metrics is None
    assert [r["taps"] for r in off.records] == [{}] * len(off.records)
    assert [r["etas"] for r in off.records] == [r["etas"] for r in on.records]
    assert [r["bytes"] for r in off.records] == [r["bytes"] for r in on.records]


def test_stream_health_counters_track_the_run():
    api.clear_dataset_cache()
    res = stream_fit(_stream_spec())
    c = res.ingestor.counters
    assert c["ingest_instances"].total == 256
    assert c["ingest_chunks"].total == 256 // 64
    assert c["resweeps"].total == len(res.records) == 2
    assert c["resweep_sweeps"].total == sum(r["sweeps"] for r in res.records)
    assert res.ingestor.last_preq_mse == res.records[-1]["preq_mse"]


# --------------------------------------------------- gate taps (budget/fault)


def test_budget_reject_tap_counts_the_denied_broadcasts():
    full = api.fit(_spec(n_sweeps=4)).history.total_bytes
    res = api.fit(_spec(taps=("budget_rejects", "accepts"), n_sweeps=4,
                        transport=api.TransportSpec(byte_budget=0.6 * full,
                                                    policy="truncate")))
    d = len(res.spec.data.groups)
    rejects = int(res.metrics["budget_rejects"].sum())
    assert 0 < rejects <= 4 * d
    # a denied broadcast can never commit: accepts per sweep are bounded by
    # the broadcasts the budget let through
    granted = 4 * d - rejects
    assert int(res.metrics["accepts"].sum()) <= granted
    assert res.history.total_bytes <= 0.6 * full


def test_fault_retry_tap_reconciles_with_ledger_bytes():
    """ISSUE 10 acceptance: on an unbudgeted full topology with drop faults
    only (no stragglers/crashes), every transmitting agent is charged
    attempts * bcost, so the faulted-vs-clean byte overhead IS the retry tap
    total times the uniform row broadcast cost — exactly."""
    drops = FaultSpec(seed=5, drop_rate=0.4, max_retries=3)
    clean = api.fit(_spec(n_sweeps=4))
    faulted = api.fit(_spec(taps=("fault_retries",), n_sweeps=4,
                            faults=drops))
    retries = int(faulted.metrics["fault_retries"].sum())
    assert retries > 0                     # drop_rate 0.4 x 4 sweeps: certain
    tp = faulted.spec.resolved_transport()
    bcosts = np.asarray(tp.broadcast_costs(_N, False), np.float64)
    assert len(set(bcosts.tolist())) == 1  # full topology: uniform row price
    overhead = (sum(faulted.history.bytes_transmitted)
                - sum(clean.history.bytes_transmitted))
    assert overhead == retries * float(bcosts[0])


def test_codec_error_tap_is_zero_exact_positive_lossy():
    exact = api.fit(_spec(taps=("codec_error",)))
    assert np.all(exact.metrics["codec_error"] == 0.0)
    lossy = api.fit(_spec(taps=("codec_error",),
                          transport=api.TransportSpec(codec="int8_affine")))
    err = lossy.metrics["codec_error"]
    assert np.all(err > 0.0) and np.all(err < 1.0)


# ------------------------------------------------------------ runtime health


def test_counter_totals_and_rate():
    c = Counter()
    assert c.total == 0 and c.rate == 0.0
    c.add()
    c.add(4)
    assert c.total == 5
    assert c.first_t is not None and c.last_t >= c.first_t
    if c.last_t > c.first_t:
        assert c.rate == pytest.approx(5 / (c.last_t - c.first_t))


def test_latency_ring_percentiles_and_wrap():
    r = LatencyRing(capacity=4)
    assert all(math.isnan(v) for v in r.percentiles().values())
    for v in (1.0, 2.0, 3.0):
        r.observe(v)
    p = r.percentiles((50,))
    assert p["p50"] == 2.0
    for v in (10.0, 11.0, 12.0):           # wraps: window keeps the last 4
        r.observe(v)
    assert r.count == 6
    snap = sorted(r.snapshot().tolist())
    assert len(snap) == 4 and snap == [3.0, 10.0, 11.0, 12.0]
    with pytest.raises(ValueError, match="capacity"):
        LatencyRing(capacity=0)


def test_prometheus_text_exposition_format():
    text = prometheus_text([
        ("app_requests_total", "counter", "requests served", 7.0, None),
        ("app_latency_seconds", "gauge", "latency", 0.25,
         {"quantile": "p50", "bucket": "16"}),
        ("app_latency_seconds", "gauge", "latency", float("nan"),
         {"quantile": "p99", "bucket": "16"}),
    ])
    lines = text.splitlines()
    assert "# HELP app_requests_total requests served" in lines
    assert "# TYPE app_requests_total counter" in lines
    # one header pair per metric name, labels sorted, NaN is valid exposition
    assert lines.count("# TYPE app_latency_seconds gauge") == 1
    assert 'app_latency_seconds{bucket="16",quantile="p50"} 0.25' in lines
    assert 'app_latency_seconds{bucket="16",quantile="p99"} nan' in lines
    assert text.endswith("\n")


def test_predict_engine_feeds_rings_and_counters():
    res = api.fit(_spec())
    groups = res.spec.data.groups
    eng = PredictEngine(res.family, groups, n_attrs=len(groups),
                        buckets=(1, 16, 128))
    eng.update(res.params, res.weights)
    eng.warmup()
    x = np.zeros((300, len(groups)), np.asarray(res.weights).dtype)
    out = eng.predict(jnp.asarray(x))
    assert out.shape == (300,)
    # one request, three strided executions of the largest bucket program
    assert eng.requests.total == 1
    assert eng.latency[128].count == 3
    assert eng.latency[1].count == 0
    eng.predict(jnp.asarray(x[:1]))
    assert eng.latency[1].count == 1 and eng.requests.total == 2
    assert all(v > 0.0 for v in eng.latency[128].percentiles().values())
    text = eng.metrics_text()
    assert "repro_serve_requests_total 2.0" in text
    assert 'repro_serve_predict_executions_total{bucket="128"} 3.0' in text


def test_metrics_text_includes_ingestor_counters():
    api.clear_dataset_cache()
    res = stream_fit(_stream_spec())
    groups = res.spec.experiment.data.groups
    eng = PredictEngine(res.family, groups, n_attrs=len(groups))
    eng.update(res.params, res.weights)
    text = eng.metrics_text(res.ingestor)
    assert "repro_stream_ingest_instances_total 256.0" in text
    assert "repro_stream_resweeps_total 2.0" in text
    assert "repro_stream_preq_mse" in text


# ---------------------------------------------------------------- the tracer


def test_tracer_jsonl_schema_and_lifecycle(tmp_path):
    path = str(tmp_path / "events.jsonl")
    assert not active()
    configure(path, run_id="t1")
    try:
        assert active()
        with trace("outer", case="schema"):
            with trace("inner"):
                pass
            event("mark", round=3, agent=1)
    finally:
        disable()
    assert not active()
    rows = [json.loads(l) for l in open(path)]
    # spans land when they CLOSE, so the event inside `outer` precedes it
    assert [r["name"] for r in rows] == ["inner", "mark", "outer"]
    spans = [r for r in rows if r["ev"] == "span"]
    events = [r for r in rows if r["ev"] == "event"]
    assert len(spans) == 2 and len(events) == 1
    for r in rows:
        assert r["run"] == "t1" and isinstance(r["t"], float)
    outer = next(r for r in spans if r["name"] == "outer")
    inner = next(r for r in spans if r["name"] == "inner")
    assert outer["dur_s"] >= inner["dur_s"] >= 0.0
    assert outer["tags"] == {"case": "schema"}
    assert events[0]["tags"] == {"round": 3, "agent": 1}
    # disabled: instrumented call sites are no-ops, the file stays put
    with trace("ignored"):
        event("also-ignored")
    assert len(open(path).readlines()) == 3


def test_api_fit_emits_a_span_when_configured(tmp_path):
    path = str(tmp_path / "fit.jsonl")
    configure(path)
    try:
        api.fit(_spec())
    finally:
        disable()
    rows = [json.loads(l) for l in open(path)]
    fit_spans = [r for r in rows
                 if r["ev"] == "span" and r["name"] == "api.fit"]
    assert len(fit_spans) == 1
    assert fit_spans[0]["tags"]["solver"] == "icoa"


def test_spans_carry_ids_and_same_thread_parents(tmp_path):
    path = str(tmp_path / "ids.jsonl")
    configure(path)
    try:
        with trace("outer"):
            with trace("mid"):
                with trace("leaf"):
                    pass
            with trace("sibling") as tags:
                tags["count"] = 2
            # a span on another thread has no parent here: the stack of
            # open spans is per thread
            worker = threading.Thread(target=_span_on_thread)
            worker.start()
            worker.join(timeout=60)
        with trace("second-root"):
            pass
    finally:
        disable()
    assert not worker.is_alive()
    rows = {r["name"]: r for r in map(json.loads, open(path))}
    assert len({r["id"] for r in rows.values()}) == len(rows) == 6
    assert rows["outer"]["parent"] is None
    assert rows["second-root"]["parent"] is None
    assert rows["mid"]["parent"] == rows["outer"]["id"]
    assert rows["leaf"]["parent"] == rows["mid"]["id"]
    assert rows["sibling"]["parent"] == rows["outer"]["id"]
    assert rows["sibling"]["tags"] == {"count": 2}
    assert rows["thread-span"]["parent"] is None
    # ids number the spans in the order they opened
    order = ["outer", "mid", "leaf", "sibling", "thread-span", "second-root"]
    assert [rows[n]["id"] for n in order] == sorted(
        r["id"] for r in rows.values())


def _span_on_thread():
    with trace("thread-span"):
        pass


def test_rows_are_written_on_disable_and_at_exit(tmp_path):
    path = str(tmp_path / "buffered.jsonl")
    configure(path)
    try:
        with trace("held"):
            event("mark")
        # nothing is written while the sink is armed
        assert open(path).read() == ""
    finally:
        disable()
    assert [json.loads(l)["name"] for l in open(path)] == ["mark", "held"]
    # a process that exits with the sink armed still writes its rows
    exiting = str(tmp_path / "exit.jsonl")
    code = ("import sys\nfrom repro import obs\nobs.configure(sys.argv[1])\n"
            "with obs.trace('at-exit', k=1):\n    pass\n")
    out = subprocess.run([sys.executable, "-c", code, exiting],
                         capture_output=True, text=True, timeout=120,
                         env=dict(os.environ, PYTHONPATH=os.path.join(
                             REPO, "src")))
    assert out.returncode == 0, out.stderr
    rows = [json.loads(l) for l in open(exiting)]
    assert [(r["name"], r["tags"], r["parent"]) for r in rows] == [
        ("at-exit", {"k": 1}, None)]


_BATCH_PHASES = ("batch_fit.launch", "batch_fit.wait", "batch_fit.fetch",
                 "batch_fit.assemble")


def test_batch_fit_spans_cover_the_call_in_order(tmp_path):
    # a seed of its own, so the program memo has not seen this spec
    spec = api.replace(_spec(), seed=1313)
    path = str(tmp_path / "batch.jsonl")
    configure(path)
    try:
        api.batch_fit(spec, 3)
        rs = api.batch_fit(spec, 3)
    finally:
        disable()
    wire_bytes = int(sum(sum(r.history.bytes_transmitted)
                         for r in rs.results))
    # the fetch copies, per trial: train/test/eta and the int32 byte ledger
    # (R records each), converged_at, params (D x degree+1), weights, f;
    # all 4-byte words in the default f32
    trials, d, records = 3, 5, spec.solver.n_sweeps + 1
    words = 4 * records + 1 + d * (3 + 1) + d + d * _N
    host_bytes = 4 * trials * words
    rows = [json.loads(l) for l in open(path)]
    parents = [r for r in rows if r["name"] == "api.batch_fit"]
    assert len(parents) == 2
    for call, parent in enumerate(parents):
        assert parent["parent"] is None
        assert parent["tags"] == {"n_trials": 3, "solver": "icoa",
                                  "backend": "local", "agents_mesh": 1,
                                  "sub_rows": _N}
        kids = sorted((r for r in rows if r["parent"] == parent["id"]),
                      key=lambda r: r["id"])
        assert tuple(r["name"] for r in kids) == _BATCH_PHASES
        # `t` is the wall clock and `dur_s` the monotonic one: allow their
        # drift over the call
        eps = 1e-4
        assert kids[0]["t"] >= parent["t"] - eps
        assert kids[-1]["t"] + kids[-1]["dur_s"] <= (
            parent["t"] + parent["dur_s"] + eps)
        for a, b in zip(kids, kids[1:]):
            assert a["t"] + a["dur_s"] <= b["t"] + eps
        assert sum(k["dur_s"] for k in kids) <= parent["dur_s"]
        assert kids[0]["tags"] == {"new_program": call == 0}
        assert kids[2]["tags"] == {"host_bytes": host_bytes}
        assert kids[-1]["tags"] == {"trials": 3, "wire_bytes": wire_bytes}
    assert wire_bytes > 0

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"), path],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    table = out.stdout.split("== batch_fit calls ==")[1].splitlines()
    assert table[2].split() == ["local", "1", str(_N), "2", "6",
                                table[2].split()[5], str(2 * wire_bytes)]


_SUB_ROWS_SCRIPT = r"""
import sys
from repro import api, obs

spec = api.ExperimentSpec(
    data=api.DataSpec(n_train=120, n_test=120, seed=3),
    agent=api.AgentSpec(family="polynomial", options=(("degree", 3),)),
    solver=api.SolverSpec(n_sweeps=2, eps=0.0),
    backend=api.BackendSpec(name="shard_map"))
obs.configure(sys.argv[1])
try:
    for alpha in (1.0, 7.0):
        api.batch_fit(api.replace(
            spec, solver=api.replace(spec.solver, alpha=alpha)), 2)
finally:
    obs.disable()
"""


def test_batch_fit_sub_rows_tag_on_the_shard_map_backend(tmp_path):
    """`sub_rows` is what each party sends per residual gather: all 120
    instances at alpha 1 (the sweep bodies' identity path), the alpha
    subsample above it; obs_report's batch table keys a row on it."""
    from repro.core import covariance as cov

    path = str(tmp_path / "mesh.jsonl")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=5")
    out = subprocess.run([sys.executable, "-c", _SUB_ROWS_SCRIPT, path],
                         env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    rows = [json.loads(l) for l in open(path)]
    calls = [r["tags"] for r in rows if r["name"] == "api.batch_fit"]
    sub7 = cov.subsample_size(120, 7.0)
    assert sub7 == 18
    assert [(c["backend"], c["agents_mesh"], c["sub_rows"]) for c in calls] \
        == [("shard_map", 5, 120), ("shard_map", 5, sub7)]

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"), path],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    table = out.stdout.split("== batch_fit calls ==")[1].splitlines()
    assert table[1].split()[:3] == ["backend", "agents_mesh", "sub_rows"]
    assert sorted(line.split()[:5] for line in table[2:4]) == [
        ["shard_map", "5", "120", "1", "2"],
        ["shard_map", "5", str(sub7), "1", "2"]]


def test_serial_batch_fit_nests_its_api_fit_spans(tmp_path):
    path = str(tmp_path / "serial.jsonl")
    configure(path)
    try:
        api.batch_fit(_spec(), 2, compiled=False)
    finally:
        disable()
    rows = [json.loads(l) for l in open(path)]
    (parent,) = [r for r in rows if r["name"] == "api.batch_fit"]
    kids = [r["name"] for r in rows if r["parent"] == parent["id"]]
    assert kids == ["api.fit", "api.fit"]


def test_batch_fit_spans_land_on_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData

    spec = _spec()
    api.batch_fit(spec, 2)                      # compile outside the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        api.batch_fit(spec, 2)
    finally:
        jax.profiler.stop_trace()
    (pb,) = list(tmp_path.rglob("*.xplane.pb"))
    names = set()
    for plane in ProfileData.from_file(str(pb)).planes:
        if plane.name.startswith("/host:CPU"):
            names.update(e.name for line in plane.lines for e in line.events)
    assert {"api.batch_fit", *_BATCH_PHASES} <= names


def test_stream_fit_event_log_renders_through_obs_report(tmp_path):
    """End-to-end: stream_fit with the tracer armed -> JSONL -> the stdlib
    obs_report tool renders the span/metric tables and its ledger cross-check
    passes (exit 0)."""
    api.clear_dataset_cache()
    path = str(tmp_path / "stream.jsonl")
    configure(path, run_id="s1")
    try:
        stream_fit(_stream_spec())
    finally:
        disable()
    rows = [json.loads(l) for l in open(path)]
    names = {r["name"] for r in rows}
    assert {"stream.fit", "stream.resweep", "stream.record"} <= names
    records = [r for r in rows if r["name"] == "stream.record"]
    assert len(records) == 2
    assert records[-1]["tags"]["bytes_total"] == \
        sum(r["tags"]["bytes"] for r in records)

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"), path],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "stream.resweep" in out.stdout
    assert "[OK]" in out.stdout

    # a dropped record must fail the cross-check (exit 1)
    broken = str(tmp_path / "broken.jsonl")
    with open(broken, "w") as fh:
        for r in rows:
            if not (r["name"] == "stream.record"
                    and r["tags"]["count"] == 128):
                fh.write(json.dumps(r) + "\n")
    bad = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         broken], capture_output=True, text=True, timeout=120)
    assert bad.returncode == 1
    assert "MISMATCH" in bad.stdout


# ----------------------------------------------------------- bench envelope


def test_envelope_meta_and_validate(tmp_path):
    from benchmarks import envelope

    doc = envelope.envelope("probe", {"k": 1})
    assert set(doc) == {"meta", "results"}
    assert set(envelope.META_KEYS) <= set(doc["meta"])
    assert doc["meta"]["bench"] == "probe"
    assert doc["meta"]["host_cpu_count"] == os.cpu_count()
    envelope.validate(doc, "probe.json")

    with pytest.raises(ValueError, match="meta"):
        envelope.validate({"results": {}}, "x.json")
    with pytest.raises(ValueError, match="timestamp"):
        bad = {"meta": {k: "v" for k in envelope.META_KEYS
                        if k != "timestamp"}, "results": {}}
        envelope.validate(bad, "x.json")
    with pytest.raises(ValueError, match="unexpected"):
        envelope.validate({**doc, "stray": 1}, "x.json")

    path = str(tmp_path / "BENCH_probe.json")
    envelope.write_bench(path, "probe", {"k": [1, 2]})
    back = envelope.load_bench(path)
    assert back["results"] == {"k": [1, 2]}
    envelope.validate(back, path)


def test_bench_schema_check_passes_on_the_checked_in_benchmarks():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_schema.py"),
         "check", REPO],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "BENCH_transport.json" in out.stdout
