"""repro.faults (PR 9): seeded fault traces (pure in (seed, round, agent)),
resilience policies with ledger-charged retransmits, spec plumbing with
strict round-trips, the zero-fault bit-identity guarantee, and the stream
chaos (kill/restore) contract."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro import transport as tlib
from repro.agents import PolynomialFamily
from repro.core import icoa
from repro.data.friedman import make_dataset
from repro.data.partition import one_per_agent
from repro.faults import (FaultError, FaultSpec, alive_at, broadcast_outcome,
                          corrupt, straggles)
from repro.stream.run import stream_fit
from repro.stream.serve import PredictEngine

_N = 150

# one fully-loaded failure model reused across the replay tests: every
# injection mechanism active at once (drops+retries, corruption, stragglers,
# one crash-and-rejoin)
_FAULTS = FaultSpec(seed=5, drop_rate=0.3, corrupt_rate=0.2, corrupt_bits=4,
                    straggle_rate=0.1, max_retries=2, crash=((1, 1, 3),))


def _spec(faults=FaultSpec(), **solver_kw):
    solver_kw.setdefault("n_sweeps", 4)
    solver_kw.setdefault("eps", 0.0)
    return api.ExperimentSpec(
        data=api.DataSpec(n_train=_N, n_test=_N, seed=7),
        agent=api.AgentSpec(family="polynomial", options=(("degree", 3),)),
        solver=api.SolverSpec(**solver_kw),
        faults=faults)


# ------------------------------------------------------------- trace purity


def test_trace_pure_in_seed_round_agent():
    """Every draw is a fold_in chain from (seed, tag, round, agent): repeated
    evaluation — eager or jitted — replays the identical outcome."""
    spec = _FAULTS
    jit_outcome = jax.jit(lambda r, i: broadcast_outcome(spec, r, i))
    for r in range(4):
        for i in range(3):
            rr = jnp.asarray(r, jnp.int32)
            ii = jnp.asarray(i, jnp.int32)
            d1, a1 = broadcast_outcome(spec, rr, ii)
            d2, a2 = broadcast_outcome(spec, rr, ii)
            d3, a3 = jit_outcome(rr, ii)
            assert bool(d1) == bool(d2) == bool(d3)
            assert int(a1) == int(a2) == int(a3)
            s1 = straggles(spec, rr, ii)
            assert bool(s1) == bool(straggles(spec, rr, ii))


def test_trace_coordinates_decorrelate():
    """Different (seed | round | agent) give different outcome streams —
    the trace is a function, not a constant."""
    def stream(spec, rounds, agent):
        out = []
        for r in rounds:
            d, a = broadcast_outcome(spec, jnp.asarray(r, jnp.int32),
                                     jnp.asarray(agent, jnp.int32))
            out.append((bool(d), int(a)))
        return out

    base = stream(_FAULTS, range(12), 0)
    assert stream(_FAULTS, range(12), 0) == base          # replay
    assert stream(dataclasses.replace(_FAULTS, seed=6), range(12), 0) != base
    assert stream(_FAULTS, range(12), 1) != base
    assert stream(_FAULTS, range(12, 24), 0) != base


def test_trace_ignores_topology_rng():
    """random_graph topologies draw their own numpy RNG; the fault trace must
    not interact with it (purity in (seed, round, agent) only)."""
    rr = jnp.asarray(3, jnp.int32)
    ii = jnp.asarray(1, jnp.int32)
    before = (bool(broadcast_outcome(_FAULTS, rr, ii)[0]),
              int(broadcast_outcome(_FAULTS, rr, ii)[1]),
              bool(straggles(_FAULTS, rr, ii)))
    for seed in range(4):
        tlib.build_topology("random_graph", 6,
                            options=(("p", 0.8), ("seed", seed)))
    after = (bool(broadcast_outcome(_FAULTS, rr, ii)[0]),
             int(broadcast_outcome(_FAULTS, rr, ii)[1]),
             bool(straggles(_FAULTS, rr, ii)))
    assert before == after


def test_trace_and_topology_seed_do_not_interact_end_to_end():
    """Two random_graph topology seeds, same FaultSpec, exact codec: the
    accept/reject pattern is a function of the fault trace alone, so the eta
    histories must be identical even though the graphs (and hence the byte
    costs) differ."""
    def run(topo_seed):
        spec = dataclasses.replace(
            _spec(faults=_FAULTS, n_sweeps=3),
            transport=api.TransportSpec(
                topology="random_graph",
                topology_options=(("p", 0.9), ("seed", topo_seed))))
        return api.fit(spec)

    ra, rb = run(0), run(3)
    assert ra.history.eta == rb.history.eta
    assert ra.history.train_mse == rb.history.train_mse


def test_alive_at_crash_and_rejoin_windows():
    spec = FaultSpec(crash=((1, 2, 4), (3, 1, -1)))
    expect = {0: (True, True, True, True, True),
              1: (True, True, True, False, True),
              2: (True, False, True, False, True),
              3: (True, False, True, False, True),
              4: (True, True, True, False, True)}
    for r, want in expect.items():
        got = alive_at(spec, 5, jnp.asarray(r, jnp.int32))
        assert tuple(bool(v) for v in np.asarray(got)) == want, r
    # record 0 convention: round -1 = nobody has crashed yet
    got = alive_at(spec, 5, jnp.asarray(-1, jnp.int32))
    assert all(bool(v) for v in np.asarray(got))


def test_corrupt_keeps_payload_finite_and_is_replayable():
    """Mantissa-only bit flips: corrupted floats stay finite (no NaN/inf
    smuggled into the solver), and the flip pattern replays bit-identically."""
    spec = FaultSpec(seed=9, corrupt_rate=1.0, corrupt_bits=8)
    x = jax.random.normal(jax.random.PRNGKey(0), (64,))
    rr = jnp.asarray(2, jnp.int32)
    ii = jnp.asarray(0, jnp.int32)
    c1 = corrupt(spec, x, rr, ii)
    c2 = corrupt(spec, x, rr, ii)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    assert bool(jnp.all(jnp.isfinite(c1)))
    assert bool(jnp.any(c1 != x))                 # rate=1.0 actually flips
    # inert rate is a static no-op (the zero-fault path returns x itself)
    assert corrupt(FaultSpec(), x, rr, ii) is x


# --------------------------------------------------- spec round-trips/errors


def test_fault_spec_json_roundtrip():
    spec = _spec(faults=_FAULTS)
    d = json.loads(json.dumps(api.spec_to_dict(spec)))
    back = api.spec_from_dict(d)
    assert back == spec                            # crash triples re-tupled
    assert back.faults.crash == ((1, 1, 3),)
    # a faults-free dict still loads (older saves): defaults are inert
    d2 = json.loads(json.dumps(api.spec_to_dict(_spec())))
    del d2["faults"]
    assert api.spec_from_dict(d2).faults.is_inert


def test_spec_from_dict_names_faults_key_paths():
    d = api.spec_to_dict(_spec(faults=_FAULTS))
    d["faults"]["drop_rat"] = 0.5
    with pytest.raises(api.SpecError) as e:
        api.spec_from_dict(d)
    assert "spec['faults']" in str(e.value) and "drop_rat" in str(e.value)

    d = api.spec_to_dict(_spec(faults=_FAULTS))
    d["faults"]["crash"] = [[1, 2]]               # not a triple
    with pytest.raises(api.SpecError) as e:
        api.spec_from_dict(d)
    assert "spec['faults']['crash'][0]" in str(e.value)

    d = api.spec_to_dict(_spec(faults=_FAULTS))
    d["faults"]["crash"] = 7                      # not even a sequence
    with pytest.raises(api.SpecError, match=r"spec\['faults'\]\['crash'\]"):
        api.spec_from_dict(d)


def test_fault_spec_validation_errors():
    with pytest.raises(FaultError, match="drop_rate"):
        FaultSpec(drop_rate=1.5).validate()
    with pytest.raises(FaultError, match="max_retries"):
        FaultSpec(max_retries=-1).validate()
    with pytest.raises(FaultError, match="corrupt_bits"):
        FaultSpec(corrupt_bits=0).validate()
    with pytest.raises(FaultError, match="rejoin_round"):
        FaultSpec(crash=((0, 3, 2),)).validate()


def test_experiment_spec_guards_fault_combinations():
    # faults need a trace-level injection point: icoa incremental/fused only
    with pytest.raises(api.SpecError, match="engine"):
        _spec(faults=_FAULTS, engine="dense").validate()
    with pytest.raises(api.SpecError, match="solver"):
        dataclasses.replace(_spec(faults=_FAULTS),
                            solver=api.SolverSpec(name="averaging")).validate()
    # crash re-weighting has no masked minimax closed form
    with pytest.raises(api.SpecError, match="delta"):
        _spec(faults=_FAULTS, delta=0.01).validate()
    # crash agent index must exist in the run
    bad = FaultSpec(crash=((9, 0, -1),))
    with pytest.raises(api.SpecError, match="agent 9"):
        _spec(faults=bad).validate()
    # ... and the Transport twin of the same guard
    tp = tlib.Transport(topology=tlib.build_topology("full", 5),
                        codec=tlib.build_codec("exact_f64"), faults=bad)
    with pytest.raises(tlib.TransportError, match="agent 9"):
        tp.validate_for(5)


def test_core_sweep_rejects_dense_engine_under_faults():
    xtr, ytr, _, _ = make_dataset(1, n_train=64, n_test=64, seed=0)
    xcols = jnp.stack([xtr[:, g] for g in one_per_agent(5)])
    fam = PolynomialFamily(n_cols=1, degree=2)
    tp = tlib.Transport(topology=tlib.build_topology("full", 5),
                        codec=tlib.build_codec("exact_f64"),
                        faults=FaultSpec(drop_rate=0.5))
    cfg = icoa.ICOAConfig(n_sweeps=1, engine="dense", transport=tp)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    st = icoa.init_state(fam, keys, xcols, ytr)
    with pytest.raises(ValueError, match="incremental"):
        icoa.sweep(fam, cfg, st.params, st.f, xcols, ytr,
                   jax.random.PRNGKey(1))


# ------------------------------------------------- zero-fault bit-identity


def test_inert_fault_spec_normalises_away():
    """An inject-nothing FaultSpec IS the reliable wire: Transport folds it
    to None, so the zero-fault jit cache key (and program) is unchanged."""
    tp = tlib.Transport(topology=tlib.build_topology("full", 5),
                        codec=tlib.build_codec("exact_f64"),
                        faults=FaultSpec(seed=123))
    assert tp.faults is None
    tp2 = dataclasses.replace(tp)                 # replace() re-runs post_init
    assert tp == tp2 and hash(tp) == hash(tp2)
    # the spec layer folds the same way
    assert _spec(faults=FaultSpec(seed=123)).resolved_transport().faults is None


@pytest.mark.parametrize("engine", ["incremental", "fused"])
def test_zero_fault_path_is_bit_identical(engine):
    """fit() with a default (inert) FaultSpec — even a non-default seed —
    must be BIT-identical to fit() without one, on every engine."""
    ra = api.fit(_spec(engine=engine))
    rb = api.fit(_spec(faults=FaultSpec(seed=99), engine=engine))
    assert ra.history.eta == rb.history.eta
    assert ra.history.train_mse == rb.history.train_mse
    assert ra.history.test_mse == rb.history.test_mse
    assert ra.history.bytes_transmitted == rb.history.bytes_transmitted
    np.testing.assert_array_equal(np.asarray(ra.weights),
                                  np.asarray(rb.weights))


# -------------------------------------------------- replay + ledger charging


@pytest.mark.parametrize("engine", ["incremental", "fused"])
def test_same_fault_seed_replays_identical_history_and_bytes(engine):
    """Acceptance: same FaultSpec seed => identical histories AND identical
    measured ledger bytes, retransmits included."""
    ra = api.fit(_spec(faults=_FAULTS, engine=engine))
    rb = api.fit(_spec(faults=_FAULTS, engine=engine))
    assert ra.history.eta == rb.history.eta
    assert ra.history.train_mse == rb.history.train_mse
    assert ra.history.bytes_transmitted == rb.history.bytes_transmitted
    np.testing.assert_array_equal(np.asarray(ra.weights),
                                  np.asarray(rb.weights))
    # a different fault seed draws a different trace (bytes shift with the
    # retry/skip pattern)
    rc = api.fit(_spec(faults=dataclasses.replace(_FAULTS, seed=11),
                       engine=engine))
    assert rc.history.bytes_transmitted != ra.history.bytes_transmitted


def test_retry_and_skip_both_move_the_ledger():
    """Per-sweep bytes under faults bracket the reliable-wire constant:
    retransmits charge MORE than a clean sweep, straggler/drop skips charge
    LESS — both effects must show up in the measured ledger."""
    clean = api.fit(_spec()).history.bytes_transmitted[1:]
    assert len(set(clean)) == 1                   # reliable wire: constant
    b0 = clean[0]
    # 10 sweeps: under jax's partitionable threefry draws, seed 5's first
    # net-skip sweep (skips outweighing retries) is sweep 9
    faulted = api.fit(_spec(faults=_FAULTS, n_sweeps=10)
                      ).history.bytes_transmitted[1:]
    assert max(faulted) > b0                      # charged retransmits
    assert min(faulted) < b0                      # skipped broadcasts
    # retry-on-drop (same trace seed otherwise) can only add attempts: the
    # retry policy's total bytes dominate the give-up-immediately policy's
    drops = FaultSpec(seed=5, drop_rate=0.4, max_retries=3)
    skip = dataclasses.replace(drops, max_retries=0)
    by_retry = sum(api.fit(_spec(faults=drops)).history.bytes_transmitted)
    by_skip = sum(api.fit(_spec(faults=skip)).history.bytes_transmitted)
    assert by_retry > by_skip


# ------------------------------------------------------- crash + degradation


def test_permanent_crash_zeroes_the_dead_agents_weight():
    faults = FaultSpec(crash=((2, 0, -1),))
    res = api.fit(_spec(faults=faults))
    w = np.asarray(res.weights)
    assert w[2] == 0.0
    assert abs(float(w.sum()) - 1.0) < 1e-5
    assert res.test_mse is not None


def test_rejoined_agent_recovers_weight():
    down = api.fit(_spec(faults=FaultSpec(crash=((1, 1, -1),)), n_sweeps=5))
    back = api.fit(_spec(faults=FaultSpec(crash=((1, 1, 3),)), n_sweeps=5))
    assert np.asarray(down.weights)[1] == 0.0
    assert np.asarray(back.weights)[1] != 0.0     # warm rebuild after rejoin
    # the degraded run still combines sensibly
    assert abs(float(np.asarray(down.weights).sum()) - 1.0) < 1e-5


# ---------------------------------------------------- backends + batch paths


def test_batch_fit_runs_under_faults():
    spec = _spec(faults=_FAULTS, n_sweeps=2)
    rs = api.batch_fit(spec, n_trials=2)
    assert len(rs.results) == 2
    # the fault trace is shared across trials (same FaultSpec seed), so the
    # byte histories — retransmits included — agree trial-to-trial
    assert (rs.results[0].history.bytes_transmitted
            == rs.results[1].history.bytes_transmitted)


_SHMAP_SCRIPT = r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import api
from repro.faults import FaultSpec

assert len(jax.devices()) == 5, jax.devices()
faults = FaultSpec(seed=5, drop_rate=0.3, corrupt_rate=0.2, corrupt_bits=4,
                   straggle_rate=0.1, max_retries=2, crash=((1, 1, 3),))
spec = api.ExperimentSpec(
    data=api.DataSpec(n_train=150, n_test=150, seed=7),
    agent=api.AgentSpec(family="polynomial", options=(("degree", 3),)),
    solver=api.SolverSpec(n_sweeps=3, eps=0.0),
    backend=api.BackendSpec(name="shard_map"),
    faults=faults)
ra = api.fit(spec)
rb = api.fit(spec)
assert ra.history.eta == rb.history.eta, "shard_map fault replay"
assert ra.history.bytes_transmitted == rb.history.bytes_transmitted
local = api.fit(dataclasses.replace(spec, backend=api.BackendSpec()))
np.testing.assert_allclose(np.asarray(ra.history.eta),
                           np.asarray(local.history.eta),
                           rtol=1e-5, atol=1e-12)
assert ra.history.bytes_transmitted == local.history.bytes_transmitted
print("SHMAP_FAULTS_OK")
"""


@pytest.mark.slow
def test_shard_map_backend_runs_the_same_fault_trace():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=5"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SHMAP_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHMAP_FAULTS_OK" in out.stdout


# ------------------------------------------------------------- stream chaos


def _stream_spec(faults, total_instances=512, checkpoint_every=None):
    exp = api.ExperimentSpec(
        data=api.DataSpec(source="cosine", n_train=64, n_test=64),
        solver=api.SolverSpec(name="icoa", n_sweeps=2),
        faults=faults)
    return api.StreamSpec(experiment=exp, window=256, chunk=64,
                          total_instances=total_instances, resweep_every=128,
                          checkpoint_every=checkpoint_every)


def test_stream_chaos_kill_restore_is_bit_identical(tmp_path):
    """Chaos drill: kill the stream at a seeded random arrival index (mid
    fault trace, between re-sweeps), restore from the checkpoint, and demand
    a bit-identical remainder — the fault trace must resume mid-schedule,
    not restart."""
    faults = FaultSpec(seed=5, drop_rate=0.3, max_retries=2,
                       crash=((1, 2, 5),))
    full = _stream_spec(faults, checkpoint_every=64)
    resA = stream_fit(full)                        # uninterrupted reference
    assert [r["count"] for r in resA.records] == [128, 256, 384, 512]

    # seeded chaos point: a random chunk boundary strictly inside the stream
    n_chunks = full.total_instances // full.chunk
    kill_chunk = 1 + int(jax.random.randint(jax.random.PRNGKey(42), (),
                                            0, n_chunks - 2))
    kill_at = kill_chunk * full.chunk
    ckdir = os.fspath(tmp_path / "chaos")
    stream_fit(dataclasses.replace(full, total_instances=kill_at),
               checkpoint_dir=ckdir)               # "crash" here

    resB = stream_fit(full, checkpoint_dir=ckdir, resume=True)
    survivors = [r for r in resA.records if r["count"] > kill_at]
    assert [r["count"] for r in resB.records] == [r["count"]
                                                  for r in survivors]
    for ra, rb in zip(survivors, resB.records):
        for k in ("count", "filled", "preq_n", "sweeps", "bytes",
                  "bytes_total"):
            assert ra[k] == rb[k], k
        for k in ("train_mse", "preq_mse", "eta"):
            assert ra[k] == rb[k], k               # bit-identical floats
    np.testing.assert_array_equal(np.asarray(resA.weights),
                                  np.asarray(resB.weights))
    np.testing.assert_array_equal(np.asarray(resA.state.f),
                                  np.asarray(resB.state.f))
    assert int(resA.state.ledger.spent) == int(resB.state.ledger.spent)
    assert int(resA.state.rounds) == int(resB.state.rounds)


def test_stream_serves_only_survivors_under_crash():
    """stream_fit under a permanent crash publishes survivor-masked weights
    to the PredictEngine: the dead agent never contributes to serving."""
    faults = FaultSpec(crash=((1, 0, -1),))
    spec = _stream_spec(faults, total_instances=256)
    groups = spec.experiment.data.groups
    eng = PredictEngine(PolynomialFamily(n_cols=len(groups[0]), degree=4),
                        groups, spec.experiment.data.resolved_n_attrs,
                        buckets=(4,))
    res = stream_fit(spec, engine=eng)
    assert float(np.asarray(eng._weights)[1]) == 0.0
    assert abs(float(np.asarray(eng._weights).sum()) - 1.0) < 1e-5
    assert res.records


def test_predict_engine_alive_masking_unit():
    eng = PredictEngine(PolynomialFamily(n_cols=1, degree=2), [[0], [1]], 2,
                        buckets=(1,))
    params = jnp.zeros((2, 3), jnp.float32)
    w = jnp.asarray([0.25, 0.75])
    eng.update(params, w, alive=jnp.asarray([True, False]))
    np.testing.assert_allclose(np.asarray(eng._weights), [1.0, 0.0])
    eng.update(params, w, alive=jnp.asarray([False, False]))
    np.testing.assert_allclose(np.asarray(eng._weights), [0.5, 0.5])
    eng.update(params, w, alive=None)
    np.testing.assert_allclose(np.asarray(eng._weights), [0.25, 0.75])
