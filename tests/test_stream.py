"""repro.stream: rank-1 column commits, offline parity, elastic restarts.

The online subsystem's contracts (DESIGN.md §11):
  * covstate.replace_col == a fresh build after the column swap (1e-10 f64);
  * a stream that ingests an offline training set one instance at a time and
    then resweeps reproduces api.fit's history to 1e-10 relative in f64
    (window not yet saturated — the same instances in the same order);
  * checkpoint/restore mid-stream resumes bit-identically: every subsequent
    record — ledger bytes included — equals the uninterrupted run's;
  * PredictEngine serves the exact ensemble combination and never retraces
    once warm.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.analysis import recompile
from repro.core import covstate, ensemble
from repro.stream import (ChunkSource, PredictEngine, latest_stream_step,
                          stream_fit)
from repro.stream.run import build_ingestor


def _rand_state(key, d=5, m=32):
    r = jax.random.normal(key, (d, m))
    return covstate.build(r)


# ------------------------------------------------------- rank-1 column swaps


def test_replace_col_matches_build_f64():
    with jax.enable_x64(True):
        key = jax.random.PRNGKey(0)
        st = _rand_state(key)
        c_new = jax.random.normal(jax.random.fold_in(key, 1), (5,))
        got = covstate.replace_col(st, 3, c_new)
        want = covstate.build(st.r_sub.at[:, 3].set(c_new))
        for name in ("r_sub", "a0", "m_inv", "s", "eta_tilde"):
            np.testing.assert_allclose(
                np.asarray(getattr(got, name)), np.asarray(getattr(want, name)),
                rtol=1e-10, atol=1e-12, err_msg=name)


def test_replace_col_zero_column_is_pure_append_f64():
    # the ring's warm-up regime: evicting an all-zero placeholder column must
    # be an exact no-op downdate
    with jax.enable_x64(True):
        key = jax.random.PRNGKey(1)
        r = jax.random.normal(key, (4, 16)).at[:, 7].set(0.0)
        st = covstate.build(r)
        c_new = jax.random.normal(jax.random.fold_in(key, 2), (4,))
        got = covstate.replace_col(st, 7, c_new)
        want = covstate.build(r.at[:, 7].set(c_new))
        np.testing.assert_allclose(np.asarray(got.m_inv),
                                   np.asarray(want.m_inv),
                                   rtol=1e-10, atol=1e-12)
        np.testing.assert_allclose(np.asarray(got.s), np.asarray(want.s),
                                   rtol=1e-10, atol=1e-12)


def test_replace_col_sequential_commits_bounded_drift_f64():
    # a full ring's worth of commits between refreshes stays at solver scale
    with jax.enable_x64(True):
        key = jax.random.PRNGKey(2)
        st = _rand_state(key, d=4, m=24)
        r = st.r_sub
        for j in range(24):
            c = jax.random.normal(jax.random.fold_in(key, 10 + j), (4,))
            st = covstate.replace_col(st, j, c)
            r = r.at[:, j].set(c)
        want = covstate.build(r)
        np.testing.assert_allclose(np.asarray(st.s), np.asarray(want.s),
                                   rtol=1e-9, atol=1e-11)
        np.testing.assert_allclose(float(st.eta_tilde),
                                   float(want.eta_tilde), rtol=1e-9)


# --------------------------------------------------------- streaming parity


def _stream_spec(**kw):
    exp = kw.pop("experiment", None) or api.ExperimentSpec(
        data=api.DataSpec(source="cosine", n_train=256, n_test=64),
        solver=api.SolverSpec(name="icoa", n_sweeps=5, eps=0.0))
    return api.StreamSpec(experiment=exp, **kw)


def test_stream_then_resweep_matches_offline_fit_f64():
    """Ingest N rows one at a time, resweep == api.fit on the same N rows."""
    with jax.enable_x64(True):
        api.clear_dataset_cache()
        spec = _stream_spec(window=384, chunk=1, total_instances=256,
                            resweep_every=256, sweeps_per_resweep=5)
        res = api.fit(spec.experiment)
        # reconstruct the full-attribute rows from the partitioned views
        # (one_per_agent: column j of x IS agent j's single column)
        x = jnp.stack([res.data.xcols[i, :, 0]
                       for i in range(res.data.xcols.shape[0])], axis=1)
        y = res.data.y

        ing = build_ingestor(spec)
        state = ing.init_state()
        for i in range(x.shape[0]):
            state = ing.ingest(state, x[i:i + 1], y[i:i + 1])
        assert int(state.count) == 256 and int(state.live) == 0
        state, rec = ing.resweep(state)

        hist = res.history
        np.testing.assert_allclose(rec["etas"], hist.eta[1:], rtol=1e-10,
                                   err_msg="per-sweep eta history")
        np.testing.assert_allclose(rec["train_mse"], hist.train_mse[-1],
                                   rtol=1e-10)
        np.testing.assert_allclose(np.asarray(state.weights),
                                   np.asarray(res.weights), rtol=1e-9,
                                   atol=1e-12)
        np.testing.assert_allclose(np.asarray(state.f[:, :256]),
                                   np.asarray(res.f), rtol=1e-9, atol=1e-12)
        # the ledger metered the same re-sweep traffic the offline run paid
        assert rec["bytes"] == int(sum(hist.bytes_transmitted))
        api.clear_dataset_cache()


def test_live_weights_track_resweep_weights():
    # post-resweep the served weights ARE the recorded closed-form weights
    spec = _stream_spec(window=128, chunk=64, total_instances=128,
                        resweep_every=128)
    res = stream_fit(spec)
    assert len(res.records) == 1
    np.testing.assert_allclose(np.asarray(res.state.weights),
                               np.asarray(res.weights))
    assert int(res.state.live) == 1
    assert res.records[0]["count"] == 128


# ------------------------------------------------------- elastic restarts


def test_checkpoint_roundtrip_bit_identical(tmp_path):
    ckdir = os.fspath(tmp_path / "ck")
    exp = api.ExperimentSpec(
        data=api.DataSpec(source="cosine", n_train=64, n_test=64),
        solver=api.SolverSpec(name="icoa", n_sweeps=2))
    full = api.StreamSpec(experiment=exp, window=256, chunk=64,
                          total_instances=512, resweep_every=128,
                          checkpoint_every=256)
    resA = stream_fit(full)                         # uninterrupted reference
    assert [r["count"] for r in resA.records] == [128, 256, 384, 512]

    # "kill" after 256 instances: run a half-length stream that checkpoints
    half = dataclasses.replace(full, total_instances=256)
    stream_fit(half, checkpoint_dir=ckdir)
    assert latest_stream_step(ckdir) == 256

    # restart: resume the FULL spec from the saved state
    resB = stream_fit(full, checkpoint_dir=ckdir, resume=True)
    assert [r["count"] for r in resB.records] == [384, 512]
    for ra, rb in zip(resA.records[2:], resB.records):
        for k in ("count", "filled", "preq_n", "sweeps", "bytes",
                  "bytes_total"):
            assert ra[k] == rb[k], k
        for k in ("train_mse", "preq_mse", "eta"):
            assert ra[k] == rb[k], k                 # bit-identical floats
    np.testing.assert_array_equal(np.asarray(resA.weights),
                                  np.asarray(resB.weights))
    np.testing.assert_array_equal(np.asarray(resA.state.f),
                                  np.asarray(resB.state.f))
    assert int(resA.state.ledger.spent) == int(resB.state.ledger.spent)


def test_resume_requires_checkpoint_dir():
    spec = _stream_spec(window=128, chunk=64, total_instances=128,
                        resweep_every=128)
    with pytest.raises(ValueError, match="checkpoint_dir"):
        stream_fit(spec, resume=True)


def test_legacy_checkpoint_missing_leaf_raises_named_error(tmp_path):
    """A pre-PR-9 checkpoint (no `rounds` fault-round counter) must fail with
    a CheckpointError NAMING the missing leaf + the README migration table —
    not the raw numpy KeyError the restore used to die with."""
    from repro.stream.checkpoint import (CheckpointError, restore_stream,
                                         save_stream)

    spec = _stream_spec(window=128, chunk=64, total_instances=128,
                        resweep_every=128)
    ing = build_ingestor(spec)
    state = ing.init_state()
    state = state._replace(count=jnp.asarray(64, jnp.int32))
    ckdir = os.fspath(tmp_path / "ck")
    save_stream(ckdir, state)

    # synthesize the legacy layout: strip the `rounds` leaf from BOTH the
    # npz archive and the manifest, exactly what an old release wrote
    npz = os.path.join(ckdir, "ckpt_00000064.npz")
    man = os.path.join(ckdir, "ckpt_00000064.json")
    arrays = dict(np.load(npz))
    assert ".rounds" in arrays
    del arrays[".rounds"]
    np.savez_compressed(npz, **arrays)
    manifest = json.load(open(man))
    manifest["keys"] = [k for k in manifest["keys"] if k != ".rounds"]
    json.dump(manifest, open(man, "w"))

    with pytest.raises(CheckpointError, match=r"\.rounds.*README"):
        restore_stream(ckdir, like=ing.init_state())

    # and an intact checkpoint still restores through the schema check
    ck2 = os.fspath(tmp_path / "ck2")
    save_stream(ck2, state)
    restored, step = restore_stream(ck2, like=ing.init_state())
    assert step == 64 and int(restored.count) == 64


# ------------------------------------------------------------- serving


def _served_setup():
    spec = _stream_spec(window=128, chunk=64, total_instances=256,
                        resweep_every=128)
    res = stream_fit(spec)
    groups = spec.experiment.data.groups
    eng = PredictEngine(res.family, groups,
                        spec.experiment.data.resolved_n_attrs,
                        buckets=(4, 16))
    eng.update(res.params, res.weights)
    return spec, res, eng


def test_predict_engine_matches_direct_ensemble():
    spec, res, eng = _served_setup()
    x = jax.random.uniform(jax.random.PRNGKey(3), (7, 5))
    got = eng.predict(x)
    assert got.shape == (7,)
    xc = jnp.stack([x[:, jnp.asarray(g)]
                    for g in spec.experiment.data.groups])
    preds = jax.vmap(res.family.predict)(res.params, xc)
    want = ensemble.combine(res.weights, preds)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_predict_engine_strides_oversized_batches():
    _, res, eng = _served_setup()
    x = jax.random.uniform(jax.random.PRNGKey(4), (37, 5))
    np.testing.assert_allclose(np.asarray(eng.predict(x)),
                               np.asarray(eng.predict(x)), rtol=0)
    assert eng.predict(x).shape == (37,)


def test_predict_engine_no_steady_state_retrace():
    _, res, eng = _served_setup()
    eng.warmup()
    shapes = [(1, 5), (3, 5), (16, 5), (37, 5)]
    for s in shapes:                       # warm the eager pad/slice programs
        eng.predict(jnp.zeros(s, jnp.float32)).block_until_ready()
    with recompile.count_compilations() as log:
        for s in shapes:
            eng.predict(jnp.ones(s, jnp.float32)).block_until_ready()
    assert log.total == 0, log.counts


def test_ingest_no_steady_state_retrace():
    spec = _stream_spec(window=128, chunk=64, total_instances=256,
                        resweep_every=128)
    ing = build_ingestor(spec)
    src = ChunkSource("cosine", 64, 64)
    state = ing.init_state()
    for t in range(4):                     # warm: ingest + both resweep fills
        state = ing.ingest(state, *src(t))
        if (t + 1) % 2 == 0:
            state, _ = ing.resweep(state)
    with recompile.count_compilations() as log:
        for t in range(4, 8):
            state = ing.ingest(state, *src(t))
            if (t + 1) % 2 == 0:
                state, _ = ing.resweep(state)
    assert log.total == 0, log.counts


def test_ingest_with_every_tap_on_no_steady_state_retrace():
    """Taps add carry state to the compiled resweep; with all of them on
    the steady state still compiles nothing."""
    from repro.obs import ALL_TAPS, ObsSpec

    exp = api.ExperimentSpec(
        data=api.DataSpec(source="cosine", n_train=256, n_test=64),
        solver=api.SolverSpec(name="icoa", n_sweeps=5, eps=0.0),
        obs=ObsSpec(taps=tuple(ALL_TAPS)))
    spec = _stream_spec(experiment=exp, window=128, chunk=64,
                        total_instances=256, resweep_every=128)
    ing = build_ingestor(spec)
    src = ChunkSource("cosine", 64, 64)
    state = ing.init_state()
    for t in range(4):                     # warm: ingest + both resweep fills
        state = ing.ingest(state, *src(t))
        if (t + 1) % 2 == 0:
            state, _ = ing.resweep(state)
    with recompile.count_compilations() as log:
        for t in range(4, 8):
            state = ing.ingest(state, *src(t))
            if (t + 1) % 2 == 0:
                state, _ = ing.resweep(state)
    assert log.total == 0, log.counts


# ------------------------------------------------------------ spec layer


def test_stream_spec_validation_errors():
    good = _stream_spec(window=128, chunk=64, total_instances=256,
                        resweep_every=128)
    good.validate()
    with pytest.raises(api.SpecError, match="multiple of chunk"):
        dataclasses.replace(good, window=100).validate()
    with pytest.raises(api.SpecError, match="no sweep to cadence"):
        dataclasses.replace(good, experiment=dataclasses.replace(
            good.experiment,
            solver=api.SolverSpec(name="averaging"))).validate()
    with pytest.raises(api.SpecError, match="drift"):
        dataclasses.replace(good, drift_option="nope").validate()
    with pytest.raises(api.SpecError, match="local"):
        dataclasses.replace(good, experiment=dataclasses.replace(
            good.experiment,
            backend=api.BackendSpec(name="shard_map"))).validate()


def test_stream_spec_json_roundtrip():
    spec = _stream_spec(window=128, chunk=64, total_instances=256,
                        resweep_every=128, drift_option="freq",
                        drift_start=1.0, drift_end=2.0,
                        serve_buckets=(2, 8))
    d = json.loads(json.dumps(api.stream_spec_to_dict(spec)))
    assert api.stream_spec_from_dict(d) == spec


def test_chunk_source_deterministic_and_drifting():
    src = ChunkSource("cosine", 32, 10, seed=7, drift_option="freq",
                      drift_start=1.0, drift_end=2.0)
    x0, y0 = src(0)
    x0b, y0b = src(0)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y0b))
    x9, y9 = src(9)
    assert x0.shape == (32, 5) and y9.shape == (32,)
    assert not np.allclose(np.asarray(y0), np.asarray(y9))
