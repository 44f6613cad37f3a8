"""Compiled Monte-Carlo execution (api v2): one program, many trials, many
devices.

Every figure in the paper is an average over independent trials of one
scenario.  `fit` runs one trial eagerly; this module splits the work along
the static/dynamic line instead:

    run_fn = build_runner(spec)      # spec-static structure closed over
    out    = run_fn(trial)           # ONLY the trial index / PRNG seeds trace

Everything decidable from the spec — array shapes, the resolved agent
family, the partition, the solver schedule, the covariance engine — is
closed over at build time; the returned `run_fn` takes a (traced) trial
offset, regenerates that trial's dataset INSIDE the trace (sources.
make_dataset is seed-traceable), and runs the solver's `*_scan` variant.

`batch_fit` then executes all trials as one compiled program, picking the
execution geometry from the spec (DESIGN.md §7):

  * local backend, >1 host device: the trial axis is sharded over a
    `launch.mesh.make_trial_mesh` — shard_map over the device axis, vmap
    within each device — so K devices run ~K trials concurrently.  Trial
    counts that do not divide the device count are padded (clamped trial
    indices) and the padding rows sliced away on return.
  * local backend, 1 device (or `backend.trial_devices=1`): the classic
    single `jit(vmap(run_fn))`.
  * shard_map backend: each trial needs the whole agent mesh, so trials run
    as a compiled `lax.scan` over `run_fn` — one XLA program, collectives
    inside the scan body, no Python-loop serial fallback.

`solver.use_kernel=True` compiles under every path: the Pallas Gram kernels
carry custom-vmap rules that lower the trial batch to batch-gridded kernels
(kernels/gram).  `backend.compute_dtype` casts the generated data (and hence
the whole solve) inside the trace; `backend.donate` donates the trial-index
buffer to the compiled program.

Trial t of a spec is exactly `fit(trial_spec(spec, t))`: both the data seed
and the solver seed are offset by t, so compiled histories are checked
against serial runs to machine precision (tests/test_api_v2.py,
tests/test_batch_parallel.py).  The one semantic difference: the compiled
schedule is static, so `solver.eps` early-stopping cannot break the loop —
instead `History.converged_at` records where the serial rule would have
stopped (core.icoa.converged_record).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import checkify
from jax.sharding import PartitionSpec as P

from repro.analysis import sanitize
from repro.core import baselines, distributed, icoa
from repro.core import covariance as cov
from repro.data import sources as data_sources
from repro.launch.mesh import make_trial_mesh
from repro.obs import taps as obs_taps
from repro.obs.trace import trace as _obs_span

from repro.api.result import History, Result, ResultSet
from repro.api.solvers import _bytes_history, _mesh
from repro.api.specs import _COMPUTE_DTYPES, ExperimentSpec, SpecError

__all__ = ["build_runner", "build_distributed_runner", "batch_fit",
           "trial_spec", "clear_program_cache"]

_COMPILED_SOLVERS = ("icoa", "averaging", "residual_refitting")


def trial_spec(spec: ExperimentSpec, trial: int) -> ExperimentSpec:
    """The spec of Monte-Carlo trial `trial`: fresh data AND solver streams
    (both seeds offset by the trial index; trial 0 is the spec verbatim)."""
    if trial == 0:
        return spec
    return dataclasses.replace(
        spec, seed=spec.seed + trial,
        data=dataclasses.replace(spec.data, seed=spec.data.seed + trial))


def _trial_dataset(spec: ExperimentSpec, trial):
    """Generate + cast + partition one trial's data INSIDE the trace."""
    dspec = spec.data
    xtr, ytr, xte, yte = data_sources.make_dataset(
        dspec.source, n_train=dspec.n_train, n_test=dspec.n_test,
        seed=dspec.seed + trial, noise=dspec.noise,
        n_attrs=dspec.n_attrs, options=dspec.source_options)
    if spec.backend.compute_dtype is not None:
        dt = _COMPUTE_DTYPES[spec.backend.compute_dtype]
        xtr, ytr, xte, yte = (a.astype(dt) for a in (xtr, ytr, xte, yte))
    groups = dspec.groups
    xcols = jnp.stack([xtr[:, g] for g in groups])
    xcols_test = jnp.stack([xte[:, g] for g in groups])
    return xcols, ytr, xcols_test, yte


def build_runner(spec: ExperimentSpec) -> Callable[[Any], Dict[str, Any]]:
    """Close over the spec-static structure; return `run_fn(trial)`.

    `run_fn` is pure and fully traceable: `trial` may be a traced int32, so
    `jax.vmap(run_fn)(jnp.arange(k))` stages k independent trials into one
    program (and shard_map over a trial mesh shards that batch across
    devices).  It returns a dict of jnp values:

        params    stacked agent params, leading dim D
        weights   (D,) combination weights
        f         (D, N_train) final per-agent train predictions
        train_mse / test_mse / eta   history arrays (records axis)
        converged_at  (icoa only) record index of the serial eps stop
    """
    spec.validate()
    if spec.backend.name != "local":
        raise SpecError(
            "build_runner compiles the local backend only; the shard_map "
            "backend runs one-agent-per-device collectives — use "
            "build_distributed_runner (batch_fit picks the right one)")
    groups = spec.data.groups
    family = spec.agent.resolve(n_cols=len(groups[0]))
    solver = spec.solver

    def run_fn(trial) -> Dict[str, Any]:
        xcols, ytr, xcols_test, yte = _trial_dataset(spec, trial)
        seed = spec.seed + trial
        d = len(groups)

        if solver.name == "icoa":
            params, f, weights, hist = icoa.run_scan(
                family, solver.icoa_config(spec.resolved_transport(),
                                           checks=spec.backend.checks,
                                           obs=spec.obs.normalized()),
                xcols, ytr, xcols_test, yte, seed)
        elif solver.name == "averaging":
            params, f, hist = baselines.averaging_scan(
                family, xcols, ytr, xcols_test, yte, seed)
            weights = jnp.ones((d,), f.dtype) / d
        elif solver.name == "residual_refitting":
            params, f, hist = baselines.residual_refitting_scan(
                family, xcols, ytr, xcols_test, yte, solver.n_sweeps, seed,
                codec=spec.transport.resolve(d).codec)
            # the ring ensemble is the SUM of agents (see api.solvers)
            weights = jnp.ones((d,), f.dtype)
        else:
            raise SpecError(
                f"no compiled runner for solver {solver.name!r}; registered "
                f"third-party solvers run through fit()/the serial fallback")
        return {"params": params, "weights": weights, "f": f, **hist}

    return run_fn


def build_distributed_runner(spec: ExperimentSpec,
                             mesh=None) -> Callable[[Any], Dict[str, Any]]:
    """`build_runner`'s shard_map twin: one agent per mesh device.

    The returned `run_fn(trial)` is traceable (the shard_map'd sweeps stage
    under jit/scan), so `batch_fit` runs a whole trial batch as one compiled
    `lax.scan` — each trial occupies the full agent mesh, trials execute
    sequentially, and nothing falls back to eager `fit()` calls.
    """
    spec.validate()
    if spec.backend.name != "shard_map":
        raise SpecError(
            "build_distributed_runner compiles the shard_map backend; use "
            "build_runner for the local backend")
    groups = spec.data.groups
    d = len(groups)
    mesh = mesh or _mesh(spec, d)   # one-agent-per-device rule lives in solvers
    family = spec.agent.resolve(n_cols=len(groups[0]))
    solver = spec.solver

    def run_fn(trial) -> Dict[str, Any]:
        xcols, ytr, xcols_test, yte = _trial_dataset(spec, trial)
        seed = spec.seed + trial

        if solver.name == "icoa":
            params, f, weights, hist = distributed.run_scan_distributed(
                family, solver.icoa_config(spec.resolved_transport(),
                                           checks=spec.backend.checks,
                                           obs=spec.obs.normalized()),
                xcols, ytr, xcols_test, yte, seed, mesh)
        elif solver.name == "averaging":
            params, f, hist = distributed.run_averaging_scan_distributed(
                family, xcols, ytr, xcols_test, yte, seed, mesh)
            weights = jnp.ones((d,), f.dtype) / d
        elif solver.name == "residual_refitting":
            params, f, hist = distributed.run_refit_scan_distributed(
                family, xcols, ytr, xcols_test, yte, solver.n_sweeps, seed,
                mesh, codec=spec.transport.resolve(d).codec)
            weights = jnp.ones((d,), f.dtype)
        else:
            raise SpecError(
                f"no compiled distributed runner for solver {solver.name!r}; "
                f"registered third-party solvers run through fit()")
        return {"params": params, "weights": weights, "f": f, **hist}

    return run_fn


def _can_compile(spec: ExperimentSpec) -> bool:
    # every built-in solver compiles on both backends (kernel paths included);
    # only registered third-party solvers still go through serial fit()
    return spec.solver.name in _COMPILED_SOLVERS


def _trial_device_count(spec: ExperimentSpec, n_trials: int) -> int:
    avail = len(jax.devices())
    k = avail if spec.backend.trial_devices is None else spec.backend.trial_devices
    if k > avail:
        raise SpecError(
            f"backend.trial_devices={k} but only {avail} host device(s) exist "
            f"(launch with XLA_FLAGS=--xla_force_host_platform_device_count=K)")
    return min(k, n_trials)   # never mesh more devices than trials


# batch programs live in a spec-keyed memo: specs are frozen/hashable, so
# repeated batch_fit calls on the same (spec, n_trials) reuse ONE jitted
# program instead of retracing a fresh closure per call — the retrace class
# the recompilation auditor (repro.analysis.recompile) budgets against
_PROGRAM_CACHE_SIZE = 8


def _run_batch_program(fn, spec: ExperimentSpec, trials: jnp.ndarray):
    """Execute a jitted batch program (discharging checkify when armed).

    Donation is best-effort by design: the trial-index buffer is tiny and
    integer-typed, so XLA often cannot alias it into the float outputs — the
    "donated buffers were not usable" warning is the expected no-op outcome,
    not a bug, and is silenced here.
    """
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        if spec.backend.checks == "raise":
            # the scope is open while the (first-call) trace runs, so every
            # check site in the closed-over solver stack inserts; later calls
            # hit the jit cache, whose key includes spec.backend.checks
            with sanitize.sanitize_scope("raise"):
                err, out = fn(trials)
            checkify.check_error(err)
            return out
        return fn(trials)


def _local_trials(spec: ExperimentSpec, n_trials: int) -> jnp.ndarray:
    """The local backend's trial vector, built FRESH per call: it may be
    donated to the compiled program, so it must never come from the memo."""
    k = _trial_device_count(spec, n_trials)
    if k <= 1:
        return jnp.arange(n_trials)
    padded = -(-n_trials // k) * k
    return jnp.minimum(jnp.arange(padded), n_trials - 1)


def _local_batch_program(spec: ExperimentSpec, n_trials: int):
    """The local backend's pre-jit batch program + its trial vector.

    Single device (or trial_devices=1): plain `vmap(run_fn)`.  Otherwise the
    vmapped batch is shard_map'd over the trial mesh, with padding/masking
    for n_trials % k != 0: the tail re-runs the last real trial (any index
    is valid work) and callers slice its rows away.
    """
    run_fn = build_runner(spec)
    if spec.backend.checks == "raise":
        base = run_fn

        def checked_trial(t):
            # the padding clamp must keep every index a real trial — the one
            # OOB hazard of the batch geometry, so it gets a named check site
            t = sanitize.check_in_bounds(
                t, n_trials, "local batch: padded trial indices (clamped tail)")
            return base(t)

        # checkify sits INSIDE the trial vmap: the solver bodies carry
        # while-loops, and checkify cannot discharge vmap-of-while — the
        # supported orientation is vmap-of-checkify, one Error per trial
        # (check_error on the batched Error throws the first failure)
        run_fn = checkify.checkify(checked_trial)
    k = _trial_device_count(spec, n_trials)
    trials = _local_trials(spec, n_trials)
    if k <= 1:
        return jax.vmap(run_fn), trials
    mesh = make_trial_mesh(k)

    def shard(t):
        return jax.vmap(run_fn)(t)

    fn = jax.shard_map(shard, mesh=mesh, in_specs=P("trials"),
                       out_specs=P("trials"), check_vma=False)
    return fn, trials


def _shard_map_batch_program(spec: ExperimentSpec, n_trials: int):
    """The shard_map backend's pre-jit batch program: a per-device trial loop
    (lax.scan over the distributed run_fn) — each trial uses the whole agent
    mesh, so trials are sequential, but the loop is ONE XLA program, not k
    eager fit() calls."""
    run_fn = build_distributed_runner(spec)

    def loop(trials):
        carry0 = jnp.asarray(0, jnp.int32)   # typed dummy carry (reprolint)
        return jax.lax.scan(lambda c, t: (c, run_fn(t)), carry0, trials)[1]

    return loop, jnp.arange(n_trials)


@functools.lru_cache(maxsize=_PROGRAM_CACHE_SIZE)
def _jitted_batch_program(spec: ExperimentSpec, n_trials: int):
    """ONE jit wrapper per (spec, n_trials), memoised on the hashable spec.

    Without the memo every batch_fit call wraps a fresh closure in jax.jit —
    a guaranteed retrace of the largest programs in the stack.  Under
    checks="raise" the program is checkify-transformed before jit (it then
    returns (err, out) and _run_batch_program discharges the error); the
    knob is a spec field, so sanitized and bare programs key separately.
    The memoised wrapper never holds the donated trial vector — callers
    build that fresh via _local_trials / jnp.arange.
    """
    if spec.backend.name == "shard_map":
        fn, _ = _shard_map_batch_program(spec, n_trials)
        if spec.backend.checks == "raise":
            # the trial loop is a scan (not a vmap), so checkify discharges
            # through it from the outside
            fn = checkify.checkify(fn)
    else:
        # the local program already carries checkify INSIDE its trial vmap
        # (see _local_batch_program) and returns (err, out) itself
        fn, _ = _local_batch_program(spec, n_trials)
    return jax.jit(fn, donate_argnums=(0,) if spec.backend.donate else ())


def clear_program_cache() -> None:
    """Drop every memoised batch program (frees the compiled executables)."""
    _jitted_batch_program.cache_clear()


def _batch_local(spec: ExperimentSpec, n_trials: int) -> Dict[str, Any]:
    """Local backend: vmap the trial axis, sharded over the trial mesh."""
    trials = _local_trials(spec, n_trials)
    out = _run_batch_program(_jitted_batch_program(spec, n_trials), spec,
                             trials)
    if trials.shape[0] != n_trials:
        out = jax.tree.map(lambda a: a[:n_trials], out)
    return out


def _batch_shard_map(spec: ExperimentSpec, n_trials: int) -> Dict[str, Any]:
    """shard_map backend: the compiled trial loop of _shard_map_batch_program."""
    return _run_batch_program(_jitted_batch_program(spec, n_trials), spec,
                              jnp.arange(n_trials))


def batch_fit(spec: ExperimentSpec, n_trials: int, *,
              compiled: Optional[bool] = None) -> ResultSet:
    """Run `n_trials` independent Monte-Carlo trials of one spec.

    One compiled program for every built-in solver on both backends — the
    trial axis sharded across host devices on the local backend (see the
    module docstring for the geometry), a compiled scan on the shard_map
    backend, Pallas-kernel Gram paths batched via their custom-vmap rules.
    `compiled=False` forces the serial path (k `fit()` calls — what
    registered third-party solvers always use); `compiled=True` errors if the
    spec cannot compile.  Per-trial histories of every path agree to machine
    precision; the compiled paths ignore `solver.eps` (static schedule) but
    report the serial stopping record as `History.converged_at`.

    Traced as the `api.batch_fit` span (obs.trace; tags `agents_mesh`: the
    devices one trial's agents span, 1 on the local backend; `sub_rows`: the
    instances each party sends per residual gather, n_train at alpha 1, where
    the sweep bodies index nothing, else the alpha subsample).  On the
    compiled paths four child spans cover it one after another:
    `batch_fit.launch` (validation, the program memo, the asynchronous call
    into the program; tag `new_program`: the memo missed, so this call
    traced the program), `batch_fit.wait` (the host blocked on the device),
    `batch_fit.fetch` (one bulk device-to-host copy of the histories,
    params, weights and f; tag `host_bytes`: the bytes it copied) and
    `batch_fit.assemble` (the per-trial `Result`s, host views of the fetched
    arrays; tag `wire_bytes`: the residual bytes the call's trials sent,
    summed from their histories).  The serial
    path's children are its `api.fit` spans.
    """
    if compiled is None:
        compiled = _can_compile(spec)
    with _obs_span("api.batch_fit", n_trials=n_trials,
                   solver=spec.solver.name, backend=spec.backend.name,
                   agents_mesh=(len(spec.data.groups)
                                if spec.backend.name == "shard_map" else 1),
                   sub_rows=(cov.subsample_size(spec.data.n_train,
                                                spec.solver.alpha)
                             if spec.solver.alpha > 1.0
                             else spec.data.n_train)):
        if not compiled:
            _check_batch_args(spec, n_trials)
            from repro.api import fit  # local import: api.__init__ imports this module

            return ResultSet(spec, [fit(trial_spec(spec, t))
                                    for t in range(n_trials)])
        with _obs_span("batch_fit.launch") as launch:
            _check_batch_args(spec, n_trials)
            misses = _jitted_batch_program.cache_info().misses
            if spec.backend.name == "shard_map":
                out = _batch_shard_map(spec, n_trials)
            else:
                out = _batch_local(spec, n_trials)
            launch["new_program"] = (
                _jitted_batch_program.cache_info().misses != misses)
        with _obs_span("batch_fit.wait"):
            jax.block_until_ready(out)
        with _obs_span("batch_fit.fetch") as fetch:
            # `out` holds exactly what the Results need: one bulk
            # device-to-host copy of all of it, issued together, so that
            # assembly slices host arrays only
            host = jax.device_get(out)
            fetch["host_bytes"] = int(sum(
                a.nbytes for a in jax.tree.leaves(host)))
        with _obs_span("batch_fit.assemble", trials=n_trials) as assemble:
            rs = _assemble(spec, n_trials, host)
            assemble["wire_bytes"] = int(sum(
                sum(r.history.bytes_transmitted) for r in rs.results))
            return rs


def _check_batch_args(spec: ExperimentSpec, n_trials: int) -> None:
    spec.validate()
    if n_trials < 1:
        raise SpecError(f"need n_trials >= 1, got {n_trials}")


def _assemble(spec: ExperimentSpec, n_trials: int,
              host: Dict[str, Any]) -> ResultSet:
    """The per-trial `Result`s of a compiled batch, all from the host arrays
    of one fetch: histories as lists, params/weights/f as NumPy views of
    trial t's row (no device work per trial)."""
    groups = spec.data.groups
    family = spec.agent.resolve(n_cols=len(groups[0]))
    d, n = len(groups), spec.data.n_train
    n_records = host["train_mse"].shape[1]
    # icoa scans return the MEASURED per-sweep ledger; the baselines have no
    # traced ledger (averaging: zero traffic, refit: constant psum price)
    bytes_meas = host.get("bytes")
    conv = host.get("converged_at")
    # collected obs taps ride the out dict as one more stacked pytree: the
    # trial axis lands in front of the per-sweep axis (vmap/scan semantics),
    # so trial t's Metrics is a plain leading-axis slice
    taps_host = host.get("taps") or None
    bytes_hist = None if bytes_meas is not None else _bytes_history(
        spec, d, n, n_records,
        initial_record=spec.solver.name != "residual_refitting")
    obs_norm = spec.obs.normalized()

    results = []
    for t in range(n_trials):
        history = History(
            train_mse=host["train_mse"][t].tolist(),
            test_mse=host["test_mse"][t].tolist(),
            eta=host["eta"][t].tolist(),
            bytes_transmitted=(list(bytes_hist) if bytes_meas is None
                               else bytes_meas[t].astype(float).tolist()),
            converged_at=None if conv is None else int(conv[t]))
        metrics = None if taps_host is None else obs_taps.metrics_from_taps(
            obs_norm, {k: v[t] for k, v in taps_host.items()})
        results.append(Result(
            spec=trial_spec(spec, t), family=family,
            params=jax.tree.map(lambda a: a[t], host["params"]),
            weights=host["weights"][t], f=host["f"][t], history=history,
            data=None, metrics=metrics))
    return ResultSet(spec, results)
