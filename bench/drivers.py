"""The ways a traffic mix drives the program, by the traffic file's "entry"
(today `batch_fit`).  Each driver builds the program's specs from the
configuration, warms every shape the window will use, measures one
closed-loop window, and afterwards checks what the window produced against
the configuration's reference.  `_call` returns the histories the check
reads, and `reference` the reference's records for them.

A configuration names its reference by the key "reference": a module under
bench/ (default `reference`, the unprotected ICOA of bench/reference.py)
with a function `records(data, seeds, *, agent, solver, n_sweeps, prec)`
that returns each trial's records 0..n_sweeps as (train, test, eta), each
of shape (trials, n_sweeps + 1).  `data` is every trial's (xcols, y,
xcols_test, y_test) along a leading trial axis, regenerated here from the
trial's seed; `seeds` are those seeds, which are also the trials' solver
seeds; `agent` and `solver` are the configuration's blocks; `prec` is
"highest" for the reference and "high" for its control.  A deployment
whose mathematics differ brings its own module, and no file here changes.

Seeds: a run's base seed is drawn from `--seed` into [0, 2e9), so that the
program's int32 seeds (base + trial index, and + 1) never overflow and
nearby seeds share no trials.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import sys
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import data

SEED_SPAN = 2_000_000_000


def make(cell, seed: int, api):
    entry = cell.traffic["entry"]
    cls = {"batch_fit": BatchDriver}[entry]
    return cls(cell, seed, api)


def _apply(cls, fields: dict, where: str):
    """`cls(**fields)`, keeping only the fields the program's dataclass
    still has (a later program may derive a setting a spec names today)."""
    names = {f.name for f in dataclasses.fields(cls)}
    for k in sorted(set(fields) - names):
        print(f"bench: {where}.{k} is no field of the program's "
              f"{cls.__name__}; not applied", file=sys.stderr)
    return cls(**{k: v for k, v in fields.items() if k in names})


def groups_of(cfg: dict) -> List[List[int]]:
    g = cfg["data"]["groups"]
    if g == "one_per_agent":
        return [[i] for i in range(cfg["data"]["n_attrs"])]
    return [list(x) for x in g]


def experiment_spec(api, cfg: dict, seed: int):
    d = cfg["data"]
    data_fields = {
        "source": "bench_" + d["source"], "n_train": d["n_train"],
        "n_test": d["n_test"], "noise": d["noise"], "seed": seed,
        "n_attrs": d["n_attrs"],
        "source_options": tuple(sorted(d["options"].items())),
        "partition": d["partition"]}
    if "n_agents" in d:
        data_fields["n_agents"] = d["n_agents"]
    agent = api.AgentSpec(family=cfg["agent"]["family"],
                          options=(("degree", cfg["agent"]["degree"]),))
    return api.ExperimentSpec(
        data=_apply(api.DataSpec, data_fields, "data"), agent=agent,
        solver=_apply(api.SolverSpec, cfg["solver"], "solver"),
        backend=_apply(api.BackendSpec, cfg["backend"], "backend"),
        seed=seed)


def _xcols(x, groups):
    return jnp.stack([x[:, g] for g in groups])


def rel_gap(a, b) -> float:
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)


class _Driver:
    def __init__(self, cell, seed: int, api):
        self.cell, self.cfg, self.traffic = cell, cell.config, cell.traffic
        self.api = api
        self.seed = seed
        self.base = int(np.random.SeedSequence(seed).generate_state(1)[0]
                        % SEED_SPAN)
        self.groups = groups_of(self.cfg)

    def reference_data(self, seed: int):
        d = self.cfg["data"]
        xtr, ytr, xte, yte = data.make_split(
            d["source"], d["n_train"], d["n_test"], seed, d["n_attrs"],
            d["noise"], tuple(sorted(d["options"].items())))
        return _xcols(xtr, self.groups), ytr, _xcols(xte, self.groups), yte

    def free(self) -> None:
        pass


def record_gaps(pairs) -> Dict[str, float]:
    """Relative gaps between program histories and reference records, over
    records 0..K of every (history, reference) pair: `<q>_rel` the largest,
    `<q>_rel0` the largest at record 0 (the non-cooperative start, before
    any accept decision), `<q>_rel_med` and `<q>_rel_q95` the median and
    the 95th percentile over pairs of each pair's largest; q is eta, train
    or test."""
    out: Dict[str, float] = {}
    for q, col in (("train", 0), ("test", 1), ("eta", 2)):
        per = []
        for hist, ref in pairs:
            prog = {"train": hist.train_mse, "test": hist.test_mse,
                    "eta": hist.eta}[q]
            k = min(len(prog), len(ref[col]))
            per.append([rel_gap(prog[j], ref[col][j]) for j in range(k)])
        worst = [max(g) for g in per]
        out[q + "_rel"] = max(worst)
        out[q + "_rel0"] = max(g[0] for g in per)
        out[q + "_rel_med"] = float(np.median(worst))
        out[q + "_rel_q95"] = float(np.quantile(worst, 0.95))
    return out


@contextlib.contextmanager
def _uncached():
    """Compile without writing to the persistent compilation cache."""
    key = "jax_persistent_cache_min_compile_time_secs"
    old = getattr(jax.config, key)
    jax.config.update(key, 1e9)
    try:
        yield
    finally:
        jax.config.update(key, old)


class BatchDriver(_Driver):
    """Back-to-back `api.batch_fit(spec, trials)` on the run's spec: trial t
    fits the data of seed base + t.

    `batch_fit` folds the spec's seed into its compiled program as a
    constant, so each seed has a program of its own.  Set-up first runs the
    configuration at seed 0, which compiles (or loads from the cache) every
    program a call needs, then compiles the run's own batch program without
    writing it to the cache: every run does the same work in set-up,
    whether its seed has run in this checkout before or not."""

    def setup(self) -> None:
        self.trials = self.traffic["trials"]
        self.spec = experiment_spec(self.api, self.cfg, 0)
        self._call()
        self.spec = experiment_spec(self.api, self.cfg, self.base)
        with _uncached():
            self._call()
        for _ in range(self.traffic["warmup_calls"] - 1):
            self._call()

    def _call(self):
        rs = self.api.batch_fit(self.spec, self.trials)
        jax.block_until_ready([(r.params, r.weights) for r in rs.results])
        return [r.history for r in rs.results]

    def window(self, seconds: float) -> Dict[str, Any]:
        n = 0
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.call"):
                self.last = self._call()
            n += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        trials = n * self.trials
        return {"e2e": {"trials_per_s": trials / elapsed},
                "elapsed_s": elapsed, "attempted": trials, "failed": 0,
                "work": {"calls": n, "trials": self.trials,
                         "sweeps": self.cfg["solver"]["n_sweeps"]}}

    def check(self) -> Dict[str, float]:
        """Every trial of the last timed call against the reference."""
        hists, self.last = self.last, None
        return record_gaps(list(zip(hists, self.reference())))

    def reference(self, precision: str = "highest"):
        """Records 0..check_sweeps of every trial, trial t from the data and
        the solver seed base + t, from the configuration's reference."""
        seeds = self.base + jnp.arange(self.trials)
        dat = jax.vmap(self.reference_data)(seeds)
        ref = importlib.import_module(
            "bench." + self.cfg.get("reference", "reference"))
        with jax.default_matmul_precision(precision):
            out = ref.records(dat, seeds, agent=self.cfg["agent"],
                              solver=self.cfg["solver"],
                              n_sweeps=self.traffic["check_sweeps"],
                              prec=precision)
        out = [np.asarray(v) for v in out]
        return [[v[t] for v in out] for t in range(self.trials)]
