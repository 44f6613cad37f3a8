"""Standardised run output: every solver/backend combination returns the same
`Result`, so examples and benchmarks never touch solver-specific tuples again.

`History` is uniform across solvers: per-record `train_mse` / `test_mse` /
`eta` / `bytes_transmitted`. `eta` is always the MSE an optimally re-weighted
ensemble of the current agents would achieve (paper eq. 11) — for averaging
and residual refitting this is a diagnostic (they combine uniformly / by
summation), for ICOA it is the objective itself. `bytes_transmitted` is the
MEASURED wire cost of the sweep that produced the record (record 0 — the
non-cooperative init — is always 0): the transport ledger's encoded-payload
bytes × relay transmissions (DESIGN.md §8.3), codec/topology-dependent and,
under a byte budget, data-dependent — which is why `ResultSet.
cumulative_bytes` validates per-trial agreement.  The paper's transmission /
performance trade-off is directly the `(cumulative_bytes, test_mse)` pairs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import covariance as cov
from repro.core import ensemble, icoa, minimax
from repro.obs.taps import Metrics

from repro.api.specs import Dataset, ExperimentSpec

__all__ = ["History", "Result", "ResultSet"]


@dataclasses.dataclass
class History:
    train_mse: List[float] = dataclasses.field(default_factory=list)
    test_mse: List[float] = dataclasses.field(default_factory=list)
    eta: List[float] = dataclasses.field(default_factory=list)
    bytes_transmitted: List[float] = dataclasses.field(default_factory=list)
    # record index where the serial eps rule stops (|eta_k - eta_{k-1}| < eps
    # over post-sweep records).  Serial icoa runs truncate the history there,
    # so it is simply the last record; compiled batch runs execute the full
    # static schedule and report where fit() WOULD have stopped instead
    # (DESIGN.md §7).  None for solvers without an eps rule.
    converged_at: Optional[int] = None

    @property
    def total_bytes(self) -> float:
        return float(sum(self.bytes_transmitted))

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "History":
        series = {f.name: list(d.get(f.name, []))
                  for f in dataclasses.fields(cls) if f.name != "converged_at"}
        conv = d.get("converged_at")
        return cls(converged_at=None if conv is None else int(conv), **series)


@dataclasses.dataclass
class Result:
    """One run.  `fit()` (and the serial `batch_fit` path) leaves params,
    weights and f as device arrays; a compiled `batch_fit` gives host
    `np.ndarray` views of trial t's row of one bulk fetch.  `predict`,
    `mse` and `save` take either as they are."""

    spec: ExperimentSpec
    family: Any               # resolved agent family (static dataclass)
    params: Any               # stacked agent params, leading dim D
    weights: jnp.ndarray      # (D,) combination weights (sum-combining solvers
    #                           use literal ones, so `weights @ f` is uniform)
    f: jnp.ndarray            # (D, N_train) final per-agent train predictions
    history: History
    data: Optional[Dataset] = None   # in-memory only; never serialised
    metrics: Optional[Metrics] = None  # collected obs taps (spec.obs); None
    #                                    when obs is off.  In-memory only,
    #                                    like `data`: io round-trips drop it

    # ------------------------------------------------------------- evaluate

    @property
    def groups(self) -> List[List[int]]:
        return self.spec.data.groups

    @property
    def train_mse(self) -> float:
        return self.history.train_mse[-1]

    @property
    def test_mse(self) -> Optional[float]:
        return self.history.test_mse[-1] if self.history.test_mse else None

    def predict(self, x: jnp.ndarray) -> jnp.ndarray:
        """Ensemble prediction for a full (N, M) covariate matrix: slice each
        agent's columns, predict per agent, combine with the run's weights."""
        xcols = jnp.stack([x[:, g] for g in self.groups])
        preds = jax.vmap(self.family.predict)(self.params, xcols)
        return ensemble.combine(self.weights, preds)

    def mse(self, x: jnp.ndarray, y: jnp.ndarray) -> float:
        return float(jnp.mean((y - self.predict(x)) ** 2))

    def minimax_upper_bound(self, alpha: Optional[float] = None) -> float:
        """Paper eq. 28: the high-probability test-error upper bound at
        compression rate `alpha` (default: the rate this run used), computed
        from the PRE-cooperation residual covariance — every ICOA sweep only
        improves on it w.h.p."""
        if self.data is None:
            raise ValueError("minimax_upper_bound needs the in-memory Dataset "
                             "(loaded results drop it; re-run spec.data.build())")
        if alpha is None:
            alpha = self.spec.solver.alpha
        d = self.data.xcols.shape[0]
        keys = jax.random.split(jax.random.PRNGKey(self.spec.seed), d)
        state0 = icoa.init_state(self.family, keys, self.data.xcols, self.data.y)
        a_ini = cov.gram(self.data.y[None, :] - state0.f)
        # same inner-solver budget as the run itself (SolverSpec.minimax_*),
        # so the bound and the protected weights share one PGD configuration
        return minimax.upper_bound(a_ini, alpha, self.data.y.shape[0],
                                   steps=self.spec.solver.minimax_steps,
                                   lr=self.spec.solver.minimax_lr)

    # ---------------------------------------------------------- persistence

    def save(self, directory: str) -> str:
        """Checkpoint params/weights/f + the full spec and history as JSON.
        Restore with `repro.api.load(directory)`."""
        from repro.api import io  # local import: io imports Result

        return io.save_result(directory, self)


@dataclasses.dataclass
class ResultSet:
    """Monte-Carlo aggregate: every trial of ONE spec (api.batch_fit).

    Each element is a full per-trial `Result` whose spec carries that trial's
    seeds (trial t offsets both `seed` and `data.seed` by t).  Aggregates are
    computed over the trial axis; histories are truncated to the shortest
    trial before stacking (serial-fallback trials may early-stop on eps — the
    compiled batch runner always records the full static schedule).

    The paper's figures are one call:

        bytes, mean, std = rs.curve("test_mse")   # trade-off curve ± std
    """

    spec: ExperimentSpec          # the base spec (trial 0 runs it verbatim)
    results: List[Result]

    def __len__(self) -> int:
        return len(self.results)

    def __iter__(self):
        return iter(self.results)

    def __getitem__(self, i: int) -> Result:
        return self.results[i]

    @property
    def n_records(self) -> int:
        return min(len(r.history.train_mse) for r in self.results)

    def stack(self, field: str = "test_mse") -> np.ndarray:
        """(n_trials, n_records) history matrix for one History field."""
        t = self.n_records
        return np.asarray([getattr(r.history, field)[:t] for r in self.results])

    def mean(self, field: str = "test_mse") -> np.ndarray:
        return self.stack(field).mean(axis=0)

    def std(self, field: str = "test_mse") -> np.ndarray:
        return self.stack(field).std(axis=0)

    @property
    def converged_sweeps(self) -> List[Optional[int]]:
        """Per-trial record index where the serial eps rule stops (see
        History.converged_at); None where the solver has no eps rule."""
        return [r.history.converged_at for r in self.results]

    @property
    def cumulative_bytes(self) -> np.ndarray:
        """Cumulative measured wire bytes per record — defined only when the
        per-trial ledgers agree.

        Unbudgeted runs charge spec-static payload prices, so every trial's
        byte history is identical and the shared axis is well-defined.  Under
        a `byte_budget` (which rows transmit is data-dependent) — or a
        topology whose structure varies per trial — the ledgers genuinely
        diverge, and silently returning trial 0's axis would mislabel every
        other trial's curve; use `stack("bytes_transmitted")` and aggregate
        per trial instead."""
        b = self.stack("bytes_transmitted")
        scale = max(float(np.max(np.abs(b))), 1.0)
        dev = np.abs(b - b[0:1])
        if np.max(dev) > 1e-9 * scale:
            # name the first offending (trial, record) so the error points at
            # the divergent ledger, not at the aggregation that tripped on it
            trial, record = np.unravel_index(int(np.argmax(dev)), dev.shape)
            raise ValueError(
                f"per-trial byte ledgers diverge: trial {trial} record "
                f"{record} transmitted {b[trial, record]:g} bytes vs trial 0's "
                f"{b[0, record]:g} (a byte_budget or per-trial topology makes "
                f"measured traffic data-dependent); there is no single byte "
                f"axis — use np.cumsum(rs.stack('bytes_transmitted'), axis=1) "
                f"for per-trial curves")
        return np.cumsum(b[0])

    def curve(self, field: str = "test_mse") -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The paper's trade-off curve: (cumulative_bytes, mean, std)."""
        return self.cumulative_bytes, self.mean(field), self.std(field)

    @property
    def test_mse_mean(self) -> float:
        return float(self.mean("test_mse")[-1])

    @property
    def test_mse_std(self) -> float:
        return float(self.std("test_mse")[-1])
