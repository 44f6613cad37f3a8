"""Live ensemble predict engine: pre-jitted closures, static shapes.

The jit-and-cache discipline of serve/engine.py applied to the ensemble:
ONE compiled program per batch-size bucket, compiled up front by `warmup()`,
so a predict request never retraces — the request batch is padded up to the
smallest bucket that fits (oversized requests stride through the largest
bucket).  `update()` swaps in fresh (params, weights) device references — a
plain attribute write, no recompilation, which is what lets the Ingestor's
resweep loop publish new weights while request threads keep calling
`predict()` (jitted executions are thread-safe; the engine never mutates
arrays in place).
"""
from __future__ import annotations

import time
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import ensemble
from repro.obs import health as obs_health

__all__ = ["PredictEngine"]


class PredictEngine:
    """Batched low-latency ensemble predict against live combination weights.

    `groups` is the attribute partition; requests arrive as full-attribute
    rows `x : (B, n_attrs)` and are sliced into per-agent column views inside
    the compiled program.
    """

    def __init__(self, family, groups: Sequence[Sequence[int]], n_attrs: int,
                 buckets: Sequence[int] = (1, 16, 128)):
        if not buckets or any(b < 1 for b in buckets):
            raise ValueError("need at least one positive bucket size")
        self.family = family
        self.n_attrs = n_attrs
        self.buckets: Tuple[int, ...] = tuple(sorted(set(int(b) for b in buckets)))
        self._gidx = [jnp.asarray(list(g), jnp.int32) for g in groups]
        self._params: Any = None
        self._weights: Any = None

        def _predict(params, weights, x):
            xc = jnp.stack([x[:, g] for g in self._gidx])   # (D, b, C)
            preds = jax.vmap(family.predict)(params, xc)    # (D, b)
            return ensemble.combine(weights, preds)         # (b,)

        # one jit wrapper; the bucket sizes key its trace cache, so warmup()
        # pre-populates exactly the programs predict() will hit
        self._fn = jax.jit(_predict)

        # in-engine runtime health (repro.obs.health): ONE latency ring per
        # bucket program, fed by the engine itself — pad + execute +
        # block_until_ready, the full request-visible cost of that program.
        # Consumers (stream_demo, the metrics_text hook) read
        # these instead of running their own stopwatches.
        self.latency = {b: obs_health.LatencyRing() for b in self.buckets}
        self.requests = obs_health.Counter()

    def update(self, params: Any, weights: jnp.ndarray,
               alive: Optional[jnp.ndarray] = None) -> None:
        """Publish fresh model state — an attribute swap, never a retrace.

        `alive` ((D,) bool, fault-degraded serving) masks dead agents out of
        the served combination and renormalises the survivors' weights —
        defence in depth over the trainer's own survivor re-weighting, so a
        crash between publishes can never serve a dead agent's stale
        predictions.  Zero survivors degrade to uniform-over-all (the engine
        keeps answering; DESIGN.md §12).  The mask is a couple of eager (D,)
        ops at publish time — the compiled predict programs are untouched.
        """
        if alive is not None:
            w = jnp.where(alive, weights, jnp.zeros_like(weights))
            s = jnp.sum(w)
            ok = s > 0
            weights = jnp.where(
                ok, w / jnp.where(ok, s, jnp.ones_like(s)),
                jnp.full_like(weights, 1.0 / weights.shape[0]))
        self._params = params
        self._weights = weights

    def warmup(self) -> None:
        """Compile every bucket program up front (requires update() first)."""
        if self._params is None:
            raise ValueError("PredictEngine.warmup before update(): no live "
                             "params to compile against")
        dt = self._weights.dtype
        for b in self.buckets:
            self._fn(self._params, self._weights,
                     jnp.zeros((b, self.n_attrs), dt)).block_until_ready()

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def _predict_one(self, x: jnp.ndarray, n: int) -> jnp.ndarray:
        """ONE bucket program execution, timed end-to-end into its ring (pad +
        execute + block_until_ready — the request-visible latency of that
        program).  The stride path calls this per slice, so each execution is
        observed exactly once."""
        b = self._bucket(n)
        t0 = time.perf_counter()
        if n < b:
            x = jnp.concatenate(
                [x, jnp.zeros((b - n, x.shape[1]), x.dtype)])
        out = self._fn(self._params, self._weights, x)
        out.block_until_ready()
        self.latency[b].observe(time.perf_counter() - t0)
        return out[:n]

    def predict(self, x: jnp.ndarray) -> jnp.ndarray:
        """(B, n_attrs) -> (B,) ensemble predictions at the live weights.

        B <= max bucket: one padded call.  Larger B strides through the
        largest bucket.  Either way every executed program was compiled at
        warmup — zero steady-state retraces (tests/test_stream.py pins it).
        Per-bucket execution latency lands in `self.latency` (obs.health
        rings); `predict` blocks on the result so the observed time is the
        caller's, not the dispatch queue's.
        """
        if self._params is None:
            raise ValueError("PredictEngine.predict before update(): no live "
                             "params/weights have been published")
        self.requests.add(1)
        x = jnp.asarray(x)
        n = x.shape[0]
        big = self.buckets[-1]
        if n > big:
            return jnp.concatenate(
                [self._predict_one(x[i:i + big], min(big, n - i))
                 for i in range(0, n, big)])
        return self._predict_one(x, n)

    # ------------------------------------------------------- metrics hook

    def metrics_rows(self, ingestor=None) -> List[tuple]:
        """(name, type, help, value, labels) rows for obs.health.
        prometheus_text — engine request/latency state plus, when an
        `Ingestor` is passed, its throughput counters and last prequential
        MSE (the full stream/serve health surface in one scrape)."""
        rows: List[tuple] = [
            ("repro_serve_requests_total", "counter",
             "predict() calls answered", float(self.requests.total), None),
            ("repro_serve_requests_per_second", "gauge",
             "request rate over the observed span", self.requests.rate, None),
        ]
        for b in self.buckets:
            ring = self.latency[b]
            lab = {"bucket": str(b)}
            rows.append((
                "repro_serve_predict_executions_total", "counter",
                "bucket program executions", float(ring.count), lab))
            for q, v in ring.percentiles().items():
                rows.append((
                    "repro_serve_predict_latency_seconds", "gauge",
                    "end-to-end bucket execution latency (ring window)",
                    v, {**lab, "quantile": q}))
        if ingestor is not None:
            for name, c in ingestor.counters.items():
                rows.append((f"repro_stream_{name}_total", "counter",
                             f"stream {name.replace('_', ' ')}",
                             float(c.total), None))
                rows.append((f"repro_stream_{name}_per_second", "gauge",
                             f"stream {name.replace('_', ' ')} rate",
                             c.rate, None))
            rows.append(("repro_stream_preq_mse", "gauge",
                         "prequential MSE of the last resweep record",
                         ingestor.last_preq_mse, None))
        return rows

    def metrics_text(self, ingestor=None) -> str:
        """Prometheus text exposition (v0.0.4) of `metrics_rows`."""
        return obs_health.prometheus_text(self.metrics_rows(ingestor))
