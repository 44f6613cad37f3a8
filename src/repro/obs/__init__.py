"""repro.obs — unified telemetry (DESIGN.md §13).

Three layers, one subsystem:

  * **in-trace metric taps** (spec.py / taps.py): a static `ObsSpec` on the
    experiment spec selects named per-sweep scalars — eta, the solve vector
    s, commit acceptance, budget rejections, fault retry counts, codec
    round-trip error — collected INSIDE the compiled sweep and surfaced as
    `Result.metrics` / `StreamResult.metrics`.  Off by default and
    statically gated: the off-mode program is bit-identical.
  * **host-side span tracer** (trace.py): `obs.trace`/`obs.event` emit
    structured JSONL (rendered by tools/obs_report.py) plus
    jax.profiler annotations for Perfetto/XProf captures.
  * **runtime health** (health.py): lock-free latency rings and throughput
    counters for the stream/serve loop, exported as Prometheus text via
    `stream.serve.metrics_text`.

Import discipline: this package depends only on jax/numpy and (lazily)
repro.faults — api/core/stream import IT, never the reverse.
"""
from __future__ import annotations

from repro.obs.health import Counter, LatencyRing, prometheus_text
from repro.obs.spec import ALL_TAPS, TAPS, ObsError, ObsSpec
from repro.obs.taps import Metrics
from repro.obs.trace import Tracer, active, configure, disable, event, trace

__all__ = [
    "ALL_TAPS", "Counter", "LatencyRing", "Metrics", "ObsError", "ObsSpec",
    "TAPS", "Tracer", "active", "configure", "disable", "event",
    "prometheus_text", "trace",
]
