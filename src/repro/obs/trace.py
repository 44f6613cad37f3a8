"""Host-side span tracer: structured JSONL event logs + profiler annotations.

The tracer instruments the HOST orchestration layer (api.fit's solver
dispatch, api.batch_fit's launch/wait/fetch/assemble phases, stream_fit's
resweep cadence, checkpoint saves, fault-schedule boundaries) — never traced
code: in-jit telemetry is the tap layer's job (obs.taps).  Disabled (the
default) every `trace()` / `event()` call is a cheap no-op, so instrumented
call sites cost nothing in production paths.

    from repro import obs

    obs.configure("events.jsonl", run_id="demo")
    with obs.trace("fit", solver="icoa") as tags:
        ...
        tags["hits"] = 3          # a count known only at the span's end
    obs.event("record", count=2048, bytes_total=163840)
    obs.disable()

Schema (one JSON object per line):

    {"ev": "span",  "name": ..., "run": ..., "t": <wall s>, "dur_s": ...,
     "id": <int>, "parent": <int or null>, "tags": {...}}
    {"ev": "event", "name": ..., "run": ..., "t": <wall s>, "tags": {...}}

`id` numbers the spans of one sink from 1; `parent` is the id of the span
that encloses this one on the same thread, or null.  A span is recorded if
the sink was armed when it opened and is still armed when it closes.  Rows
are kept in memory and written when `disable()` runs (or `configure()`
replaces the sink, or the process exits), so a span does no file write
while the program runs.

`tags` carries the structured coordinates — resweep spans tag the fault
trace's (round, agent) keys where applicable, so the JSONL joins against
the seeded fault schedule.  Spans additionally open a
`jax.profiler.TraceAnnotation`, so the same names land in Perfetto/XProf
captures (and on the device trace's clock) when a profiler trace is active.
`tools/obs_report.py` renders the run summary from the JSONL.
"""
from __future__ import annotations

import atexit
import contextlib
import itertools
import json
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax

__all__ = ["Tracer", "configure", "disable", "active", "trace", "event"]


class Tracer:
    """Collects span/event rows in memory and appends them to a JSONL file
    on `close()` (thread-safe)."""

    def __init__(self, path: str, run_id: Optional[str] = None) -> None:
        self.path = path
        self.run_id = run_id
        open(path, "a").close()      # an unwritable path fails here, not at exit
        self._rows: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._local = threading.local()   # per-thread stack of open span ids
        atexit.register(self.close)

    def _emit(self, obj: Dict[str, Any]) -> None:
        if self.run_id is not None:
            obj["run"] = self.run_id
        with self._lock:
            self._rows.append(obj)

    def _stack(self) -> List[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def open_span(self) -> Tuple[int, Optional[int]]:
        """(id, parent id): a new span, enclosed by the innermost span open
        on this thread."""
        stack = self._stack()
        sid = next(self._ids)
        parent = stack[-1] if stack else None
        stack.append(sid)
        return sid, parent

    def close_span(self, sid: int, parent: Optional[int], name: str,
                   t_start: float, dur_s: float, tags: Dict[str, Any]) -> None:
        stack = self._stack()
        if sid in stack:
            stack.remove(sid)
        self._emit({"ev": "span", "name": name, "t": t_start,
                    "dur_s": dur_s, "id": sid, "parent": parent,
                    "tags": tags})

    def event(self, name: str, tags: Dict[str, Any]) -> None:
        self._emit({"ev": "event", "name": name, "t": time.time(),
                    "tags": tags})

    def close(self) -> None:
        """Append the collected rows to the file (idempotent)."""
        atexit.unregister(self.close)
        with self._lock:
            rows, self._rows = self._rows, []
            if rows:
                with open(self.path, "a") as fh:
                    fh.writelines(json.dumps(r, default=str) + "\n"
                                  for r in rows)


_tracer: Optional[Tracer] = None


def configure(path: str, run_id: Optional[str] = None) -> Tracer:
    """Arm `path` (appended to) as the process-wide JSONL sink."""
    global _tracer
    if _tracer is not None:
        _tracer.close()
    _tracer = Tracer(path, run_id=run_id)
    return _tracer


def disable() -> None:
    """Write the collected rows and close the sink; trace()/event() return
    to no-ops."""
    global _tracer
    if _tracer is not None:
        _tracer.close()
        _tracer = None


def active() -> bool:
    return _tracer is not None


@contextlib.contextmanager
def trace(name: str, **tags: Any) -> Iterator[Dict[str, Any]]:
    """Span context manager: jax.profiler.TraceAnnotation + JSONL row.

    The profiler annotation opens even when no JSONL sink is configured —
    it is free unless a profiler trace is being captured — but the row (and
    the clock reads and parent bookkeeping) happen only when `configure()`
    armed the tracer.  Yields `tags`, to which the body may add counts it
    knows only at its end.
    """
    tracer = _tracer
    with jax.profiler.TraceAnnotation(name):
        if tracer is None:
            yield tags
            return
        sid, parent = tracer.open_span()
        t_wall = time.time()
        t0 = time.perf_counter()
        try:
            yield tags
        finally:
            if tracer is _tracer:       # still armed: not disabled meanwhile
                tracer.close_span(sid, parent, name, t_wall,
                                  time.perf_counter() - t0, tags)


def event(name: str, **tags: Any) -> None:
    """Point-in-time structured event (no-op when not configured)."""
    if _tracer is not None:
        _tracer.event(name, tags)
