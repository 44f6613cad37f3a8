"""Reduce a JAX profiler trace to the numbers the benchmark reports.

A trace is first flattened to a plain structure, which is also what the
small recorded trace under bench/testdata holds:

    {"devices": [{"name": plane, "ops": [[name, start_ns, dur_ns], ...],
                  "modules": [[name, start_ns, dur_ns], ...]}, ...],
     "host": [[span name, start_ns, dur_ns], ...]}

`ops` are the device's XLA operations (kernels, fusions, collectives) and
`modules` the jitted programs that contain them.  Host spans are the
`TraceAnnotation`s of the harness and of the program; the profiler puts them
on the same clock as the device events.

    python3 -m bench.trace_reduce <trace dir or .json>   # summary of a trace
"""
from __future__ import annotations

import glob
import heapq
import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, duration_ns

# collective ops as XLA names them on the TPU (sync and async halves)
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|collective-permute|reduce-scatter|all-to-all")
_SUFFIX = re.compile(r"\(\d+\)$")


def base_name(name: str) -> str:
    """'jit_sweep(123)' -> 'jit_sweep'; an op's HLO text
    '%fusion.12 = f32[..] fusion(..)' -> 'fusion.12'."""
    name = name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", name)


def load(path: str) -> dict:
    """Flatten a profiler directory (or a flattened .json) to the structure
    above.  Device planes are the ones named /device:TPU:<n> (or any
    /device: plane with an 'XLA Ops' line)."""
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    out: dict = {"devices": [], "host": []}
    for fname in files:
        pd = ProfileData.from_file(fname)
        for plane in pd.planes:
            lines = {ln.name: ln for ln in plane.lines}
            if plane.name.startswith("/device:") and "XLA Ops" in lines:
                dev = {"name": plane.name, "ops": [], "modules": []}
                for key, line in (("ops", "XLA Ops"), ("modules", "XLA Modules")):
                    if line in lines:
                        dev[key] = [[e.name, e.start_ns, e.duration_ns]
                                    for e in lines[line].events]
                out["devices"].append(dev)
            elif plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    out["host"].extend([e.name, e.start_ns, e.duration_ns]
                                       for e in line.events
                                       if e.duration_ns > 0)
    out["devices"].sort(key=lambda d: _device_index(d["name"]))
    return out


def _device_index(name: str) -> int:
    m = re.search(r"(\d+)$", name)
    return int(m.group(1)) if m else 0


def merge(events: Sequence[Event], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Union of the events' intervals, clipped to [lo, hi]."""
    iv = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                if s + d > lo and s < hi)
    out: List[Tuple[float, float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(dev: dict, lo: float, hi: float) -> float:
    """Time in [lo, hi] during which some operation ran on the device."""
    return sum(e - s for s, e in merge(dev["ops"], lo, hi))


def window(trace: dict, span: str) -> Tuple[float, float]:
    """[start, end] of the named host span (the last one, if several)."""
    hits = [(s, s + d) for n, s, d in trace["host"] if n == span]
    if not hits:
        raise KeyError(f"no host span {span!r} in the trace")
    return hits[-1]


def time_by_name(events: Sequence[Event], lo: float, hi: float,
                 pattern: Optional[re.Pattern] = None) -> Dict[str, float]:
    """Summed duration (ns) per base name of the events that start in
    [lo, hi], optionally only those whose name matches `pattern`."""
    out: Dict[str, float] = defaultdict(float)
    for n, s, d in events:
        if lo <= s < hi and (pattern is None or pattern.search(n)):
            out[base_name(n)] += d
    return dict(out)


def collective_ns(dev: dict, lo: float, hi: float) -> float:
    """Busy time of collective ops on the device (union, so the start and
    done halves of an async collective are not counted twice)."""
    coll = [e for e in dev["ops"] if COLLECTIVE.search(e[0].lower())]
    return sum(e - s for s, e in merge(coll, lo, hi))


def idle_gaps(dev: dict, host: Sequence[Event], lo: float, hi: float
              ) -> Dict[str, float]:
    """Idle device time (ns) in [lo, hi], by the innermost host span that
    covers the middle of each gap ('(no span)' where none does)."""
    busy = merge(dev["ops"], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    spans = sorted((s, s + d, n) for n, s, d in host)
    out: Dict[str, float] = defaultdict(float)
    # sweep the gaps in time order; the heap holds the spans begun so far,
    # narrowest first, and drops the narrowest while it has already ended
    live: list = []
    j = 0
    for g0, g1 in gaps:
        mid = 0.5 * (g0 + g1)
        while j < len(spans) and spans[j][0] <= mid:
            s, e, n = spans[j]
            heapq.heappush(live, (e - s, e, n))
            j += 1
        while live and live[0][1] < mid:
            heapq.heappop(live)
        out[live[0][2] if live else "(no span)"] += g1 - g0
    return dict(out)


def top(d: Dict[str, float], k: int = 10, scale: float = 1e-9
        ) -> List[list]:
    """The k largest entries as [[name, value * scale], ...]."""
    return [[n, v * scale] for n, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:k]]


def summarize(trace: dict) -> dict:
    """Plane, line and event-name counts: what to read before matching."""
    out = {"host_spans": {}, "devices": []}
    names: Dict[str, int] = defaultdict(int)
    for n, _, _ in trace["host"]:
        names[n] += 1
    out["host_spans"] = dict(sorted(names.items(), key=lambda kv: -kv[1])[:40])
    for dev in trace["devices"]:
        ops = time_by_name(dev["ops"], float("-inf"), float("inf"))
        mods = time_by_name(dev["modules"], float("-inf"), float("inf"))
        out["devices"].append({
            "name": dev["name"], "n_ops": len(dev["ops"]),
            "n_modules": len(dev["modules"]),
            "top_ops_s": top(ops, 25), "modules_s": top(mods, 25),
            "first_op_ns": min((s for _, s, _ in dev["ops"]), default=None),
        })
    return out


if __name__ == "__main__":
    json.dump(summarize(load(sys.argv[1])), sys.stdout, indent=1)
    print()
