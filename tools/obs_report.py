"""Render a run summary from an obs tracer JSONL event log.

Usage::

    python tools/obs_report.py events.jsonl

Four sections, all derived from the `repro.obs.trace` schema
(``{"ev": "span"|"event", "name": ..., "t": ..., "dur_s": ..., "tags": ...}``):

  spans    per-name count / total / mean / max wall seconds — where the run
           actually spent its host time (fit, resweep cadence, checkpoints)
  batch    the `api.batch_fit` calls by backend, agent mesh size
           (`agents_mesh`) and instances sent per residual gather
           (`sub_rows`): calls, trials, mean wall seconds and the residual
           bytes the trials sent (`wire_bytes` of `batch_fit.assemble`)
  metrics  the per-record metric table from `stream.record` events (round,
           instance count, sweeps executed, eta, windowed train MSE,
           prequential MSE, re-sweep wire bytes)
  ledger   cross-check: the sum of per-record `bytes` deltas must equal the
           final record's cumulative `bytes_total` (both come from the same
           transport ledger, so a mismatch means records were dropped or the
           log mixes runs) — exit 1 on mismatch

Dependency-free (stdlib only): runs anywhere the JSONL landed, no jax
needed.
"""
from __future__ import annotations

import json
import sys
from collections import defaultdict
from typing import Any, Dict, List


def load_lines(path: str) -> List[Dict[str, Any]]:
    rows = []
    with open(path) as fh:
        for ln, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError as e:
                raise SystemExit(f"{path}:{ln}: not JSON ({e})")
    return rows


def span_table(rows: List[Dict[str, Any]]) -> List[str]:
    agg: Dict[str, List[float]] = defaultdict(list)
    for r in rows:
        if r.get("ev") == "span":
            agg[r["name"]].append(float(r.get("dur_s", 0.0)))
    out = ["== spans ==",
           f"{'name':<24} {'count':>6} {'total_s':>10} {'mean_s':>10} "
           f"{'max_s':>10}"]
    for name in sorted(agg):
        ds = agg[name]
        out.append(f"{name:<24} {len(ds):>6} {sum(ds):>10.4f} "
                   f"{sum(ds) / len(ds):>10.4f} {max(ds):>10.4f}")
    if not agg:
        out.append("(no spans)")
    return out


def batch_table(rows: List[Dict[str, Any]]) -> List[str]:
    calls = {r["id"]: r for r in rows
             if r.get("ev") == "span" and r["name"] == "api.batch_fit"}
    wire = {r["parent"]: r["tags"].get("wire_bytes", 0) for r in rows
            if r.get("ev") == "span" and r["name"] == "batch_fit.assemble"}
    agg: Dict[tuple, List[tuple]] = defaultdict(list)
    for sid, r in calls.items():
        t = r.get("tags", {})
        key = (t.get("backend", "-"), t.get("agents_mesh", "-"),
               t.get("sub_rows", "-"))
        agg[key].append((t.get("n_trials", 0), float(r.get("dur_s", 0.0)),
                         wire.get(sid, 0)))
    out = ["== batch_fit calls ==",
           f"{'backend':<10} {'agents_mesh':>11} {'sub_rows':>9} {'calls':>6} "
           f"{'trials':>8} {'mean_s':>10} {'wire_bytes':>14}"]
    for (backend, mesh, sub), cs in sorted(agg.items(), key=str):
        out.append(f"{backend:<10} {mesh:>11} {sub:>9} {len(cs):>6} "
                   f"{sum(c[0] for c in cs):>8} "
                   f"{sum(c[1] for c in cs) / len(cs):>10.4f} "
                   f"{sum(c[2] for c in cs):>14}")
    if not calls:
        out.append("(no api.batch_fit spans)")
    return out


def metric_table(records: List[Dict[str, Any]]) -> List[str]:
    out = ["== stream records ==",
           f"{'round':>6} {'count':>8} {'sweeps':>6} {'eta':>12} "
           f"{'train_mse':>12} {'preq_mse':>12} {'bytes':>12}"]
    for t in records:
        out.append(
            f"{t.get('round', '-'):>6} {t.get('count', '-'):>8} "
            f"{t.get('sweeps', '-'):>6} {t.get('eta', float('nan')):>12.6g} "
            f"{t.get('train_mse', float('nan')):>12.6g} "
            f"{t.get('preq_mse', float('nan')):>12.6g} "
            f"{t.get('bytes', 0):>12}")
    if not records:
        out.append("(no stream.record events)")
    return out


def ledger_check(records: List[Dict[str, Any]]) -> tuple:
    """(lines, ok): per-record byte deltas must sum to the final cumulative
    total — both sides come from the transport ledger."""
    out = ["== ledger cross-check =="]
    if not records:
        return out + ["(no records to check)"], True
    delta_sum = sum(int(t.get("bytes", 0)) for t in records)
    final_total = int(records[-1].get("bytes_total", -1))
    ok = delta_sum == final_total
    verdict = "OK" if ok else "MISMATCH"
    out.append(f"sum(per-record bytes) = {delta_sum}")
    out.append(f"final bytes_total     = {final_total}   [{verdict}]")
    if not ok:
        out.append("records were dropped or the log mixes runs — per-record "
                   "deltas and the cumulative total come from the SAME "
                   "transport ledger and must agree")
    return out, ok


def main(argv: List[str]) -> int:
    if len(argv) != 1:
        print(__doc__)
        return 2
    rows = load_lines(argv[0])
    records = [r["tags"] for r in rows
               if r.get("ev") == "event" and r.get("name") == "stream.record"]
    faults = [r["tags"] for r in rows
              if r.get("ev") == "event" and r.get("name") == "fault.crash"]
    runs = sorted({r["run"] for r in rows if "run" in r})
    print(f"{argv[0]}: {len(rows)} lines"
          + (f", run(s) {', '.join(map(str, runs))}" if runs else ""))
    for line in span_table(rows):
        print(line)
    print()
    for line in batch_table(rows):
        print(line)
    print()
    for line in metric_table(records):
        print(line)
    if faults:
        print()
        print("== fault events ==")
        for t in faults:
            print(f"crash at round {t.get('round')} agent {t.get('agent')}")
    print()
    lines, ok = ledger_check(records)
    for line in lines:
        print(line)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
