"""Recompilation auditor: count XLA compiles, enforce a checked-in budget.

Every jit cache miss in this stack costs seconds (the sweep programs are
large) and usually signals a broken static key — a spec that stopped being
hashable, a closure rebuilt per call, a flag that silently widened the cache.
This module turns "did we retrace?" into a number CI can diff:

    with count_compilations() as log:
        run_the_suite()
    log.counts   # {"sweep": 2, "run_fn": 1, ...}
    log.total

The counter hooks the `jax_log_compiles` logging channel: jax emits exactly
one "Compiling <name> ..." WARNING per real XLA compilation (cache hits emit
nothing), so attaching a filtering handler to that logger counts every
compile in-process with zero overhead on the hot path.

`install_from_env()` is the fleet hook: when `REPRO_RECOMPILE_AUDIT` names a
JSON path, the calling process (the pytest session via tests/conftest.py, a
benchmark entry point) counts all compiles for its lifetime and writes the
audit JSON at exit.  `tools/recompile_audit.py check` then compares audits
against `tools/recompile_budget.json` and fails CI on unexpected retraces.

Budget file format (checked in, headroom included):

    {"entries": {"tier1_suite": {"max_compiles": 900}, ...}}
"""
from __future__ import annotations

import atexit
import contextlib
import dataclasses
import json
import logging
import os
import re
from typing import Dict, Iterator, List, Optional

import jax

__all__ = ["CompilationLog", "count_compilations", "install_from_env",
           "absorb_counts", "load_budget", "check_budget", "write_audit"]

# jax logs "Compiling jit(<name>) with global shapes ..."; the group is the
# function name inside the transform wrapper
_COMPILE_RE = re.compile(r"^Compiling (?:\w+\()?([\w<>\-.]+)")
# the channel that emits one record per real XLA compile under
# jax_log_compiles (cache hits are silent)
_PXLA_LOGGER = "jax._src.interpreters.pxla"
# tracing-chatter channels that jax_log_compiles also turns on; silenced
# while the counter is active so audits don't spam stderr
_NOISY_LOGGERS = ("jax._src.dispatch",)


@dataclasses.dataclass
class CompilationLog:
    """Per-function compile counts captured by `count_compilations`."""

    counts: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def total(self) -> int:
        return sum(self.counts.values())

    def record(self, name: str) -> None:
        self.counts[name] = self.counts.get(name, 0) + 1

    def as_dict(self) -> Dict[str, object]:
        return {"total": self.total,
                "counts": dict(sorted(self.counts.items()))}


class _CountingHandler(logging.Handler):
    def __init__(self, log: CompilationLog) -> None:
        super().__init__(level=logging.DEBUG)
        self._log = log

    def emit(self, record: logging.LogRecord) -> None:
        m = _COMPILE_RE.match(record.getMessage())
        if m:
            self._log.record(m.group(1))


class _DropAll(logging.Filter):
    def filter(self, record: logging.LogRecord) -> bool:
        return False


@contextlib.contextmanager
def count_compilations() -> Iterator[CompilationLog]:
    """Count every XLA compilation in this process for the scope's extent."""
    log = CompilationLog()
    handler = _CountingHandler(log)
    pxla = logging.getLogger(_PXLA_LOGGER)
    prev_level = pxla.level
    prev_flag = jax.config.jax_log_compiles
    prev_propagate = pxla.propagate
    silencer = _DropAll()
    noisy = [logging.getLogger(name) for name in _NOISY_LOGGERS]
    jax.config.update("jax_log_compiles", True)
    pxla.addHandler(handler)
    # the counting handler needs the records; keep them off the root handlers
    pxla.propagate = False
    for lg in noisy:
        lg.addFilter(silencer)
    try:
        yield log
    finally:
        # restore (not reset) flag and propagation: scopes nest — a local
        # scope inside a process-lifetime audit must leave the outer
        # counter's state exactly as it found it
        pxla.removeHandler(handler)
        pxla.propagate = prev_propagate
        pxla.setLevel(prev_level)
        for lg in noisy:
            lg.removeFilter(silencer)
        if not prev_flag:
            jax.config.update("jax_log_compiles", False)


# ------------------------------------------------------------ process hook

# the log installed by `install_from_env`, if any — forked workers report
# their counts back through `absorb_counts` so the process audit covers them
_installed: Optional[CompilationLog] = None


def absorb_counts(counts: Dict[str, int]) -> None:
    """Fold a forked worker's compile counts into this process's audit.

    Benchmarks that must vary the XLA device count fork subprocesses (device
    topology is fixed at jax init), so their compiles are invisible to the
    parent's logging hook.  Workers count locally with `count_compilations`
    and hand `log.counts` back over stdout; the parent calls this.  No-op
    when auditing is off.
    """
    if _installed is None:
        return
    for name, n in counts.items():
        _installed.counts[name] = _installed.counts.get(name, 0) + int(n)


def write_audit(path: str, entry: str, log: CompilationLog) -> None:
    payload = {"entry": entry, **log.as_dict()}
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
        fh.write("\n")


def install_from_env(entry: str,
                     env_var: str = "REPRO_RECOMPILE_AUDIT") -> Optional[CompilationLog]:
    """Start process-lifetime compile counting when `env_var` is set.

    The audit JSON is written to the env var's path at interpreter exit
    (atexit), tagged with `entry` so one budget file can cover several
    processes (the pytest session, each benchmark entry point).  Returns the
    live log, or None when auditing is off.
    """
    global _installed
    path = os.environ.get(env_var)
    if not path:
        return None
    ctx = count_compilations()
    log = ctx.__enter__()
    _installed = log

    def _finish() -> None:
        ctx.__exit__(None, None, None)
        write_audit(path, entry, log)

    atexit.register(_finish)
    return log


# ------------------------------------------------------------ budget checks


def load_budget(path: str) -> Dict[str, Dict[str, int]]:
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    entries = data.get("entries")
    if not isinstance(entries, dict):
        raise ValueError(f"budget file {path!r} needs an 'entries' mapping")
    return entries


def check_budget(entry: str, log_total: int,
                 budget: Dict[str, Dict[str, int]]) -> List[str]:
    """Return human-readable violations (empty == within budget).

    An entry missing from the budget file is itself a violation: new audited
    processes must declare their expected compile ceiling, or regressions
    in them would pass silently.
    """
    spec = budget.get(entry)
    if spec is None:
        return [f"audit entry {entry!r} has no budget; add it to the budget "
                f"file with a measured ceiling"]
    ceiling = int(spec["max_compiles"])
    if log_total > ceiling:
        return [f"{entry}: {log_total} XLA compilations exceed the budget of "
                f"{ceiling} — an unexpected retrace crept in (check static "
                f"argument hashability / per-call closures); if the growth "
                f"is intentional, re-measure and update the budget file"]
    return []
