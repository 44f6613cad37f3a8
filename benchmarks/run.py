"""Benchmark harness: one function per paper table/figure (deliverable (d)).

Prints ``name,us_per_call,derived`` CSV rows. Usage:

    PYTHONPATH=src python -m benchmarks.run              # everything
    PYTHONPATH=src python -m benchmarks.run table1 fig5  # a subset

These suites reproduce the paper's numbers (MSE, bytes, fault counts) on the
host.  Their ``us_per_call`` column is a host wall time, not a chip metric:
the performance of record is the chip benchmark under ``bench/`` (PERF.md).
The harness prints a ``host_env`` row recording the compile cache and
``XLA_FLAGS`` it ran under, so every CSV capture is self-describing.
"""
from __future__ import annotations

import os
import sys
import traceback

from repro.analysis import recompile
from repro.launch.compile_cache import use_compile_cache

from benchmarks import (comm_cost, faults_bench, fig1_overtraining,
                        fig3_divergence, fig5_upper_bound, table1_algorithms,
                        table2_minimax, transport_bench)

SUITES = {
    "table1": table1_algorithms.run,     # paper Table 1
    "fig1": fig1_overtraining.run,       # paper Fig. 1
    "fig3": fig3_divergence.run,         # paper Fig. 3/4
    "table2": table2_minimax.run,        # paper Table 2
    "fig5": fig5_upper_bound.run,        # paper Fig. 5
    "comm": comm_cost.run,               # paper Fig. 2 / Sec 4 cost table
    "transport": transport_bench.run,    # trade-off curves per topology x
                                         # codec (writes BENCH_transport.json)
    "faults": faults_bench.run,          # chaos harness: MSE + retry byte
                                         # overhead vs drop x topology x
                                         # policy (writes BENCH_faults.json)
}


def _env_row(cache: str) -> str:
    """One self-describing row: the allocator, compile cache and XLA flags."""
    alloc = "tcmalloc" if "tcmalloc" in os.environ.get("LD_PRELOAD", "") \
        else "glibc"
    xla = os.environ.get("XLA_FLAGS", "")
    return f"host_env,0,alloc={alloc};jax_cache={cache};xla_flags={xla or '-'}"


def main() -> int:
    which = sys.argv[1:] or list(SUITES)
    cache = use_compile_cache(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache"))
    # recompilation audit (DESIGN.md §9.3): active only when
    # REPRO_RECOMPILE_AUDIT names a JSON path — the audit is written at exit,
    # tagged per suite selection so tools/recompile_budget.json can hold one
    # entry per benchmark entry point (bench_faults, ...)
    recompile.install_from_env("bench_" + "_".join(sorted(which)))
    print("name,us_per_call,derived")
    print(_env_row(cache), flush=True)
    failed = 0
    for name in which:
        try:
            for line in SUITES[name]():
                print(line, flush=True)
        except Exception:
            failed += 1
            traceback.print_exc()
            print(f"{name},0,SUITE_FAILED")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
