"""On-chip benchmark of the ICOA system (see BENCHMARK.json and PERF.md)."""
