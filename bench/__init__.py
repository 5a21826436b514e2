"""On-chip benchmark of the join engine (see BENCHMARK.json and PERF.md)."""
