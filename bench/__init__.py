"""The on-chip benchmark of the NoC sweep (see BENCHMARK.json and PERF.md)."""
