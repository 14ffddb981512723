"""Chip benchmark of the SpMV plan path: see `BENCHMARK.json` and `PERF.md`."""
