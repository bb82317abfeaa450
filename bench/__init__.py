"""On-chip benchmark of the split executor and serving paths (see PERF.md
and BENCHMARK.json)."""
