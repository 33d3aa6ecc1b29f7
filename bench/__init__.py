"""On-chip serving benchmark: one cell per run, driven by BENCHMARK.json."""
