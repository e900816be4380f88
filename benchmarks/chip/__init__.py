"""On-chip benchmark: one cell per run, driven by BENCHMARK.json."""
