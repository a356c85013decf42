"""The benchmark: see BENCHMARK.json and PERF.md. `run.py` is the entry."""
