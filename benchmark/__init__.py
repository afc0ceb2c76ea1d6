"""Chip benchmark of slate_tpu: see BENCHMARK.json and PERF.md."""
