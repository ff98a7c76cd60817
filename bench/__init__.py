"""Chip benchmark of the allocation system: see BENCHMARK.json and run.py."""
