"""Chip benchmark of the Chronos pipeline trainer (see BENCHMARK.json)."""
