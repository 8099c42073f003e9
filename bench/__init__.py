"""Chip benchmark of the served planning tick (see BENCHMARK.json)."""
