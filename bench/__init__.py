"""Chip benchmark of the admission path (see ``BENCHMARK.json``)."""
