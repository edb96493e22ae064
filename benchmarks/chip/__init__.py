"""Chip benchmark of this repository: see BENCHMARK.json at the root and
`run.py` in this directory."""
