"""Chip benchmark of the LSH-MoE trainer: see ``run.py`` and PERF.md."""
