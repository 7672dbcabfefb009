"""Chip benchmark of the pipeline server: one cell per run (`bench/run.py`)."""
