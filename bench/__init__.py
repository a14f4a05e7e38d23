"""Chip benchmark of the scheduler's main path (see ``bench/run.py``)."""
