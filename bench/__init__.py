"""Chip benchmark of exact and landmark Isomap (see ``bench/run.py``)."""
