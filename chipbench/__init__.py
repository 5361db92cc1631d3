"""Chip benchmark of the served HPrepost mining path (see ``run.py``)."""
