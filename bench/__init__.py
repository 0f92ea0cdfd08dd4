"""Chip benchmark of the BO service (see ``bench/run.py``)."""
