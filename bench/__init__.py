"""Chip benchmark of the P2M serving path (see ``run.py``)."""
