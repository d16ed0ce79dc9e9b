"""Chip benchmark of the DLT engine and the routing service (see run.py)."""
