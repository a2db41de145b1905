"""On-chip benchmark of MVE program serving (see run.py)."""
