"""Chip benchmark of the FlexGrip-JAX serving runtime (see ``run.py``)."""
