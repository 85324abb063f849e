"""Chip benchmark of the GNN serving path (see ``run.py``)."""
