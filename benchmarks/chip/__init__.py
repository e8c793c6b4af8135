"""Chip benchmark of the gossip-learning stack (see ``run.py``)."""
