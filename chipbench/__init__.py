"""Chip benchmark of Armada's fused device tick (see ``run.py``)."""
