"""Chip benchmark of the DDM matcher and server (see ``run.py``)."""
