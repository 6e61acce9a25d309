"""Chip benchmark of the REWAFL campaign engine (see `run.py`)."""
