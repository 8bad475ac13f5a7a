"""Chip benchmark of the SKR datagen pipeline (see `run.py`)."""
