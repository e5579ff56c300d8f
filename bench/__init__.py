"""Chip benchmark of the plan -> schedule -> run path (see run.py)."""
