"""Chip benchmark of ResiHP's training paths on TPU (see run.py)."""
