"""Chip benchmark of the pooled emulated-memory serving path (see run.py)."""
