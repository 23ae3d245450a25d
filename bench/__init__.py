"""Chip benchmark of the exact-selection engine; see bench/run.py."""
