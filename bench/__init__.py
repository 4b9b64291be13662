"""The on-chip benchmark (see run.py)."""
