"""The repo's benchmark: one cell, one run, one JSON line (see run.py)."""
