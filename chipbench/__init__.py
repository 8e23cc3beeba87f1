"""The chip benchmark: one cell, one run, one result line (see run.py)."""
