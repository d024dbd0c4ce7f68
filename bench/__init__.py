"""On-chip benchmark of the GBATC codec (see run.py)."""
