"""On-chip benchmark of the graph-ANN serving path (see bench/run.py)."""
