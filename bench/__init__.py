"""The graph store's benchmark: one command, cells found by name (see run.py)."""
