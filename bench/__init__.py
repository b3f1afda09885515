"""On-chip benchmark of the arena program; see run.py."""
