"""On-chip benchmark of the repository: see ``run.py``."""
