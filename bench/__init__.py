"""On-chip benchmark of the RPCA solvers (see ``run.py``)."""
