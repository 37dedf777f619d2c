"""On-chip benchmark of the SVM trainer and server (see run.py)."""
