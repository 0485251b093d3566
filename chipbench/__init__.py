"""On-chip benchmark of the federated train-and-serve system (see run.py)."""
