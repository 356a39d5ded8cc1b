"""On-chip benchmark of the n-gram hashing data plane (see ``run.py``)."""
