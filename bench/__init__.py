"""On-chip benchmark of the persistent-homology engine (``bench/run.py``)."""
