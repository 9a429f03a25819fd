"""On-chip benchmark of the filtered-search serving path (``bench/run.py``)."""
