"""The on-chip benchmark of the graph server (``python bench/run.py``)."""
