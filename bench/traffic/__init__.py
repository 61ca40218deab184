"""Traffic drivers, one module per kind (``bench/cells.py``)."""
