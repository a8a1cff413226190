"""The on-chip benchmark: one cell per run (``bench/harness.py``)."""
