"""On-chip serving benchmark: one run of one cell per process (``run_cell.py``)."""
