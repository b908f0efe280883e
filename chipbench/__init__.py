"""On-chip benchmark of the streaming CNN serving path (``run.py``)."""
