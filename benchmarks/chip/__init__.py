"""The chip benchmark: one command runs one cell (``run.py``)."""
