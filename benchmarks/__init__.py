"""The repo's benchmark: one command per cell and run (``run.py``)."""
