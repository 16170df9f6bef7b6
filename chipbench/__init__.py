"""The chip benchmark: one cell per run (``run.py``), driven by the files
beside it."""
