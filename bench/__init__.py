"""The chip benchmark: one command runs one cell (``python3 -m bench.run``)."""
