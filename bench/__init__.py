"""The benchmark: cells, drivers, metric readers, references (see run.py)."""
