"""The benchmark: harness, yardstick and cells (see run.py)."""
