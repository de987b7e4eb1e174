"""Per-layer metric readers, one module per metric in ``BENCHMARK.json``."""
