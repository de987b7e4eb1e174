"""``device_idle_pct`` of a cell whose end-to-end metrics are the ``.short_calls``
ones: the same reading, under the name that moves ``gflops.short_calls``."""
from benchmark.metrics.device_idle_pct import read  # noqa: F401
