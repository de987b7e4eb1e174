"""``device_idle_pct`` of a request-serving cell whose end-to-end metrics
are the ``.open`` ones: the same reading, under the name that moves
``solve_ms_p95.open``."""
from benchmark.metrics.device_idle_pct import read  # noqa: F401
