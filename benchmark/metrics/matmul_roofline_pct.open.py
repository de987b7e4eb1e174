"""``matmul_roofline_pct`` of a request-serving cell: the same reading
over every program the traced window ran (each device op is joined to
the index of its own program), under the name that moves
``gflops.open``."""
from benchmark.metrics.matmul_roofline_pct import read  # noqa: F401
