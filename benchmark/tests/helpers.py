"""Runs of the harness at small sizes on the CPU."""
import json
import time

from benchmark import harness

SMALL = {"N": 512, "nb": 128}
CELLS = ("cholesky_f64.closed", "cholesky_f32.closed",
         "lu_f32_grid2x2.closed")


def run_small(cell, trace=False, seed=2 ** 31 + 7, **kw):
    """One run of ``cell`` at a small size on the CPU, round-tripped
    through the line the harness prints."""
    kw.setdefault("sizes", SMALL)
    line = harness.run(cell, seed, 0.5, trace, t_process=time.perf_counter(),
                       require_chip=False, **kw)
    return json.loads(json.dumps(line))
