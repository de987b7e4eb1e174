"""Sweeps and panels: share of device busy time in the operations that
JAX emits for ``cholesky``, ``lu`` and ``triangular_solve`` (and what
they lower to), found by the op-name metadata of the compiled HLO."""
from benchmark import reduce


def read(ctx):
    t = ctx["trace"]
    panel = busy = 0.0
    for plane, evs in t.devices.items():
        panel += reduce.length(reduce.union(
            (a, b) for n, a, b in evs
            if t.info(n)["category"] == "panel"))
        busy += reduce.length(t.busy(plane))
    if panel <= 0 or busy <= 0:
        return None
    return 100.0 * panel / busy
