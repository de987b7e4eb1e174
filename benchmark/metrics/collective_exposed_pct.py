"""Distributed: share of the traced window in which a collective (an
HLO collective or a ``dplasma_ring_*`` ICI kernel) runs on a device and
nothing else does, averaged over the devices."""
from benchmark import reduce


def read(ctx):
    t = ctx["trace"]
    if not t.devices:
        return None
    total = 0.0
    found = False
    for plane, evs in t.devices.items():
        coll = [(a, b) for n, a, b in evs
                if t.info(n)["category"] == "collective"]
        found = found or bool(coll)
        rest = reduce.union((a, b) for n, a, b in evs
                            if t.info(n)["category"] != "collective")
        total += reduce.length(reduce.subtract(reduce.union(coll), rest))
    if not found:
        return None
    return 100.0 * total * 1e-9 / t.window_s / len(t.devices)
