"""Serving (host): the share of the operations the device ran for the
traced window's requests that no request asked for. Each dispatch runs
its program's whole batch at the bucket's n and NRHS (identity padding
and empty slots included); a request asked for the LAWN-41 operations
of its own n and NRHS. Each request carries its dispatch's batch over
the number of requests in it."""
from benchmark.pool import FLOPS


def read(ctx):
    asked = ran = 0.0
    for r in ctx.get("requests", ()):
        b = r["bucket"]
        if b is None:
            continue
        asked += r["flops"]
        ran += b["batch"] * FLOPS[r["op"]](b["n"], b["nrhs"]) \
            / b["requests"]
    if ran <= 0:
        return None
    return 100.0 * (1.0 - asked / ran)
