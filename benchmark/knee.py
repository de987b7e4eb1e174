#!/usr/bin/env python3
"""The knee of a request-serving cell: the highest offered rate it
sustains, found by one sweep on the chip.

    python3 benchmark/knee.py --workload <cell> --seed <n> --seconds 20

One process makes the cell's pool from the seed, starts the server,
warms it once, and then runs the cell's open loop at one rate after
another: 8 requests/s, doubled until a rate fails, then halved between
the last rate that passed and the first that failed until the two lie
within 10%. A rate passes when at least 97% of the requests it offered
were answered by the end of its window, and the 95th percentile of the
due-to-answer times is at most 4 times its value at 8 requests/s. It
prints one JSON line per rate and, last, the knee and 0.8 of it. The
benchmark's runs never run this; the cell's traffic file holds the
rate it chose.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

START = 8.0
COMPLETED = 0.97
P95_GROWTH = 4.0
RESOLUTION = 0.10


def _trial(client, loop, traffic, operands, seed, seconds, rate, server):
    from benchmark import serve
    t = dict(traffic, rate=rate)
    recs, t0, _ = loop.drive(client, seconds, t, seed,
                             [o.flops for o in operands])
    reqs = serve._requests(server, operands, recs)
    done = [r for r in reqs if r["t_ready"] is not None]
    lat = sorted(r["t_ready"] - r["t_due"] for r in done)
    return {"rate": rate, "offered": len(reqs),
            "completed_in_window": sum(1 for r in done
                                       if r["t_ready"] <= t0 + seconds),
            "answered": len(done),
            "p50_ms": serve._pct(lat, 50) * 1e3,
            "p95_ms": serve._pct(lat, 95) * 1e3,
            "submit_late_p95_ms": serve._pct(
                [r["t_submit"] - r["t_due"] for r in reqs], 95) * 1e3}


def sweep(cell_name: str, seed: int, seconds: float) -> list:
    import jax
    from benchmark import pool as pool_mod, serve, spec
    c = spec.cell(spec.load_spec(), cell_name)
    devices = jax.devices()[:c.chips]
    prog = spec.entry(c).build(dict(c.config), devices)
    operands = pool_mod.make(seed, c.traffic, c.config["dtype"])
    server = prog.start(None)
    rows = []
    try:
        t = time.perf_counter()
        programs = server.warm(serve.warm_shapes(operands))
        print(json.dumps({"programs": len(programs),
                          "warm_s": time.perf_counter() - t}), flush=True)
        client = serve._Client(server, operands)
        loop = spec.loop(c)

        def run(rate):
            row = _trial(client, loop, c.traffic, operands, seed, seconds,
                         rate, server)
            base = rows[0]["p95_ms"] if rows else row["p95_ms"]
            row["passed"] = bool(
                row["completed_in_window"] >= COMPLETED * row["offered"]
                and row["p95_ms"] <= P95_GROWTH * base)
            rows.append(row)
            print(json.dumps(row), flush=True)
            return row["passed"]

        lo, hi = None, START
        while run(hi):
            lo, hi = hi, 2 * hi
        while lo is not None and (hi - lo) / lo > RESOLUTION:
            mid = (lo + hi) / 2
            if run(mid):
                lo = mid
            else:
                hi = mid
    finally:
        server.close()
    knee = lo or 0.0
    print(json.dumps({"knee": knee, "rate": int(0.8 * knee)}), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ns = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark.run import use_checkout_cache
    use_checkout_cache()
    sweep(ns.workload, ns.seed, ns.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
