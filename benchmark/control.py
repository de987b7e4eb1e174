#!/usr/bin/env python3
"""The control: the plain reference put in the program's place, in the
precision below the configuration's, run through the harness.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 2

For each seed it runs the cell as ``benchmark/run.py`` does, at the
cell's own size and load, with the timed entry replaced by
``reference.solve`` at the configuration's ``control`` dtype and
precision, and prints the run's line: the comparison is sound only if
``correct`` comes out false there. ``--stated`` puts the reference in at
the configuration's own precision instead, which has to come out
correct. All seeds run in one process. The benchmark's runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def wrap_for(config: dict, stated: bool = False):
    """``harness.run``'s ``wrap``: ``reference.solve`` in the entry's
    place, in the control's precision (or, ``stated``, the
    configuration's own), answering in the entry's dtype. A service's
    configuration names a solver per op (``{"posv": "cholesky", ...}``):
    there the reference takes the place of the batched solve
    ``fn(op, a, b)``, one problem of the batch at a time."""
    import jax

    from benchmark import reference
    if stated:
        lower, precision = config["dtype"], "highest"
    else:
        lower = config["control"]["dtype"]
        precision = config["control"]["precision"]

    def one(solver, a, b):
        return reference.solve(solver, a.astype(lower), b.astype(lower),
                               config["nb"], precision).astype(a.dtype)

    if isinstance(config["solver"], dict):
        def wrap(_fn):
            def solve(op, a, b):
                return jax.vmap(lambda x, y: one(config["solver"][op], x,
                                                 y))(a, b)
            return solve
        return wrap

    def wrap(_fn):
        def solve(a, b):
            return one(config["solver"], a, b)
        return solve
    return wrap


def run(cell_name: str, seed: int, seconds: float, *, stated: bool = False,
        sizes: dict | None = None, require_chip: bool = True,
        root: str | None = None) -> dict:
    """The line of one run of ``cell_name`` with the control in place."""
    from benchmark import harness, spec
    root = root or spec.ROOT
    c = spec.cell(spec.load_spec(root), cell_name, root)
    cfg = dict(c.config, **(sizes or {}))
    return harness.run(cell_name, seed, seconds, False,
                       t_process=time.perf_counter(), sizes=sizes,
                       require_chip=require_chip,
                       wrap=wrap_for(cfg, stated), root=root)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--stated", action="store_true",
                    help="the reference at the configuration's precision")
    ns = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark.run import use_checkout_cache
    use_checkout_cache()
    for seed in [int(s) for s in ns.seeds.split(",")]:
        line = run(ns.workload, seed, ns.seconds, stated=ns.stated)
        print(json.dumps(dict(line, seed=seed, stated=ns.stated)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
