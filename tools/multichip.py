#!/usr/bin/env python3
"""multichip: per-chip-count scaling curves for the cyclic kernels.

The MULTICHIP artifacts used to be a smoke bit (does an 8-device mesh
compile and produce a finite residual). This tool turns them into a
real scaling measurement: each requested op (dpotrf/dgetrf/dgeqrf by
default) runs through the realized block-cyclic shard_map kernels
(:mod:`dplasma_tpu.parallel.cyclic`) at every requested chip count
(1/2/4/8 by default, square-ish ``square_grid`` meshes), and the tool
records per point::

    {"chips", "grid": [P, Q], "median_s", "gflops",
     "parallel_efficiency"}       # eff = T_1 / (chips * T_chips)

into (a) the run-report's ``"scaling"`` section (``--report``;
section added in schema v12, written at the current vintage), and
(b) the ``bench_history.jsonl`` ledger (``--history``) as
``"better": "higher"`` entries — GFlop/s AND parallel efficiency per
(op, chip count) — so ``tools/perfdiff.py`` gates scaling
regressions exactly like time regressions.

On the CPU host-platform mesh every scaling section AND every ledger
entry carries ``"placeholder": true``: virtual chips share one
socket, so the curve measures XLA partitioning overhead, not ICI —
the label keeps a later hardware baseline from silently comparing
against a placeholder curve. ``--devprof`` additionally attributes
every scaling point (the measured median through
:func:`dplasma_tpu.observability.devprof.attribute`: category
seconds, per-collective measured ICI, skew) and lands the entries in
the report's schema-v14 ``"devprof"`` section.

Self-gating: with ``--history``, the newest comparable prior ledger
entry is diffed against this run BEFORE appending. On a real
accelerator backend a regression past ``--gate-threshold`` exits
nonzero; on the CPU host-platform mesh (virtual chips share one
socket — parallel "efficiency" there measures XLA partitioning
overhead, not ICI) the gate is INFORMATIONAL by default: violations
print but the exit code stays 0 unless ``--gate-strict``. The schema
and plumbing are identical either way — the first hardware run gates
for real with no code change.

Usage::

    python tools/multichip.py --n 256 --chips 1,2,4,8 \\
        --report MULTICHIP_SCALING.json --history bench_history.jsonl
"""
from __future__ import annotations

import argparse
import os
import pathlib
import statistics
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT))
sys.path.insert(0, str(_ROOT / "tools"))

#: op -> precision letter of the measured trial (f64 cyclic kernels)
_OPS = {"potrf": "d", "getrf": "d", "geqrf": "d"}


def _csv_ints(s):
    return [int(x) for x in s.split(",") if x.strip()]


def _csv_strs(s):
    return [x.strip() for x in s.split(",") if x.strip()]


def measure_point(op: str, n: int, nb: int, dtype, chips: int,
                  nruns: int = 3):
    """One (op, chip-count) measurement through the cyclic kernels:
    build the PxQ mesh over the first ``chips`` devices and time the
    SAME trial the autotuner's cyclic key space measures
    (:func:`dplasma_tpu.tuning.search._trial_problem_cyclic` — one
    trial builder, two consumers, no drift). The 1-chip baseline runs
    the cyclic program on a 1x1 grid, so every point on the curve is
    the same algorithm. Returns ``(grid, median_s, gflops)``."""
    import jax

    from dplasma_tpu.parallel import mesh as pmesh
    from dplasma_tpu.tuning.search import _trial_problem_cyclic

    P, Q = pmesh.square_grid(chips)
    mesh = pmesh.make_mesh(P, Q, jax.devices()[:chips])
    with pmesh.use_grid(mesh):
        fn, args, flops = _trial_problem_cyclic(op, n, nb, dtype,
                                                (P, Q))
        jax.block_until_ready(fn(*args))        # compile + warm
        times = []
        for _ in range(nruns):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append(time.perf_counter() - t0)
    med = statistics.median(times)
    return (P, Q), med, flops / 1e9 / max(med, 1e-12)


def run_scaling(ops, n: int, nb: int, chips_list, nruns: int = 3,
                log=print, devprof: bool = False):
    """The full sweep: every op over every chip count. Returns the
    ``"scaling"`` section (one entry per op). On the CPU
    host-platform mesh every section is labelled
    ``"placeholder": true`` — virtual chips measure partitioning
    overhead, not hardware scaling. ``devprof=True`` attaches a
    per-point measured attribution
    (:func:`dplasma_tpu.observability.devprof.attribute`)."""
    import jax

    from dplasma_tpu.utils import config as _cfg
    placeholder = jax.default_backend() == "cpu"
    out = []
    for op in ops:
        prec = _OPS[op]
        points = []
        for chips in chips_list:
            grid, med, gf = measure_point(op, n, nb, "float64",
                                          chips, nruns)
            pt = {"chips": chips,
                  "grid": [grid[0], grid[1]],
                  "median_s": med, "gflops": round(gf, 3),
                  "parallel_efficiency": None}
            if devprof:
                from dplasma_tpu.observability import devprof as _dp
                pt["devprof"] = _dp.attribute(
                    f"multichip_{prec}{op}_n{n}_c{chips}", op, med,
                    grid, n, n, nb, itemsize=8)
            points.append(pt)
        # efficiency in a second pass so it never depends on --chips
        # ordering; without a 1-chip baseline in the sweep the column
        # stays None (and its ledger entries are absent) — visible,
        # not silently wrong
        t1 = next((p["median_s"] for p in points if p["chips"] == 1),
                  None)
        for p in points:
            if t1 is not None:
                p["parallel_efficiency"] = round(
                    t1 / (p["chips"] * p["median_s"]), 4)
            dp = p.get("devprof")
            extra = ""
            if dp is not None:
                extra = (f" devprof={dp['reconciliation']['relation']}"
                         f" ici={dp['categories']['collective'] + dp['categories']['ici']:.4g}s"
                         f" skew={dp['skew']['value']:.3f}")
            log(f"# multichip[{prec}{op}]: n={n} chips={p['chips']} "
                f"grid={p['grid'][0]}x{p['grid'][1]} "
                f"median={p['median_s']:.4g}s "
                f"{p['gflops']:.2f} GF/s "
                f"eff={p['parallel_efficiency']}{extra}")
        sec = {"op": op, "prec": prec, "n": n, "nb": nb,
               "ring": _cfg.mca_get("ring.enable") or "auto",
               "points": points}
        if placeholder:
            # virtual CPU "chips" share one socket: the curve shape
            # is XLA partitioning overhead, not ICI — label it so a
            # hardware baseline never compares against it unawares
            sec["placeholder"] = True
        out.append(sec)
    return out


def ledger_doc(scaling, n: int, provenance=None) -> dict:
    """The one-line ``bench_history.jsonl`` document: higher-better
    GFlop/s + parallel-efficiency entries per (op, chip count), under
    metric names perfdiff compares across runs. Carries the
    ``"family"`` envelope key (ledger contract since schema v18) and,
    when given, the attribution ``provenance`` stamp."""
    from dplasma_tpu.tuning import db as tdb
    entries = []
    any_placeholder = False
    for sec in scaling:
        name = f"{sec['prec']}{sec['op']}"
        ph = bool(sec.get("placeholder"))
        any_placeholder = any_placeholder or ph
        for pt in sec["points"]:
            base = f"multichip_{name}_n{n}_c{pt['chips']}"
            row = {"metric": f"{base}_gflops",
                   "value": pt["gflops"],
                   "unit": "GFlop/s", "better": "higher",
                   "chips": pt["chips"]}
            if ph:
                row["placeholder"] = True
            entries.append(row)
            if pt["parallel_efficiency"] is not None:
                row = {"metric": f"{base}_eff",
                       "value": pt["parallel_efficiency"],
                       "unit": "frac", "better": "higher",
                       "chips": pt["chips"]}
                if ph:
                    row["placeholder"] = True
                entries.append(row)
    doc = {"metric": "multichip_scaling", "value": len(entries),
           "unit": "points", "ladder": entries,
           "family": "multichip",
           "pipeline": tdb.resolved_knobs(grid=(1, 1))}
    if provenance is not None:
        doc["provenance"] = provenance
    if any_placeholder:
        doc["placeholder"] = True
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="multichip", description=__doc__.splitlines()[0])
    ap.add_argument("--ops", type=_csv_strs,
                    default=["potrf", "getrf", "geqrf"],
                    help="comma list from potrf,getrf,geqrf")
    ap.add_argument("--n", type=int, default=256,
                    help="problem size per point (same N at every "
                         "chip count — strong scaling)")
    ap.add_argument("--nb", type=int, default=32, help="tile size")
    ap.add_argument("--chips", type=_csv_ints, default=[1, 2, 4, 8],
                    help="chip counts (default 1,2,4,8)")
    ap.add_argument("--nruns", type=int, default=3)
    ap.add_argument("--report", default=None,
                    help="write the run-report (scaling + devprof "
                         "sections) here")
    ap.add_argument("--devprof", action="store_true",
                    help="attribute every scaling point (category "
                         "seconds, measured per-collective ICI, "
                         "skew) via observability.devprof; entries "
                         "land in the report's schema-v14 "
                         "\"devprof\" section")
    ap.add_argument("--history", default=None,
                    help="bench_history.jsonl ledger to gate against "
                         "and append to")
    ap.add_argument("--gate-threshold", type=float, default=0.10)
    ap.add_argument("--gate-strict", action="store_true",
                    help="gate regressions even on the CPU "
                         "host-platform mesh (default: informational "
                         "there, binding on accelerators)")
    ns = ap.parse_args(argv)

    import jax

    from dplasma_tpu.utils.config import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_enable_x64", True)
    bad = [op for op in ns.ops if op not in _OPS]
    if bad:
        sys.stderr.write(f"multichip: unknown op(s) {bad} "
                         f"(know {sorted(_OPS)})\n")
        return 2
    ndev = len(jax.devices())
    chips = [c for c in ns.chips if c <= ndev]
    for c in ns.chips:
        if c > ndev:
            print(f"# multichip: {c} chips skipped "
                  f"({ndev} device(s) available)")
    if not chips:
        sys.stderr.write("multichip: no measurable chip counts\n")
        return 2

    scaling = run_scaling(ns.ops, ns.n, ns.nb, chips, ns.nruns,
                          devprof=ns.devprof)
    # schema v18 attribution stamp: the largest mesh actually
    # measured is the run's identity (a 2x4 scaling sweep and a 1x1
    # smoke are different experiments)
    from dplasma_tpu.observability.trend import collect_provenance
    from dplasma_tpu.parallel import mesh as pmesh
    prov = collect_provenance(
        family="multichip",
        mesh_shape=list(pmesh.square_grid(max(chips))))
    doc = ledger_doc(scaling, ns.n, provenance=prov)

    rc = 0
    if ns.history:
        import perfdiff
        if os.path.exists(ns.history):
            base = perfdiff.latest_comparable_entry(ns.history, doc)
            if base is not None:
                res = perfdiff.compare(base, doc,
                                       threshold=ns.gate_threshold)
                for line in perfdiff.format_result(res):
                    print(line)
                if not res["ok"]:
                    informational = (jax.default_backend() == "cpu"
                                     and not ns.gate_strict)
                    if informational:
                        print("# multichip: gate informational on "
                              "the CPU host-platform mesh (virtual "
                              "chips share one socket); use "
                              "--gate-strict to enforce")
                    else:
                        rc = 1
        perfdiff.append_ledger(ns.history, doc)
        print(f"# multichip: ledger entry appended to {ns.history}")

    if ns.report:
        from dplasma_tpu.observability.report import RunReport
        rep = RunReport("multichip")
        for sec in scaling:
            rep.add_scaling(sec)
            for pt in sec["points"]:
                rep.add_op(f"multichip_{sec['prec']}{sec['op']}"
                           f"_c{pt['chips']}",
                           prec=sec["prec"],
                           runs_s=[pt["median_s"]],
                           gflops=pt["gflops"])
                if pt.get("devprof") is not None:
                    rep.add_devprof(pt["devprof"])
        rep.entries.extend(doc["ladder"])
        rep.provenance = prov
        rep.write(ns.report)
        print(f"# multichip: run-report written to {ns.report}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
