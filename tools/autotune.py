#!/usr/bin/env python3
"""autotune: sweep the knob space, persist winners, audit the DB.

The CLI face of :mod:`dplasma_tpu.tuning` — the roofline-pruned knob
search over ``(op, n, dtype, grid)`` tuning keys and the persistent
tuning database every driver's ``--autotune`` (and the serving layer)
consults::

    python tools/autotune.py sweep --ops potrf,getrf --sizes 256,512 \\
        --nbs 32,64,128 --lookaheads 0,1 --db tune_db.json \\
        --history bench_history.jsonl
    python tools/autotune.py sweep --ops potrf --sizes 512 \\
        --grid 2x2 --ring auto,on,off --db tune_db.json   # cyclic
        # key space: trials run the realized block-cyclic kernels on
        # the 2x2 mesh; ring-vs-psum is stored as a tuned decision
    python tools/autotune.py show --db tune_db.json
    python tools/autotune.py prune-report --db tune_db.json
    python tools/autotune.py export --db tune_db.json --out -
    python tools/autotune.py check --db tune_db.json   # or --check

``sweep`` enumerates candidates per key (the current default config
always first), prunes configs whose roofline lower bound already
loses to the incumbent's measured time by the ``--margin`` fraction
(each decision logged — the prune-report), measures survivors (every
trial appended to the ``--history`` ledger with its full resolved
knob vector and ``"tuning": true``), and stores the deterministic
winner with provenance. A re-sweep is perfdiff-gated: a new winner
regressing past ``--gate-threshold`` against the stored winner's
measured seconds keeps the stored entry unless ``--force``.

``check`` validates a committed DB against the current schema
(``TUNE_DB_SCHEMA``) so a stale or malformed DB fails CI fast instead
of mis-steering drivers; ``--check`` is an alias. Exit codes: 0 ok,
1 problems found / nothing measured, 2 bad usage.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT))


def _csv_ints(s):
    return [int(x) for x in s.split(",") if x.strip()]


def _csv_strs(s):
    return [x.strip() for x in s.split(",") if x.strip()]


def _grid(s):
    p, _, q = s.partition("x")
    return (int(p), int(q))


def _db_arg(ns) -> str:
    from dplasma_tpu.tuning import db as tdb
    path = ns.db or tdb.db_path()
    if not path:
        sys.stderr.write("autotune: no DB (give --db, set "
                         "DPLASMA_TUNE_DB, or MCA tune.db)\n")
        raise SystemExit(2)
    return path


def cmd_sweep(ns) -> int:
    import jax

    from dplasma_tpu.utils.config import use_compile_cache
    # the sweep is compile-dominated: ride the persistent XLA cache
    use_compile_cache()
    if ns.dtype in ("float64", "complex128"):
        jax.config.update("jax_enable_x64", True)
    from dplasma_tpu.observability import roofline as _rl
    from dplasma_tpu.tuning import search
    peaks = None
    if ns.peaks_file:
        peaks, _src = _rl.resolve_peaks(ns.peaks_file)
    report = search.sweep(
        ops=ns.ops, sizes=ns.sizes, dtype=ns.dtype, grid=ns.grid,
        db_file=_db_arg(ns), nbs=ns.nbs, lookaheads=ns.lookaheads,
        agg_depths=ns.agg_depths, panel_kernels=ns.panel_kernels,
        ring_modes=ns.ring, nruns=ns.nruns, margin=ns.margin,
        prune=not ns.no_prune, history=ns.history, peaks=peaks,
        gate_threshold=ns.gate_threshold, force=ns.force,
        devprof=ns.devprof)
    stored = sum(1 for k in report["keys"]
                 if k.get("decision") == "stored")
    kept = sum(1 for k in report["keys"]
               if k.get("decision") == "kept-prior")
    pruned = sum(len(k["pruned"]) for k in report["keys"])
    measured = sum(len(k["trials"]) for k in report["keys"])
    print(f"# autotune sweep: {len(report['keys'])} key(s), "
          f"{measured} trial(s) measured, {pruned} config(s) pruned, "
          f"{stored} winner(s) stored, {kept} kept prior")
    return 0 if measured or kept else 1


def cmd_show(ns) -> int:
    from dplasma_tpu.tuning import TuningDB
    db = TuningDB.load(_db_arg(ns))
    print(f"# tuning DB schema {db.schema}, "
          f"{len(db.entries)} entr(y/ies)")
    for key in sorted(db.entries):
        e = db.entries[key]
        knobs = e.get("knobs") or {}
        gf = e.get("gflops")
        print("%-40s nb=%-5s %s  %.4gs%s  (%d trial(s), %s)"
              % (key, knobs.get("nb"),
                 " ".join(f"{k}={knobs[k]}" for k in sorted(knobs)
                          if k not in ("nb", "grid")),
                 e.get("measured_s", float("nan")),
                 f" {gf:.2f}GF/s" if isinstance(gf, (int, float))
                 else "",
                 e.get("trials", 1), e.get("source", "?")))
    return 0


def cmd_prune_report(ns) -> int:
    path = _db_arg(ns) + ".sweep.json"
    try:
        with open(path) as f:
            rep = json.load(f)
    except OSError as exc:
        sys.stderr.write(f"autotune: no sweep report ({exc}); run "
                         "`autotune sweep` first\n")
        return 1
    total = 0
    for k in rep.get("keys", []):
        for p in k.get("pruned", []):
            total += 1
            print("%-40s pruned %s : bound %.4gs > incumbent %.4gs "
                  "+%.0f%%"
                  % (k["key"], json.dumps(p["config"], sort_keys=True),
                     p["expected_s"], p["incumbent_s"],
                     100.0 * p["margin"]))
    print(f"# {total} config(s) pruned across "
          f"{len(rep.get('keys', []))} key(s)")
    return 0


def cmd_export(ns) -> int:
    from dplasma_tpu.tuning import TuningDB
    db = TuningDB.load(_db_arg(ns))
    text = json.dumps(db.snapshot(), indent=1, sort_keys=True) + "\n"
    if not ns.out or ns.out == "-":
        sys.stdout.write(text)
    else:
        with open(ns.out, "w") as f:
            f.write(text)
    return 0


def cmd_check(ns) -> int:
    from dplasma_tpu.tuning import TuningDB
    path = _db_arg(ns)
    try:
        db = TuningDB.load(path)
    except (OSError, ValueError) as exc:
        sys.stderr.write(f"autotune check: {exc}\n")
        return 1
    problems = db.check()
    for p in problems:
        sys.stderr.write(f"autotune check: {path}: {p}\n")
    print(f"# autotune check: {path}: "
          f"{'OK' if not problems else f'{len(problems)} problem(s)'}"
          f" ({len(db.entries)} entr(y/ies), schema {db.schema})")
    return 1 if problems else 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    # `tools/autotune.py --check [--db PATH]` is the documented CI
    # spelling — alias it onto the check subcommand
    if argv and argv[0] == "--check":
        argv[0] = "check"
    ap = argparse.ArgumentParser(
        prog="autotune", description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    def add_db(p):
        p.add_argument("--db", default=None,
                       help="tuning DB path (default: env "
                            "DPLASMA_TUNE_DB / MCA tune.db)")

    sp = sub.add_parser("sweep", help="measure the knob space and "
                                      "persist per-key winners")
    add_db(sp)
    sp.add_argument("--ops", type=_csv_strs, default=["potrf", "getrf"],
                    help="comma list of op classes "
                         "(potrf,getrf,geqrf,gemm)")
    sp.add_argument("--sizes", type=_csv_ints, default=[256],
                    help="comma list of problem sizes N")
    sp.add_argument("--dtype", default="float32")
    sp.add_argument("--grid", type=_grid, default=(1, 1),
                    metavar="PxQ")
    sp.add_argument("--nbs", type=_csv_ints, default=None,
                    help="tile-size candidates (default: a ladder "
                         "around N)")
    sp.add_argument("--lookaheads", type=_csv_ints, default=None)
    sp.add_argument("--agg-depths", type=_csv_ints, default=None)
    sp.add_argument("--panel-kernels", type=_csv_strs, default=None)
    sp.add_argument("--ring", type=_csv_strs, default=None,
                    metavar="MODES",
                    help="ring.enable candidates for the cyclic-grid "
                         "key space (comma list of auto,on,off) — "
                         "stores ring-vs-psum as a tuned decision")
    sp.add_argument("--nruns", type=int, default=None,
                    help="timed runs per trial (default MCA "
                         "tune.nruns)")
    sp.add_argument("--margin", type=float, default=None,
                    help="roofline prune margin (default MCA "
                         "tune.margin)")
    sp.add_argument("--no-prune", action="store_true",
                    help="measure every candidate (pruning off)")
    sp.add_argument("--history", default=None,
                    help="bench_history.jsonl ledger for trial "
                         "entries")
    sp.add_argument("--peaks-file", default=None,
                    help="hardware peaks for the pruning bound "
                         "(bench doc/report or raw peaks dict)")
    sp.add_argument("--gate-threshold", type=float, default=0.10,
                    help="perfdiff re-tune gate threshold")
    sp.add_argument("--devprof", action="store_true",
                    help="attach measured-ICI evidence to every "
                         "stored winner (observability.devprof "
                         "attribution of the winning median: ici "
                         "seconds + fraction of run, achieved-ICI "
                         "fraction, reconciliation relation, skew)")
    sp.add_argument("--force", action="store_true",
                    help="store the new winner even when the re-tune "
                         "gate flags a regression")
    sp.set_defaults(fn=cmd_sweep)

    for name, fn, hlp in (
            ("show", cmd_show, "print the DB's per-key winners"),
            ("prune-report", cmd_prune_report,
             "print the last sweep's pruning decisions"),
            ("check", cmd_check,
             "validate a committed DB against the current schema")):
        p = sub.add_parser(name, help=hlp)
        add_db(p)
        p.set_defaults(fn=fn)
    pe = sub.add_parser("export", help="dump the DB as JSON")
    add_db(pe)
    pe.add_argument("--out", default="-",
                    help="output path ('-' = stdout)")
    pe.set_defaults(fn=cmd_export)

    ns = ap.parse_args(argv)
    return ns.fn(ns)


if __name__ == "__main__":
    sys.exit(main())
