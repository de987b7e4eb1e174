#!/usr/bin/env python3
"""Aggregate static-analysis runner: every repo gate with one exit code.

Fifteen passes, in increasing cost order:

1. ``tools/lint_excepts.py`` — no swallowed failures in
   ``dplasma_tpu/``;
2. ``dplasma_tpu.analysis.jaxlint`` — the JAX/TPU trace-safety rules
   (tracer concretization, mutable defaults, numpy-in-jit, float64
   literals, kernel nondeterminism, hard-coded mesh axis names,
   missed donations, full-operand materialization in lowmem paths);
3. a ``tools/perfdiff.py`` smoke pass — a report self-compare must
   exit 0 and a synthetically regressed report must exit nonzero with
   the offending metric named (the CI regression gate must itself be
   gated);
4. ``dplasma_tpu.analysis.threadcheck`` — the lock-discipline
   verifier over the serving/telemetry concurrency surface (T001
   guarded access outside the owning lock per the GUARDS registry,
   T002 check-then-act, T003 lock-order cycles with the full cycle
   named, T004 unregistered thread spawns, T005 publish-outside-lock
   gauge contracts) must verify the package clean, and the
   ``analysis.racefuzz`` schedule-fuzz smoke (fixed seeds,
   caller/timer/exporter thread mix against the cache/histogram/
   counter/override-stack/tracer/flight-ring/gauge invariant probes)
   must run its full surface with zero invariant failures — the
   ``schedules_run``/``invariant_failures`` counters are printed so
   perfdiff can gate a silently shrinking fuzz surface;
5. ``dplasma_tpu.analysis.palcheck`` — every ``pl.pallas_call``
   contract in the package: BlockSpec divisibility and tiling, index
   maps covering the grid, the VMEM budget, the precision contract;
6. a ``dplasma_tpu.analysis.dagcheck`` smoke pass — the analytic tile
   DAGs of all four ops (potrf/lu/qr/gemm) at 3x3 tiles on 1x1 and
   2x2 grids, plus the IR solvers' factor+solve+refine DAGs
   (posv_ir/gesv_ir, ops.refine.dag), must verify clean, with the
   comm-model reconciliation exact for the owner-computes classes;
7. a ``dplasma_tpu.analysis.memcheck`` smoke pass — the tile-liveness
   analyzer over the same four ops' DAGs (3x3 tiles, 1x1 and 2x2
   grids, wavefront and pipelined orderings) must verify clean with a
   positive resident peak and a named peak-driving task, and a
   shrunken ``memcheck.hbm_budget`` mutation must produce an
   ``hbm-budget`` diagnostic NAMING the peak task and tile plus a
   feasible spill/prefetch stream plan (the budget gate must itself
   be gated);
8. a ``dplasma_tpu.analysis.spmdcheck`` smoke pass — the cyclic
   shard_map kernels (potrf/getrf/geqrf/gemm) traced on tiny shapes
   over 1x1/2x2/1x4 grids must verify clean with the collective
   counts EXACTLY reconciling the analytic comm model, and the
   canonical ring schedule must drain deadlock-free in the abstract
   simulator;
9. a ``dplasma_tpu.serving`` smoke pass — tiny batched posv/gesv
   round-trips within the backward-error gate, cache-key determinism,
   and padded-vs-exact solution equivalence on CPU;
10. a ``dplasma_tpu.analysis.hlocheck`` smoke pass — the COMPILED
   post-GSPMD HLO of the cyclic potrf/getrf/geqrf/gemm kernels on
   the 2x2 CPU mesh must audit clean with the per-kind collective
   counts EXACTLY matching the jaxpr-level schedule (a
   GSPMD-inserted hidden collective fails here before it ever ships
   to hardware), and one serving batched executable must audit clean
   (donation/precision/anti-patterns);
11. a ``ring-smoke`` pass — every shipped explicit-ICI-ring kernel's
   abstract RingOp schedule (kernels.pallas_ring: panel-broadcast
   ring from every owner column, chunked and unchunked, plus the LU
   winner-row exchange) must drain in ``simulate_ring`` with zero
   deadlock/unpaired-semaphore findings, and ``ring.enable=off`` /
   ``auto`` must be bit-identical to the masked-psum cyclic kernels
   on the 2x2 CPU mesh (CPU always falls back);
12. a ``dplasma_tpu.tuning`` smoke pass — a tiny 2-config dpotrf
   sweep on the 1x1 grid must persist a winner to a fresh tuning DB,
   the DB must read back clean (``TuningDB.check``), and a
   subsequent driver ``--autotune`` run must provably consult it
   (v11 ``"tuning"`` report section: source ``db``, the winner's
   tile size applied, scoped overrides restored at close);
13. a ``telemetry-smoke`` pass — a tiny serving burst with tracing on:
   the span ledger must balance (every open has a close) and carry
   the per-request span taxonomy, the streaming exporter's file must
   parse as Prometheus text (``telemetry.parse_prometheus_text``)
   with the serving families present, and the flight-recorder dump
   must round-trip through the current-schema run-report
   (``report.load_report``) with its submit/dispatch event sequence
   intact;
14. a ``devprof-smoke`` pass — the measured-attribution engine
   (``observability.devprof``) on the 2x2 grid: every spmdcheck-
   priced collective class of potrf/getrf/geqrf must appear in the
   ingested timeline with the reconciliation relation ``==`` and the
   category seconds summing to the run exactly, an injected
   straggler must be attributed to the right rank and category, a
   timeline mutation dropping one priced class must produce a
   ``missing-collective`` diagnostic NAMING that class, and the
   entry must round-trip through the current-schema run-report;
15. a ``soak-smoke`` pass — the overload-hardening gate: a tiny
   serving burst whose conservation audit must balance (submitted
   == admitted + shed, resolved == admitted, zero lost futures), a
   forced queue-cap shed must raise ``AdmissionError`` AND land a
   ``shed`` flight event naming the request id, a forced
   rung-failure storm must open the (op, rung) circuit breaker with
   a ``breaker_open`` flight event, and the admission summary (with
   the audit) must round-trip through the schema-v15 run-report's
   ``"admission"`` section.

Usage: ``python tools/lint_all.py`` — prints ``file:line: message``
per violation / one line per failed smoke case, exits nonzero on any.
Wired into tier-1 via ``tests/test_lint.py``.
"""
from __future__ import annotations

import os
import pathlib
import sys

# the spmdcheck smoke builds 2x2/1x4 CPU meshes: force the virtual
# device count BEFORE anything imports jax (a no-op under pytest,
# where tests/conftest.py already did it)
if "jax" not in sys.modules:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT))
sys.path.insert(0, str(_ROOT / "tools"))


def run_excepts(pkg: pathlib.Path) -> int:
    import lint_excepts
    bad = lint_excepts.lint_tree(pkg)
    for path, line, msg in bad:
        sys.stderr.write(f"{path}:{line}: {msg}\n")
    return len(bad)


def run_jaxlint(pkg: pathlib.Path) -> int:
    from dplasma_tpu.analysis import jaxlint
    bad = jaxlint.lint_tree(pkg)
    for path, line, code, msg in bad:
        sys.stderr.write(f"{path}:{line}: {code} {msg}\n")
    return len(bad)


def run_perfdiff_smoke() -> int:
    """The regression gate, gated: self-compare exits 0; a doubled
    median / halved GFlop/s must exit nonzero and name the metric."""
    import contextlib
    import copy
    import io
    import json
    import tempfile

    import perfdiff

    base = {"schema": 8, "name": "perfdiff-smoke",
            "ops": [{"label": "testing_dpotrf", "prec": "d",
                     "gflops": 100.0,
                     "timings": {"nruns": 3, "median_s": 0.010,
                                 "best_s": 0.009}}],
            "metrics": []}
    worse = copy.deepcopy(base)
    worse["ops"][0]["timings"]["median_s"] = 0.020
    worse["ops"][0]["gflops"] = 45.0
    bad = 0
    with tempfile.TemporaryDirectory() as td:
        pa = f"{td}/base.json"
        pb = f"{td}/worse.json"
        for p, doc in ((pa, base), (pb, worse)):
            with open(p, "w") as f:
                json.dump(doc, f)
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc_same = perfdiff.main([pa, pa])
            rc_reg = perfdiff.main([pa, pb])
        if rc_same != 0:
            sys.stderr.write(
                f"perfdiff-smoke: self-compare exited {rc_same}\n")
            bad += 1
        if rc_reg == 0:
            sys.stderr.write(
                "perfdiff-smoke: regressed report exited 0\n")
            bad += 1
        if "testing_dpotrf.median_s" not in buf.getvalue():
            sys.stderr.write("perfdiff-smoke: regressed metric not "
                             "named in the diagnostic\n")
            bad += 1
    return bad


def run_threadcheck() -> int:
    """The concurrency gate: the lock-discipline verifier must find
    zero unsuppressed violations on the serving/telemetry surface,
    and the racefuzz schedule smoke (fixed seeds, full probe surface)
    must replay with zero invariant failures. The
    ``schedules_run``/``invariant_failures`` counters are printed so
    a report carrying them gates through perfdiff — a silently
    shrinking fuzz surface is a regression exactly like a slower
    median."""
    from dplasma_tpu.analysis import racefuzz, threadcheck

    bad = 0
    res = threadcheck.check_package()
    if not res.ok:
        sys.stderr.write(res.format("package") + "\n")
        bad += len(res.diagnostics)
    seeds = (0, 1)
    fz = racefuzz.fuzz(seeds=seeds, nthreads=3, nops=60)
    print(f"# threadcheck: racefuzz schedules_run="
          f"{fz['schedules_run']} invariant_failures="
          f"{fz['invariant_failures']}")
    for name, rs in sorted(fz["probes"].items()):
        for r in rs:
            for f in r["failures"]:
                sys.stderr.write(f"threadcheck: racefuzz[{name} "
                                 f"seed={r['seed']}]: {f}\n")
    bad += fz["invariant_failures"]
    expect = len(seeds) * len(racefuzz.PROBES)
    if fz["schedules_run"] < expect:
        sys.stderr.write(f"threadcheck: fuzz surface shrank: "
                         f"{fz['schedules_run']} schedule(s) run, "
                         f"expected {expect}\n")
        bad += 1
    return bad


def run_dagcheck_smoke() -> int:
    """Tiny-DAG verification sweep (the lint-speed subset of the
    tests/test_dagcheck.py golden fixtures)."""
    from dplasma_tpu.analysis.dagcheck import (check_comm, check_dag,
                                               rank_of_dist)
    from dplasma_tpu.descriptors import Dist, TileMatrix
    from dplasma_tpu.ops import gemm, lu, potrf, qr
    from dplasma_tpu.utils.profiling import DagRecorder

    nb, nt = 4, 3
    bad = 0
    for dist in (Dist(), Dist(P=2, Q=2)):
        N = nt * nb
        A = TileMatrix.zeros(N, N, nb, nb, dist=dist)
        cases = [
            # classic DAGs (lookahead=0): comm reconciliation exact;
            # pipelined DAGs (lookahead=1 + QR aggregation): the
            # engine's split-column structure must also verify clean
            # (comm walk skipped — fused-task granularity)
            ("potrf", lambda r: potrf.dag(A, "L", r, lookahead=0),
             "potrf", 1),
            ("lu", lambda r: lu.dag(A, r, lookahead=0), "getrf", 1),
            ("qr", lambda r: qr.dag(A, r, lookahead=0, agg_depth=1),
             "geqrf", 1),
            ("potrf_pipe", lambda r: potrf.dag(A, "L", r, lookahead=1),
             "potrf", 1),
            ("lu_pipe", lambda r: lu.dag(A, r, lookahead=1),
             "getrf", 1),
            ("qr_pipe", lambda r: qr.dag(A, r, lookahead=1,
                                         agg_depth=2), "geqrf", 1),
            # the panel engine's task structures: the TSQR tree panel
            # (panel_leaf -> panel_comb ladder -> panel root) and the
            # fused rec LU panel must verify race-free/flow-covered
            # like any flat DAG (verify-before-execute holds for the
            # reordered panel too)
            ("qr_tree", lambda r: qr.dag(A, r, lookahead=1,
                                         agg_depth=2,
                                         panel_kernel="tree"),
             "geqrf", 1),
            ("lu_rec", lambda r: lu.dag(A, r, lookahead=1,
                                        panel_kernel="rec"),
             "getrf", 1),
        ]
        for label, build, op, K in cases:
            rec = DagRecorder(enabled=True)
            build(rec)
            res = check_dag(rec, rank_of=rank_of_dist(dist))
            check_comm(rec, op, N, N, K, nb, nb, dist, res)
            if not res.ok:
                sys.stderr.write(res.format(
                    f"{label} {dist.P}x{dist.Q}") + "\n")
                bad += len(res.diagnostics)
        C = TileMatrix.zeros(N, N, nb, nb, dist=dist)
        Am = TileMatrix.zeros(N, 2 * nb, nb, nb, dist=dist)
        Bm = TileMatrix.zeros(2 * nb, N, nb, nb, dist=dist)
        rec = DagRecorder(enabled=True)
        gemm.dag(C, Am, Bm, rec)
        res = check_dag(rec, rank_of=rank_of_dist(dist))
        check_comm(rec, "gemm", N, N, 2 * nb, nb, nb, dist, res)
        if not res.ok:
            sys.stderr.write(res.format(
                f"gemm {dist.P}x{dist.Q}") + "\n")
            bad += len(res.diagnostics)
        # the IR solvers' factor+solve+refine DAG (ops.refine.dag):
        # verify-before-execute holds for the new solve workload too
        from dplasma_tpu.ops import refine
        for kind, op in (("posv", "posv_ir"), ("gesv", "gesv_ir")):
            rec = DagRecorder(enabled=True)
            refine.dag(A, kind, rec, iterations=2)
            res = check_dag(rec, rank_of=rank_of_dist(dist))
            check_comm(rec, op, N, N, 1, nb, nb, dist, res)
            if not res.ok:
                sys.stderr.write(res.format(
                    f"{op} {dist.P}x{dist.Q}") + "\n")
                bad += len(res.diagnostics)
    return bad


def run_memcheck_smoke() -> int:
    """Tile-liveness/residency sweep over the four ops' DAGs (the
    lint-speed subset of the tests/test_memcheck.py fixtures), plus
    the budget-gate mutation: a shrunken budget must name the peak
    task and tile and attach a feasible stream plan."""
    from dplasma_tpu.analysis import memcheck as mc
    from dplasma_tpu.descriptors import Dist, TileMatrix
    from dplasma_tpu.ops import gemm, lu, potrf, qr
    from dplasma_tpu.utils.profiling import DagRecorder

    nb, nt = 4, 3
    N = nt * nb
    bad = 0
    for dist in (Dist(), Dist(P=2, Q=2)):
        A = TileMatrix.zeros(N, N, nb, nb, dist=dist)
        C = TileMatrix.zeros(N, N, nb, nb, dist=dist)
        cases = [
            ("potrf", lambda r: potrf.dag(A, "L", r, lookahead=0), 0),
            ("getrf", lambda r: lu.dag(A, r, lookahead=0), 0),
            ("geqrf", lambda r: qr.dag(A, r, lookahead=0,
                                       agg_depth=1), 0),
            ("gemm", lambda r: gemm.dag(C, A, A, r), 0),
            # pipelined orderings: the lookahead window reshapes the
            # live set, the analyzer must still close the intervals
            ("potrf_pipe", lambda r: potrf.dag(A, "L", r,
                                               lookahead=1), 1),
            ("getrf_pipe", lambda r: lu.dag(A, r, lookahead=1), 1),
        ]
        for label, build, la in cases:
            rec = DagRecorder(enabled=True)
            build(rec)
            res = mc.check_schedule(rec, mb=nb, nb=nb, itemsize=4,
                                    dist=dist, lookahead=la,
                                    kernel=label)
            if not res.ok or res.resident_peak_bytes <= 0 or \
                    not res.peak_task:
                sys.stderr.write(res.format(
                    f"{label} {dist.P}x{dist.Q}") + "\n")
                bad += 1
    # budget-violation mutation: the gate must fire with the peak
    # task/tile named and a stream plan attached
    A = TileMatrix.zeros(N, N, nb, nb, dist=Dist())
    rec = DagRecorder(enabled=True)
    potrf.dag(A, "L", rec, lookahead=0)
    res = mc.check_schedule(rec, mb=nb, nb=nb, itemsize=4,
                            kernel="potrf", budget=nb * nb * 4)
    hits = [d for d in res.diagnostics if d.kind == "hbm-budget"]
    if res.ok or not hits or not hits[0].task or not hits[0].tile \
            or not isinstance(res.stream, dict) \
            or "feasible" not in res.stream:
        sys.stderr.write("# memcheck-smoke: budget mutation did not "
                         "produce a named hbm-budget diagnostic with "
                         "a stream plan\n")
        bad += 1
    return bad


def run_palcheck() -> int:
    """Every pallas_call contract in the package must verify clean
    (analysis.palcheck: capture + block/index/VMEM/precision checks;
    degrades to the AST site sweep where pallas cannot import)."""
    from dplasma_tpu.analysis import palcheck
    res = palcheck.check_package()
    for d in res.diagnostics:
        sys.stderr.write(f"palcheck[{d.site}]: {d.kind}: "
                         f"{d.message}\n")
    return len(res.diagnostics)


def run_spmdcheck_smoke() -> int:
    """The cyclic shard_map kernels must verify clean with EXACT
    collective-count reconciliation against the analytic comm model,
    over 1x1 / 2x2 / 1x4 grids at tiny shapes (nothing executes —
    jaxpr tracing only); plus the abstract ring simulator's golden:
    the canonical neighbor-shift schedule drains deadlock-free."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from dplasma_tpu.analysis import spmdcheck as sp
    from dplasma_tpu.descriptors import Dist
    from dplasma_tpu.parallel import cyclic
    from dplasma_tpu.parallel import mesh as pmesh

    nb, nt = 4, 4
    bad = 0
    ndev = len(jax.devices())
    for P, Q in ((1, 1), (2, 2), (1, 4)):
        if P * Q > ndev:
            print(f"# spmdcheck-smoke: {P}x{Q} skipped "
                  f"({ndev} device(s) available)")
            continue
        m = pmesh.make_mesh(P, Q)
        d = Dist(P=P, Q=Q)
        desc = cyclic.CyclicDesc(nt * nb, nt * nb, nb, nb, d)
        data = jnp.zeros((P, Q, desc.MTL * nb, desc.NTL * nb),
                         jnp.float32)
        KT = min(desc.MT, desc.NT)
        la = 1
        cases = [
            ("potrf", partial(cyclic._potrf_cyclic_jit, desc=desc,
                              mesh=m, lookahead=la), (data,), KT, la),
            ("getrf", partial(cyclic._getrf_cyclic_jit, desc=desc,
                              mesh=m, lookahead=la), (data,), KT, la),
            ("geqrf", partial(cyclic._geqrf_cyclic_jit, desc=desc,
                              mesh=m, lookahead=la), (data,), KT, la),
            ("gemm", partial(cyclic._gemm_cyclic_jit, adesc=desc,
                             bdesc=desc, mesh=m), (data, data),
             desc.NT, 0),
        ]
        for op, fn, args, kt, la_ in cases:
            res = sp.check_kernel(fn, args, f"{op}_{P}x{Q}", op=op,
                                  KT=kt, lookahead=la_)
            if not res.ok or res.relation != "==":
                sys.stderr.write(res.format(f"{op} {P}x{Q}") + "\n")
                bad += max(len(res.diagnostics), 1)
    ring = sp.check_ring("ring-shift-4",
                         sp.ring_shift_program(4, steps=3))
    if not ring.ok:
        sys.stderr.write(ring.format() + "\n")
        bad += len(ring.diagnostics)
    return bad


def run_serving_smoke() -> int:
    """The serving layer's correctness floor, CPU-fast: a tiny batched
    posv/gesv round-trip (backward error within the check_solve gate),
    cache-key determinism (the scheduler groups by the key — a drifty
    key silently unbatches everything), and padded-vs-exact
    equivalence (bucket padding must not perturb the solution)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dplasma_tpu.serving import batched
    from dplasma_tpu.serving import cache as scache

    @functools.partial(jax.jit, static_argnums=(0, 3))
    def _solve(op, a, b, nb):
        x, _ = batched.solve_batched(op, a, b, nb)
        return x, batched.backward_errors(a, b, x)

    bad = 0
    rng = np.random.default_rng(3872)
    n, nb, nrhs = 6, 4, 2
    g = rng.standard_normal((2, n, n)).astype(np.float32)
    spd = g @ g.transpose(0, 2, 1) + n * np.eye(n, dtype=np.float32)
    ge = g + n * np.eye(n, dtype=np.float32)
    b = rng.standard_normal((2, n, nrhs)).astype(np.float32)
    gate = 60.0 * np.finfo(np.float32).eps * n
    for op, a in (("posv", spd), ("gesv", ge)):
        x, bwd = _solve(op, jnp.asarray(a), jnp.asarray(b), nb)
        bwd = np.asarray(bwd)
        if not np.all(np.isfinite(np.asarray(x))) or np.any(bwd > gate):
            sys.stderr.write(f"serving-smoke: batched {op} round-trip "
                             f"failed the backward-error gate "
                             f"({bwd})\n")
            bad += 1
        # padded-vs-exact: identity/zero bucket padding must not
        # perturb the solution
        nB = scache.bucket_dim(n)
        rB = scache.bucket_dim(nrhs, floor=scache.MIN_NRHS_BUCKET)
        ap = np.asarray(scache.pad_problem(jnp.asarray(a), nB))
        bp = np.asarray(scache.pad_rhs(jnp.asarray(b), nB, rB))
        xp, _ = _solve(op, jnp.asarray(ap), jnp.asarray(bp), nb)
        diff = np.max(np.abs(np.asarray(xp)[:, :n, :nrhs]
                             - np.asarray(x)))
        scale = max(float(np.max(np.abs(np.asarray(x)))), 1.0)
        if diff > 100.0 * np.finfo(np.float32).eps * n * scale:
            sys.stderr.write(f"serving-smoke: padded {op} deviates "
                             f"from the exact-shape solve by "
                             f"{diff}\n")
            bad += 1
    k1 = scache.make_key("posv", n, np.float32, 2, nrhs)
    k2 = scache.make_key("posv", n, np.float32, 2, nrhs)
    if k1 != k2 or hash(k1) != hash(k2):
        sys.stderr.write("serving-smoke: cache key not "
                         "deterministic\n")
        bad += 1
    if (k1.n != scache.bucket_dim(n)
            or k1.batch != scache.bucket_batch(2)
            or scache.make_key("posv", n + 1, np.float32, 2,
                               nrhs) != k1._replace(
                                   n=scache.bucket_dim(n + 1))):
        sys.stderr.write("serving-smoke: cache key bucketing "
                         "drifted from the bucket functions\n")
        bad += 1
    return bad


def run_hlocheck_smoke() -> int:
    """The compiled-artifact gate: the cyclic kernels' post-GSPMD HLO
    on the 2x2 CPU mesh must carry EXACTLY the collective schedule
    the jaxpr traced (GSPMD neither inserted nor dropped), pass the
    precision/donation/HBM/anti-pattern audits, and one serving
    batched executable must audit clean. Compiles are tiny and ride
    the persistent compilation cache."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from dplasma_tpu.analysis import hlocheck as hc
    from dplasma_tpu.analysis import spmdcheck as sp
    from dplasma_tpu.descriptors import Dist
    from dplasma_tpu.parallel import cyclic
    from dplasma_tpu.parallel import mesh as pmesh

    nb, nt = 4, 4
    bad = 0
    P, Q = 2, 2
    if P * Q > len(jax.devices()):
        print(f"# hlocheck-smoke: {P}x{Q} skipped "
              f"({len(jax.devices())} device(s) available)")
        return 0
    m = pmesh.make_mesh(P, Q)
    d = Dist(P=P, Q=Q)
    desc = cyclic.CyclicDesc(nt * nb, nt * nb, nb, nb, d)
    data = jnp.zeros((P, Q, desc.MTL * nb, desc.NTL * nb),
                     jnp.float32)
    KT = min(desc.MT, desc.NT)
    la = 1
    cases = [
        ("potrf", partial(cyclic._potrf_cyclic_jit, desc=desc,
                          mesh=m, lookahead=la), (data,), KT, la),
        ("getrf", partial(cyclic._getrf_cyclic_jit, desc=desc,
                          mesh=m, lookahead=la), (data,), KT, la),
        ("geqrf", partial(cyclic._geqrf_cyclic_jit, desc=desc,
                          mesh=m, lookahead=la), (data,), KT, la),
        ("gemm", partial(cyclic._gemm_cyclic_jit, adesc=desc,
                         bdesc=desc, mesh=m), (data, data),
         desc.NT, 0),
    ]
    for op, fn, args, kt, la_ in cases:
        lowered = jax.jit(fn).lower(*args)
        compiled = lowered.compile()
        schedule = sp.extract_schedule(fn, *args, kernel=op)
        res = hc.check_executable(lowered, compiled,
                                  f"{op}_{P}x{Q}",
                                  schedule=schedule, exact=True,
                                  op=op, KT=kt, lookahead=la_,
                                  prec="s")
        if not res.ok or res.relation != "==":
            sys.stderr.write(res.format(f"{op} {P}x{Q}") + "\n")
            bad += max(len(res.diagnostics), 1)
    # one serving batched executable: the long-lived cache must only
    # admit artifacts that audit clean
    import numpy as np

    from dplasma_tpu.serving import batched

    rng = np.random.default_rng(3872)
    n, nrhs = 6, 2
    g = rng.standard_normal((2, n, n)).astype(np.float32)
    spd = g @ g.transpose(0, 2, 1) + n * np.eye(n, dtype=np.float32)
    b = rng.standard_normal((2, n, nrhs)).astype(np.float32)

    def _posv(a, bb):
        x, _ = batched.solve_batched("posv", a, bb, 4)
        return x
    lowered = jax.jit(_posv).lower(jnp.asarray(spd), jnp.asarray(b))
    compiled = lowered.compile()
    res = hc.check_executable(lowered, compiled, "serving:posv",
                              prec="s")
    if not res.ok:
        sys.stderr.write(res.format("serving:posv") + "\n")
        bad += len(res.diagnostics)
    return bad


def run_ring_smoke() -> int:
    """The explicit-ICI-ring gate: (a) every shipped ring kernel's
    abstract RingOp schedule (kernels.pallas_ring: the panel-broadcast
    ring from every owner column, chunked and unchunked, and the LU
    winner-row exchange) must drain in the spmdcheck simulator with
    zero deadlock/unpaired-semaphore findings, over the grids the
    cyclic kernels run; (b) ``ring.enable=off`` must be bit-identical
    to the psum path on the 2x2 CPU mesh (and both ``off`` and
    ``auto`` must resolve to the psum kernels on CPU — the
    CPU-always-falls-back contract)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dplasma_tpu.analysis import spmdcheck as sp
    from dplasma_tpu.descriptors import Dist
    from dplasma_tpu.kernels import pallas_ring as pring
    from dplasma_tpu.ops import generators
    from dplasma_tpu.parallel import cyclic
    from dplasma_tpu.parallel import mesh as pmesh
    from dplasma_tpu.utils import config as _cfg

    bad = 0
    for P, Q in ((2, 2), (1, 4), (2, 4), (4, 2)):
        for name, prog in pring.kernel_programs(P, Q).items():
            diags = sp.simulate_ring(f"{name}@{P}x{Q}", prog)
            for d in diags:
                sys.stderr.write(f"ring-smoke: {d.kind}: "
                                 f"{d.message}\n")
            bad += len(diags)
    # (b) off = bit-identical psum path on the 2x2 CPU mesh
    P, Q = 2, 2
    if P * Q > len(jax.devices()):
        print(f"# ring-smoke: {P}x{Q} identity leg skipped "
              f"({len(jax.devices())} device(s) available)")
        return bad
    nb, nt = 4, 3
    m = pmesh.make_mesh(P, Q)
    d = Dist(P=P, Q=Q)
    with pmesh.use_grid(m):
        A0 = generators.plghe(float(nt * nb), nt * nb, nb, seed=3872,
                              dtype="float32")
        C = cyclic.CyclicMatrix.from_tile(A0, d)
        for mode in ("off", "auto"):
            with _cfg.override_scope({"ring.enable": mode},
                                     label="ring-smoke"):
                if cyclic._cyclic_ring(C.desc, C.dtype, m,
                                       need_row=True):
                    sys.stderr.write(
                        f"ring-smoke: ring.enable={mode} resolved to "
                        f"the ring path on a CPU backend (must fall "
                        f"back)\n")
                    bad += 1
                via_mca = cyclic.potrf_cyclic(C, "L").data
            direct = cyclic._potrf_cyclic_jit(
                C.data, C.desc, m, cyclic._cyclic_lookahead(), False)
            if not np.array_equal(np.asarray(via_mca),
                                  np.asarray(direct)):
                sys.stderr.write(
                    f"ring-smoke: ring.enable={mode} output is not "
                    f"bit-identical to the psum path on the "
                    f"{P}x{Q} CPU mesh\n")
                bad += 1
    return bad


def run_tune_smoke() -> int:
    """The autotuner's closed loop, CPU-fast: a tiny 2-config dpotrf
    sweep persists a winner into a fresh DB, the DB reads back clean
    against the current schema, and a driver ``--autotune`` run
    consults it — the v11 report section names source ``db``, the
    winner's tile size lands in the parameter block, and the scoped
    MCA overrides are fully restored after close."""
    import json as _json
    import tempfile

    import jax

    from dplasma_tpu.tuning import TuningDB, make_key, search
    from dplasma_tpu.utils import config as _cfg

    bad = 0
    with tempfile.TemporaryDirectory() as td:
        dbp = f"{td}/tune_db.json"
        search.sweep(["potrf"], [32], dtype="float32", grid=(1, 1),
                     db_file=dbp, nbs=[8, 16], lookaheads=[1],
                     prune=False, nruns=2, log=lambda s: None)
        try:
            db = TuningDB.load(dbp)
        except (OSError, ValueError) as exc:
            sys.stderr.write(f"tune-smoke: DB unreadable: {exc}\n")
            return 1
        key = make_key("potrf", 32, "float32", (1, 1))
        entry = db.entries.get(key)
        if entry is None:
            sys.stderr.write(f"tune-smoke: no winner stored for "
                             f"{key}\n")
            return 1
        problems = db.check()
        if problems:
            sys.stderr.write("tune-smoke: DB check: "
                             + "; ".join(problems) + "\n")
            bad += len(problems)
        # the winner must steer a driver run (env tier of tune.db)
        from dplasma_tpu.drivers import main as drv_main
        rj = f"{td}/r.json"
        before = dict(_cfg._MCA_OVERRIDES)
        prev_db = os.environ.get("DPLASMA_TUNE_DB")
        os.environ["DPLASMA_TUNE_DB"] = dbp
        try:
            rc = drv_main(["-N", "32", "--autotune",
                           f"--report={rj}"],
                          prog="testing_spotrf")
        finally:
            # restore, don't pop: the gate may run in-process (pytest)
            # where a user's own DB pin must survive it
            if prev_db is None:
                os.environ.pop("DPLASMA_TUNE_DB", None)
            else:
                os.environ["DPLASMA_TUNE_DB"] = prev_db
        if rc != 0:
            sys.stderr.write(f"tune-smoke: --autotune driver run "
                             f"exited {rc}\n")
            return bad + 1
        if _cfg._MCA_OVERRIDES != before:
            sys.stderr.write("tune-smoke: driver leaked MCA "
                             "overrides after close\n")
            bad += 1
        with open(rj) as f:
            doc = _json.load(f)
        tune = (doc.get("tuning") or [{}])[0]
        if tune.get("source") != "db" or tune.get("key") != key:
            sys.stderr.write(f"tune-smoke: report tuning section "
                             f"did not consult the DB: {tune}\n")
            bad += 1
        nb = (entry.get("knobs") or {}).get("nb")
        if nb and (doc.get("iparam") or {}).get("NB") != nb:
            sys.stderr.write("tune-smoke: winner tile size "
                             f"nb={nb} not applied "
                             f"(NB={(doc.get('iparam') or {}).get('NB')})\n")
            bad += 1
    return bad


def run_quant_smoke() -> int:
    """The block-scaled int8 gate, CPU-fast: a quantize/dequantize
    round-trip must stay within the per-tile half-step bound, the
    block-scaled GEMM must track the f32 reference, the int8 IR rung
    must converge to the f64-equivalent backward-error gate on a
    well-conditioned seed, and the precision-autopilot DB must
    round-trip a stored rung plus an escalation write-back with a
    clean schema check."""
    import tempfile

    import jax
    import numpy as np

    from dplasma_tpu.ops.generators import plghe, plrnt
    from dplasma_tpu.kernels import quant
    from dplasma_tpu.ops import refine
    from dplasma_tpu.tuning import TuningDB
    from dplasma_tpu.tuning import autopilot as _ap

    jax.config.update("jax_enable_x64", True)
    bad = 0
    rng = np.random.default_rng(3872)
    tile = 32
    # (a) quantize/dequantize round-trip: symmetric per-tile scales —
    # every element lands within half a quantization step of its tile
    x = (rng.standard_normal((96, 64)).astype(np.float32)
         * rng.choice([1e-3, 1.0, 1e3], size=(96, 64))
         .astype(np.float32))
    q, sc = quant.quantize(x, tile)
    y = np.asarray(quant.dequantize(q, sc, tile, x.shape))
    err = np.abs(y - x)
    step = np.repeat(np.repeat(np.asarray(sc), tile, 0), tile, 1)
    if not np.all(err <= 0.5 * step[:96, :64] * (1 + 1e-6)):
        sys.stderr.write("quant-smoke: round-trip exceeds the "
                         "half-step bound\n")
        bad += 1
    # (b) block-scaled GEMM vs the f32 reference
    a = rng.standard_normal((64, 48)).astype(np.float32)
    b = rng.standard_normal((48, 80)).astype(np.float32)
    ref = a @ b
    got = np.asarray(quant.qgemm(a, b, tile))
    rel = np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30)
    if rel > 5e-2:
        sys.stderr.write(f"quant-smoke: qgemm relative error {rel:.3e}"
                         " exceeds 5e-2\n")
        bad += 1
    # (c) int8 IR rung: posv/gesv on well-conditioned seeds must hit
    # the f64-equivalent backward-error gate without escalating
    n, nb = 96, 32
    A0 = plghe(float(n), n, nb, seed=3872, dtype=np.float64)
    B0 = plrnt(n, 2, nb, nb, seed=3873, dtype=np.float64)
    for op, solve in (("posv_ir",
                       lambda: refine.posv_ir(A0, B0, "L",
                                              precision="int8")),
                      ("gesv_ir",
                       lambda: refine.gesv_ir(
                           plrnt(n, n, nb, nb, seed=3874,
                                 dtype=np.float64, diagdom=True), B0,
                           precision="int8"))):
        _, info = solve()
        summ = refine.summarize(info, op=op)
        if not summ["converged"] or summ["escalated"] \
                or summ["backward_errors"][-1] > summ["tol"]:
            sys.stderr.write(f"quant-smoke: int8-rung {op} missed the "
                             f"backward-error gate: {summ}\n")
            bad += 1
    # (d) autopilot DB round-trip + escalation write-back
    with tempfile.TemporaryDirectory() as td:
        dbp = f"{td}/tune_db.json"
        _ap.record("posv_ir", n, "float64", "well", "int8",
                   converged=True, cond_estimate=10.0, path=dbp)
        dec = _ap.consult("posv_ir", n, "float64",
                          cond=10.0, path=dbp)
        if dec is None or dec["precision"] != "int8" \
                or dec["source"] != "db":
            sys.stderr.write(f"quant-smoke: autopilot consult did not "
                             f"return the stored rung: {dec}\n")
            bad += 1
        _ap.record_escalation("posv_ir", n, "float64", "well", "int8",
                              cond_estimate=10.0, path=dbp)
        dec2 = _ap.consult("posv_ir", n, "float64", cond=10.0,
                           path=dbp)
        if dec2 is None or dec2["precision"] != "bf16":
            sys.stderr.write(f"quant-smoke: escalation write-back did "
                             f"not bump the rung: {dec2}\n")
            bad += 1
        problems = TuningDB.load(dbp).check()
        if problems:
            sys.stderr.write("quant-smoke: DB check: "
                             + "; ".join(problems) + "\n")
            bad += len(problems)
    return bad


def run_telemetry_smoke() -> int:
    """The live-telemetry gate, CPU-fast: a tiny serving burst with
    tracing ON must leave a balanced span ledger carrying the
    per-request taxonomy, the exporter snapshot must parse as
    Prometheus text with the serving families present, and the flight
    recorder's ring must round-trip through the schema-v13 run-report
    with its submit -> dispatch sequence intact."""
    import json as _json
    import tempfile

    import jax
    import numpy as np

    from dplasma_tpu.observability import telemetry as tel
    from dplasma_tpu.observability.report import (REPORT_SCHEMA,
                                                  RunReport,
                                                  load_report)
    from dplasma_tpu.serving import SolverService

    bad = 0
    rng = np.random.default_rng(3872)
    n, nrhs = 6, 2
    svc = SolverService(nb=4, max_batch=4, max_wait_ms=0)
    if not svc.telemetry.tracer.enabled:
        sys.stderr.write("telemetry-smoke: tracing is not on by "
                         "default\n")
        bad += 1
    for _ in range(2):      # two bursts: miss then hit on the cache
        futs = []
        for _i in range(3):
            g = rng.standard_normal((n, n)).astype(np.float32)
            a = g @ g.T + n * np.eye(n, dtype=np.float32)
            b = rng.standard_normal((n, nrhs)).astype(np.float32)
            futs.append(svc.submit("posv", a, b))
        svc.flush()
        for f in futs:
            f.result(120.0)
    # (a) span ledger: balanced, and the request taxonomy present
    tr = svc.telemetry.tracer
    if not tr.balanced():
        sys.stderr.write("telemetry-smoke: span ledger unbalanced "
                         f"({tr.summary()})\n")
        bad += 1
    names = {s["name"] for s in tr.spans()}
    for want in ("queue_wait", "batch", "batch_form", "cache",
                 "dispatch", "scatter_gate"):
        if want not in names:
            sys.stderr.write(f"telemetry-smoke: span {want!r} missing "
                             f"from the taxonomy ({sorted(names)})\n")
            bad += 1
    if not all(f.request_id > 0 for f in futs):
        sys.stderr.write("telemetry-smoke: futures lack stamped "
                         "request ids\n")
        bad += 1
    with tempfile.TemporaryDirectory() as td:
        # (b) exporter file parses as Prometheus text
        ex = tel.MetricsExporter(svc.metrics, f"{td}/t.prom",
                                 interval_s=60.0)
        ex.flush()
        try:
            fams = tel.parse_prometheus_text(
                open(f"{td}/t.prom").read())
        except (OSError, ValueError) as exc:
            sys.stderr.write(f"telemetry-smoke: exporter file does "
                             f"not parse: {exc}\n")
            return bad + 1
        for fam in ("serving_requests_total", "serving_latency_s",
                    "serving_queue_depth", "serving_cache_entries"):
            if fam not in fams or not fams[fam]["samples"]:
                sys.stderr.write(f"telemetry-smoke: family {fam!r} "
                                 f"missing from the exporter "
                                 f"snapshot\n")
                bad += 1
        # (c) flight-recorder dump round-trips through load_report
        rep = RunReport("telemetry-smoke")
        rep.add_telemetry(svc.telemetry.summary())
        rj = f"{td}/r.json"
        rep.write(rj)
        try:
            doc = load_report(rj)
        except (OSError, ValueError) as exc:
            sys.stderr.write(f"telemetry-smoke: report round-trip "
                             f"failed: {exc}\n")
            return bad + 1
        t = doc.get("telemetry") or {}
        evs = (t.get("flight_recorder") or {}).get("events") or []
        kinds = [e.get("kind") for e in evs]
        if doc.get("schema") != REPORT_SCHEMA or "submit" not in kinds \
                or "dispatch" not in kinds:
            sys.stderr.write(f"telemetry-smoke: flight recorder did "
                             f"not round-trip (schema="
                             f"{doc.get('schema')}, kinds={kinds})\n")
            bad += 1
        if _json.loads(_json.dumps(t)) != t:
            sys.stderr.write("telemetry-smoke: telemetry section is "
                             "not JSON-stable\n")
            bad += 1
    return bad


def run_soak_smoke() -> int:
    """The overload-hardening gate, CPU-fast: the conservation audit
    over a tiny burst must balance (submitted == admitted + shed,
    resolved == admitted, zero lost futures), a forced queue-cap shed
    must raise ``AdmissionError`` and land a ``shed`` flight event
    naming the request id, a forced rung-failure storm must open the
    (op, rung) breaker with a ``breaker_open`` flight event, and the
    admission summary + audit must round-trip through the schema-v15
    run-report."""
    import tempfile

    import jax
    import numpy as np

    from dplasma_tpu.observability.report import (REPORT_SCHEMA,
                                                  RunReport,
                                                  load_report)
    from dplasma_tpu.resilience import inject
    from dplasma_tpu.serving import AdmissionError, SolverService

    bad = 0
    rng = np.random.default_rng(3872)
    n, nrhs = 6, 2

    def operands():
        g = rng.standard_normal((n, n)).astype(np.float32)
        a = g @ g.T + n * np.eye(n, dtype=np.float32)
        b = rng.standard_normal((n, nrhs)).astype(np.float32)
        return a, b

    svc = SolverService(nb=4, max_batch=4, max_wait_ms=0)
    ctrl = svc.admission

    def counters():
        return {k: svc.metrics.counter(k).value
                for k in ("serving_admitted_total",
                          "serving_shed_total",
                          "serving_resolved_total")}

    before = counters()
    submitted = shed_seen = 0
    # (a) clean burst: everything admits and resolves
    futs = []
    for _ in range(3):
        a, b = operands()
        submitted += 1
        futs.append(svc.submit("posv", a, b))
    svc.flush()
    for f in futs:
        f.result(120.0)
    # (b) forced shed: queue cap 1, two submits without a flush — the
    # second MUST shed with the structured error and a flight event
    # naming its request id
    ctrl.max_queue, saved_q = 1, ctrl.max_queue
    try:
        a, b = operands()
        submitted += 1
        f1 = svc.submit("posv", a, b)
        a, b = operands()
        submitted += 1
        try:
            svc.submit("posv", a, b)
        except AdmissionError as exc:
            shed_seen += 1
            ev = [e for e in svc.telemetry.flight.events()
                  if e["kind"] == "shed"
                  and e.get("request") == exc.request_id]
            if exc.request_id is None or not ev:
                sys.stderr.write(
                    f"soak-smoke: shed flight event does not name "
                    f"the shed request (id={exc.request_id})\n")
                bad += 1
        else:
            sys.stderr.write("soak-smoke: queue cap 1 did not shed "
                             "the second queued submit\n")
            bad += 1
    finally:
        ctrl.max_queue = saved_q
    svc.flush()
    f1.result(120.0)
    # (c) forced breaker-open: every remediation rung raises, one
    # rung failure trips the breaker (threshold 1) — the (op, rung)
    # breaker must open with a flight event, and the failed future
    # still RESOLVES (conservation holds under the storm)
    ctrl.breaker_failures = 1

    def _raise(_r):
        raise RuntimeError("soak-smoke: poisoned rung")

    svc._solo = _raise
    svc._escalate = _raise
    inject.arm(inject.parse_plan("nan@serving:1:1", 3872))
    try:
        a, b = operands()
        submitted += 1
        fb = svc.submit("posv", a, b)
        svc.flush()
        try:
            fb.result(120.0)
        except Exception:
            pass
        else:
            sys.stderr.write("soak-smoke: poisoned-rung request did "
                             "not fail\n")
            bad += 1
    finally:
        inject.disarm()
    states = [v["state"]
              for k, v in ctrl.summary()["breakers"].items()
              if k.startswith("posv:")]
    if "open" not in states and "half_open" not in states:
        sys.stderr.write(f"soak-smoke: breaker did not open after "
                         f"the rung failure (states={states})\n")
        bad += 1
    if not any(e["kind"] == "breaker_open"
               for e in svc.telemetry.flight.events()):
        sys.stderr.write("soak-smoke: no breaker_open flight event "
                         "recorded\n")
        bad += 1
    # (d) conservation audit over everything above
    diff = {k: int(v - before[k]) for k, v in counters().items()}
    admitted = diff["serving_admitted_total"]
    shed = diff["serving_shed_total"]
    resolved = diff["serving_resolved_total"]
    audit = {"submitted": submitted, "admitted": admitted,
             "shed": shed, "resolved": resolved,
             "lost": admitted - resolved,
             "flight_shed_seen": svc.telemetry.flight.counts()
             .get("shed", 0),
             "flight_dropped": svc.telemetry.flight.summary()
             ["dropped"]}
    audit["balanced"] = (submitted == admitted + shed
                         and shed == shed_seen
                         and audit["lost"] == 0
                         and audit["flight_shed_seen"]
                         + audit["flight_dropped"] >= shed)
    if not audit["balanced"]:
        sys.stderr.write(f"soak-smoke: conservation audit does not "
                         f"balance: {audit}\n")
        bad += 1
    # (e) the admission summary + audit round-trips through the
    # schema-v15 run-report
    with tempfile.TemporaryDirectory() as td:
        rep = RunReport("soak-smoke")
        adm = ctrl.summary()
        adm["audit"] = audit
        rep.add_admission(adm)
        rj = f"{td}/r.json"
        rep.write(rj)
        try:
            doc = load_report(rj)
        except (OSError, ValueError) as exc:
            sys.stderr.write(f"soak-smoke: report round-trip "
                             f"failed: {exc}\n")
            return bad + 1
        got = doc.get("admission")
        if doc.get("schema") != REPORT_SCHEMA \
                or not isinstance(got, dict) \
                or got.get("audit", {}).get("balanced") is not True:
            sys.stderr.write(f"soak-smoke: admission section did not "
                             f"round-trip (schema="
                             f"{doc.get('schema')}, got={got})\n")
            bad += 1
    svc.close()
    return bad


def run_devprof_smoke() -> int:
    """The measured-attribution gate, CPU-fast and jax-free: devprof's
    synthetic 2x2 timelines for the priced op classes must reconcile
    ``==`` against the spmdcheck schedule with category seconds
    summing to the run, a straggler injection must be attributed to
    the injected rank + category, a dropped priced class must be a
    NAMED missing-collective diagnostic, and the entry must
    round-trip through the schema-v14 run-report."""
    import tempfile

    from dplasma_tpu.observability import devprof as dp
    from dplasma_tpu.observability.report import (REPORT_SCHEMA,
                                                  RunReport,
                                                  load_report)

    bad = 0
    run_s, grid, n, nb = 0.01, (2, 2), 64, 16
    entries = {}
    for op in ("potrf", "getrf", "geqrf"):
        e = dp.attribute(f"smoke_{op}", op, run_s, grid, n, n, nb)
        entries[op] = e
        if e["reconciliation"]["relation"] != "==" or not e["ok"]:
            sys.stderr.write(
                f"devprof-smoke: {op} does not reconcile "
                f"(relation={e['reconciliation']['relation']}, "
                f"diagnostics={e['diagnostics']})\n")
            bad += 1
        total = sum(e["categories"].values())
        if abs(total - run_s) > 1e-6 * max(run_s, 1.0):
            sys.stderr.write(f"devprof-smoke: {op} category seconds "
                             f"{total} != run {run_s}\n")
            bad += 1
        missing = [c for c in (e["reconciliation"]["expected"] or {})
                   if c not in {r["cls"] for r in e["collectives"]}]
        if missing:
            sys.stderr.write(f"devprof-smoke: {op} priced class(es) "
                             f"{missing} absent from the ingested "
                             f"timeline\n")
            bad += 1
    # straggler injection: rank 2's collectives x8 must be attributed
    # to rank 2 with a collective-side dominating category
    base = entries["potrf"]
    tl = dp.synthesize_timeline(
        run_s, 4, counts=base["reconciliation"]["expected"],
        bytes_by_class={c["cls"]: c["model_bytes"]
                        for c in base["collectives"]
                        if c["model_bytes"] is not None})
    skewed = dp.ingest(dp.stretch_rank(tl, 2, 8.0), run_s, 4,
                       expected=base["reconciliation"]["expected"],
                       op="potrf", label="smoke_straggler")
    if skewed["skew"]["slowest_rank"] != 2 \
            or skewed["skew"]["dominating_category"] not in (
                "collective", "ici") \
            or skewed["skew"]["value"] <= 0:
        sys.stderr.write(
            f"devprof-smoke: straggler attribution wrong "
            f"(skew={skewed['skew']})\n")
        bad += 1
    # mutation: drop one priced class -> a NAMED diagnostic + not ok
    drop = sorted(base["reconciliation"]["expected"])[0]
    mutated = dp.ingest([s for s in tl if s.get("cls") != drop],
                        run_s, 4,
                        expected=base["reconciliation"]["expected"],
                        op="potrf", label="smoke_mutation")
    named = [d for d in mutated["diagnostics"]
             if d["kind"] == "missing-collective" and d["op"] == drop]
    if mutated["ok"] or mutated["reconciliation"]["relation"] == "==" \
            or not named:
        sys.stderr.write(
            f"devprof-smoke: dropped class {drop} not diagnosed "
            f"(diagnostics={mutated['diagnostics']})\n")
        bad += 1
    # run-report round-trip at the current schema
    with tempfile.TemporaryDirectory() as td:
        rep = RunReport("devprof-smoke")
        rep.add_devprof(entries["potrf"])
        rj = f"{td}/r.json"
        rep.write(rj)
        try:
            doc = load_report(rj)
        except (OSError, ValueError) as exc:
            sys.stderr.write(f"devprof-smoke: report round-trip "
                             f"failed: {exc}\n")
            return bad + 1
        got = doc.get("devprof") or []
        if doc.get("schema") != REPORT_SCHEMA or len(got) != 1 \
                or got[0] != entries["potrf"]:
            sys.stderr.write(f"devprof-smoke: devprof section did "
                             f"not round-trip (schema="
                             f"{doc.get('schema')})\n")
            bad += 1
    return bad


def run_trend_smoke() -> int:
    """The perf-observatory invariants that must hold on EVERY
    commit: the trend model ingests the fixture ledger and every
    fixture artifact under tests/data without error; the changepoint
    detector finds a clean synthetic step at exactly its index (and
    nothing else); perfboard renders the dashboard and its
    ``--check`` gate is green on the fixture ledger."""
    import importlib.util
    import tempfile

    def _load(name, rel):
        mod = sys.modules.get(name)
        if mod is not None:
            return mod
        spec = importlib.util.spec_from_file_location(
            name, _ROOT / rel)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        return mod

    trend = _load("_lint_trend", "dplasma_tpu/observability/trend.py")
    perfboard = _load("_lint_perfboard", "tools/perfboard.py")
    bad = 0
    # 1) every fixture artifact loads (or is skipped with a note)
    data = _ROOT / "tests" / "data"
    for path in sorted(data.glob("*.json")):
        try:
            docs, notes = trend.load_artifact(path)
        except (OSError, ValueError) as exc:
            sys.stderr.write(f"trend-smoke: {path.name}: {exc}\n")
            bad += 1
            continue
        if not docs and not notes:
            sys.stderr.write(f"trend-smoke: {path.name}: neither "
                             f"docs nor a skip note\n")
            bad += 1
    # 2) the fixture ledger ingests; fragments are named, never fatal
    ledger = data / "ledger.jsonl"
    if ledger.exists():
        try:
            series, notes = trend.ingest_ledger(ledger)
        except (OSError, ValueError) as exc:
            sys.stderr.write(f"trend-smoke: ledger ingestion failed: "
                             f"{exc}\n")
            return bad + 1
        if not series:
            sys.stderr.write("trend-smoke: fixture ledger produced "
                             "no series\n")
            bad += 1
    # 3) detector golden: a clean 20% step at index 12, found once
    values = [100.0 + (0.4 if i % 2 else -0.4) for i in range(12)] \
        + [80.0 + (0.4 if i % 2 else -0.4) for i in range(8)]
    cps = trend.changepoints(values)
    if [c["index"] for c in cps] != [12]:
        sys.stderr.write(f"trend-smoke: step-at-12 golden found "
                         f"{[c['index'] for c in cps]}\n")
        bad += 1
    # 4) perfboard renders and the CI gate is green on the fixture
    if ledger.exists():
        with tempfile.TemporaryDirectory() as td:
            out = f"{td}/pb.html"
            rc = perfboard.main(["--ledger", str(ledger),
                                 "--check", "--out", out])
            if rc != 0:
                sys.stderr.write(f"trend-smoke: perfboard --check "
                                 f"rc={rc} on the fixture ledger\n")
                bad += 1
            else:
                with open(out) as f:
                    html_text = f.read()
                if "<svg" not in html_text \
                        or "perfboard" not in html_text:
                    sys.stderr.write("trend-smoke: dashboard HTML "
                                     "missing sparklines\n")
                    bad += 1
    return bad


def main(argv=None) -> int:
    from dplasma_tpu.utils.config import use_compile_cache
    use_compile_cache()   # the smokes compile small graphs
    pkg = _ROOT / "dplasma_tpu"
    bad = 0
    for name, fn in (("lint_excepts", lambda: run_excepts(pkg)),
                     ("jaxlint", lambda: run_jaxlint(pkg)),
                     ("perfdiff-smoke", run_perfdiff_smoke),
                     ("threadcheck", run_threadcheck),
                     ("palcheck", run_palcheck),
                     ("dagcheck-smoke", run_dagcheck_smoke),
                     ("memcheck-smoke", run_memcheck_smoke),
                     ("spmdcheck-smoke", run_spmdcheck_smoke),
                     ("serving-smoke", run_serving_smoke),
                     ("hlocheck-smoke", run_hlocheck_smoke),
                     ("ring-smoke", run_ring_smoke),
                     ("tune-smoke", run_tune_smoke),
                     ("quant-smoke", run_quant_smoke),
                     ("telemetry-smoke", run_telemetry_smoke),
                     ("devprof-smoke", run_devprof_smoke),
                     ("soak-smoke", run_soak_smoke),
                     ("trend-smoke", run_trend_smoke)):
        n = fn()
        print(f"# {name}: {'OK' if n == 0 else f'{n} violation(s)'}")
        bad += n
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
