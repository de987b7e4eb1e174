"""Headline benchmark ladder — the BASELINE.md configs on real hardware.

Mirrors the reference's measurement semantics: LAWN-41 flop formulas and
``gflops = flops/1e9 / sync_time_elapsed`` (ref tests/common.h:136-145,
src/flops.h:12-22). The reference publishes no absolute numbers
(BASELINE.md), so ``vs_baseline`` is reported against the north-star
target of 70% machine peak (BASELINE.json):

* f32 ops are measured against a full-f32-accuracy GEMM microbench peak
  (bf16x6 passes, ``Precision.HIGHEST`` — the tools/gemmpeak analog);
* FP64-equivalent ops (the metric of record: BASELINE.json targets
  "TPU FP64-equivalent peak on DPOTRF and DGEMM") run the d-precision
  compute path (kernels/dd int8 Ozaki limb GEMM + f32-seed iterative
  refinement tile kernels) and are measured against the exact limb-
  product bound: int8 matmul peak / (nl*(nl+1)/2) limb products.

``vs_baseline`` = (pct_of_peak / 0.70); 1.0 means the target is met.
The headline metric is dpotrf_f64equiv; the full ladder rides in the
``ladder`` field of the same single JSON line.

Timing methodology: the op under test runs K_lo and K_hi times inside
ONE jit (fori_loop, input perturbed per iteration so nothing hoists);
per-run time is (t_hi - t_lo)/(K_hi - K_lo), which cancels the fixed
dispatch and sync latency. min-of-3 on each endpoint.

Robustness contract (round-4): the whole run observes a hard wall-clock
budget (``DPLASMA_BENCH_BUDGET_S``, default 1500 s); the headline
``dpotrf_f64equiv`` entry runs FIRST; and the full cumulative JSON doc
is re-printed (one line, flushed) after EVERY ladder entry, so an
external timeout still leaves the last complete line parseable. Entries
that would not fit the remaining budget are recorded as skipped rather
than attempted.

Cross-run ledger: given ``--history=PATH`` (or
``DPLASMA_BENCH_HISTORY``), a completed run appends its final JSON doc
there, and ``--gate`` compares this run against the newest prior
ledger entry with ``tools/perfdiff.py`` — a ladder metric regressing
past ``--gate-threshold`` (default 10%) exits nonzero with the worst
offender named.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from dplasma_tpu.utils.config import use_compile_cache  # noqa: E402

jax.config.update("jax_enable_x64", True)
# the heavy dd graphs compile once per machine; later runs load them
use_compile_cache()

from dplasma_tpu.descriptors import TileMatrix  # noqa: E402
from dplasma_tpu.kernels import blas as kb  # noqa: E402
from dplasma_tpu.ops import generators, lu as lu_mod  # noqa: E402
from dplasma_tpu.ops import potrf as potrf_mod, qr as qr_mod  # noqa: E402
from dplasma_tpu.utils import flops as lawn41  # noqa: E402
from tools import perfdiff  # noqa: E402
from tools.gemmpeak import measure_peak  # noqa: E402


def _sync(x):
    jax.block_until_ready(x)


def _per_run_seconds(loop, lo: int, hi: int, reps: int = 3) -> float:
    """Differenced loop timing: fixed dispatch/fetch latency cancels.
    ``loop(k)`` runs the op k times (dynamic trip count: ONE compile)."""
    times = {}
    _sync(loop(hi))  # compile + warm
    for kk in (lo, hi):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            _sync(loop(kk))
            best = min(best, time.perf_counter() - t0)
        times[kk] = best
    return max((times[hi] - times[lo]) / (hi - lo), 1e-12)


def _op_loop(data, step, *extras):
    """fori_loop harness: per-iteration FIRST-ROW scale perturbation —
    unhoistable (a one-row change is not expressible as scalar*matrix,
    so no algebraic rewrite can factor it out of the op; a whole-array
    scalar scale WOULD commute out of the linear entries), SPD- and
    conditioning-preserving, and one tiny row update (the earlier f64
    diagonal scatter cost ~12 ms per iteration at N=8192 in X64-pair
    splits, profiled r4).  Full-result consumption prevents dead-code
    elimination.  ``extras`` are threaded through as jit ARGUMENTS —
    captured as closure constants they would be embedded in the
    compiled program (256 MB at N=8192 f32)."""

    @jax.jit
    def loop(k, d, *ex):
        def body(i, acc):
            shift = 1.0 + (i.astype(jnp.float32) + 1.0) * 1e-7
            a = d.at[:1].multiply(shift.astype(d.dtype))
            outs = step(a, *ex)
            return acc + sum(jnp.sum(jnp.real(o)).astype(jnp.float32)
                             for o in jax.tree_util.tree_leaves(outs))
        return lax.fori_loop(0, k, body, jnp.zeros((), jnp.float32))

    return lambda kk: loop(kk, data, *extras)



def _eager_diff_seconds(run_k, lo: int, hi: int) -> float:
    """Differenced Python-loop timing for EAGER (non-traceable) ops:
    same min-of-2 / slope methodology as _per_run_seconds."""
    run_k(1)                       # compile + warm
    times = {}
    for kk in (lo, hi):
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            run_k(kk)
            best = min(best, time.perf_counter() - t0)
        times[kk] = best
    return max((times[hi] - times[lo]) / (hi - lo), 1e-12)

def bench_potrf(N, nb, dtype=jnp.float32, lo=1, hi=6):
    A0 = generators.plghe(float(N), N, nb, seed=3872, dtype=dtype)

    def step(a):
        return potrf_mod.potrf(TileMatrix(a, A0.desc), "L").data

    t = _per_run_seconds(_op_loop(A0.data, step), lo, hi)
    return lawn41.potrf(N) / 1e9 / t


def bench_gemm(N, dtype=jnp.float32, lo=1, hi=6):
    rng = np.random.default_rng(3872)
    a = jnp.asarray(rng.standard_normal((N, N)), dtype)
    b = jnp.asarray(rng.standard_normal((N, N)), dtype)
    t = _per_run_seconds(
        _op_loop(a, lambda x, bb: kb.dot(x, bb), b), lo, hi)
    return 2.0 * N ** 3 / 1e9 / t


def bench_i8gemm(N, lo=1, hi=4):
    """Block-scaled int8 GEMM microbench (kernels.quant.qgemm):
    quantize + int32-accumulated tile products + block-scale
    dequantize, priced in GOP/s (2N^3 MACs) against the probed
    ``int8_gops`` MXU peak. The quantize/dequantize streams ride
    INSIDE the measured time — the ladder prices the usable
    block-scaled rate, not the raw systolic peak."""
    from dplasma_tpu.kernels import quant
    rng = np.random.default_rng(3872)
    a = jnp.asarray(rng.standard_normal((N, N)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((N, N)), jnp.float32)
    t = _per_run_seconds(
        _op_loop(a, lambda x, bb: quant.qgemm(x, bb), b), lo, hi)
    return 2.0 * N ** 3 / 1e9 / t


def bench_geqrf(N, nb, dtype=jnp.float32, lo=1, hi=4):
    A0 = generators.plrnt(N, N, nb, nb, seed=3872, dtype=dtype)

    from dplasma_tpu.kernels import blas as _kb
    if dtype == jnp.float64 and _kb._dd_active(jnp.dtype(jnp.float64)):
        # dd route: EAGER shape-cached executables (ops.qr dispatch) —
        # the monolithic traced sweep OOM-kills the compile helper
        # above N=2048, so the jit harness below cannot be used.
        # Guarded on the same _dd_active predicate as the ops dispatch
        # (review r4: a backend mismatch would time un-jitted eager
        # ops). Python-loop differenced timing; every iteration
        # re-dispatches (nothing to hoist).
        def run_k(kk):
            out = None
            for i in range(kk):
                a = A0.data.at[:1].multiply(1.0 + (i + 1) * 1e-7)
                out = qr_mod.geqrf(TileMatrix(a, A0.desc))
            jax.block_until_ready(out[0].data)
            _sync(out[0].data)
        return lawn41.geqrf(N, N) / 1e9 / _eager_diff_seconds(
            run_k, lo, hi)

    def step(a):
        Af, Tf = qr_mod.geqrf(TileMatrix(a, A0.desc))
        return Af.data, Tf.data

    t = _per_run_seconds(_op_loop(A0.data, step), lo, hi)
    return lawn41.geqrf(N, N) / 1e9 / t


def bench_getrf(N, nb, dtype=jnp.float32, lo=1, hi=4):
    A0 = generators.plrnt(N, N, nb, nb, seed=3872, dtype=dtype)

    from dplasma_tpu.kernels import blas as _kb
    if (dtype == jnp.float64 and _kb._dd_active(jnp.dtype(jnp.float64))
            and N // nb > 8):
        # dd route above the traced compile wall: EAGER shape-cached
        # executables (ops.lu dispatch) — see bench_geqrf. At or below
        # 8 panels the jit harness below uses the (faster) traced
        # executable.
        def run_k(kk):
            out = None
            for i in range(kk):
                a = A0.data.at[:1].multiply(1.0 + (i + 1) * 1e-7)
                out = lu_mod.getrf_1d(TileMatrix(a, A0.desc))
            jax.block_until_ready(out[0].data)
            _sync(out[0].data)
        return lawn41.getrf(N, N) / 1e9 / _eager_diff_seconds(
            run_k, lo, hi)

    def step(a):
        LU, perm = lu_mod.getrf_1d(TileMatrix(a, A0.desc))
        return LU.data, perm

    t = _per_run_seconds(_op_loop(A0.data, step), lo, hi)
    return lawn41.getrf(N, N) / 1e9 / t


def bench_ir_solver(kind, N, nb, nrhs=4, precision="f32", lo=1, hi=3):
    """Mixed-precision IR solve (ops.refine): factor in ``precision``,
    refine to f64-equivalent backward error. Eager host loop (the IR
    engine's bench path) with differenced timing; returns
    ``(gflops, record)`` where the record carries the iteration count
    and the attributed factor-phase rate — the convergence metrics the
    ladder gates alongside GFlop/s."""
    from dplasma_tpu.observability import phases
    from dplasma_tpu.ops import refine
    if kind == "posv":
        A0 = generators.plghe(float(N), N, nb, seed=3872,
                              dtype=jnp.float64)
        solve = lambda a, b, **kw: refine.posv_ir(a, b, "L", **kw)  # noqa: E731
        fl = lawn41.potrf(N) + lawn41.potrs(N, nrhs)
        fac_fl = lawn41.potrf(N)
    else:
        A0 = generators.plrnt(N, N, nb, nb, seed=3872,
                              dtype=jnp.float64)
        solve = refine.gesv_ir
        fl = lawn41.getrf(N, N) + lawn41.getrs(N, nrhs)
        fac_fl = lawn41.getrf(N, N)
    B0 = generators.plrnt(N, nrhs, nb, nb, seed=3873,
                          dtype=jnp.float64)
    got = {}

    def run_k(kk):
        res = None
        for i in range(kk):
            a = A0.data.at[:1].multiply(1.0 + (i + 1) * 1e-7)
            res = solve(TileMatrix(a, A0.desc), B0,
                        precision=precision)
        jax.block_until_ready(res[0].data)
        _sync(res[0].data)
        got["info"] = res[1]

    t = _eager_diff_seconds(run_k, lo, hi)
    # one attributed pass: the factor span's INCLUSIVE wall time (it
    # encloses the inner sweep's child spans, which hold the work)
    # prices the working-precision factorization rate for the record
    with phases.profiling() as led:
        X, _ = solve(TileMatrix(A0.data, A0.desc), B0,
                     precision=precision)
        jax.block_until_ready(X.data)
    fac = {r["phase"]: r for r in led.summary()}.get("factor")
    summ = refine.summarize(got["info"], op=f"{kind}_ir",
                            precision=precision)
    rec = {"precision": precision, "iterations": summ["iterations"],
           "converged": summ["converged"],
           "escalated": summ["escalated"],
           "backward_error": (summ["backward_errors"][-1]
                              if summ["backward_errors"] else None),
           "factor_gflops": (round(fac_fl / 1e9 / fac["total_s"], 2)
                             if fac and fac["total_s"] > 0
                             else None)}
    return fl / 1e9 / t, rec


def bench_ir_factor_rates(N, nb,
                          precisions=("int8", "bf16", "f32",
                                      "f32x2")):
    """Per-precision working-factorization rates (the bench doc's
    ``refine.factor_gflops`` table): one attributed posv_ir factor per
    precision (max_iters=1, no escalation — the factor span is what's
    being priced, not convergence)."""
    from dplasma_tpu.observability import phases
    from dplasma_tpu.ops import refine
    A0 = generators.plghe(float(N), N, nb, seed=3872,
                          dtype=jnp.float64)
    B0 = generators.plrnt(N, 1, nb, nb, seed=3873, dtype=jnp.float64)
    fac_fl = lawn41.potrf(N)
    rates = {}
    for prec in precisions:
        kw = dict(precision=prec, max_iters=1, escalate=False)
        X, _ = refine.posv_ir(A0, B0, **kw)     # compile + warm
        jax.block_until_ready(X.data)
        with phases.profiling() as led:
            X, _ = refine.posv_ir(A0, B0, **kw)
            jax.block_until_ready(X.data)
        fac = {r["phase"]: r for r in led.summary()}.get("factor")
        if fac and fac["total_s"] > 0:
            rates[prec] = round(fac_fl / 1e9 / fac["total_s"], 2)
    return rates


def _dd_bound_products(K: int) -> int:
    """Limb matmuls per FP64-equivalent GEMM at reduction depth K."""
    from dplasma_tpu.kernels import dd
    _, nl, _ = dd._plan(K, 53)
    return nl * (nl + 1) // 2


def _parse_args(argv):
    import argparse
    ap = argparse.ArgumentParser(
        prog="bench", description="headline benchmark ladder")
    ap.add_argument("--history", default=None,
                    help="JSONL ledger to gate against and append to "
                         "(default: $DPLASMA_BENCH_HISTORY; none)")
    ap.add_argument("--gate", action="store_true",
                    help="compare this run against the newest prior "
                         "ledger entry (tools/perfdiff.py); exit "
                         "nonzero on regression")
    ap.add_argument("--gate-threshold", type=float,
                    default=perfdiff.DEFAULT_THRESHOLD,
                    help="relative regression threshold for --gate")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    from dplasma_tpu.observability import RunReport

    ns = _parse_args(argv)
    history = ns.history or os.environ.get("DPLASMA_BENCH_HISTORY")
    if ns.gate and not history:
        print("bench: --gate needs a ledger (--history or "
              "DPLASMA_BENCH_HISTORY)", file=sys.stderr)
        return 2
    on_tpu = jax.default_backend() != "cpu"
    budget_s = float(os.environ.get(
        "DPLASMA_BENCH_BUDGET_S", "1500" if on_tpu else "600"))
    deadline = time.monotonic() + budget_s
    # the ladder and peak reads live in a versioned run-report; the
    # printed one-line JSON doc (format unchanged — external parsers
    # depend on it) is derived from the report state, and the full
    # report is written to DPLASMA_BENCH_REPORT when set
    report = RunReport("bench")
    ladder = report.entries
    peaks = report.extra.setdefault("peaks", {})
    report.extra["budget_s"] = budget_s
    # active pipeline shape of the factorization sweeps (schema v4):
    # the ladder's getrf/geqrf/potrf entries run with THIS config.
    # Since v11 this is the FULL resolved knob vector (sweep.lookahead,
    # qr/lu.agg_depth, every panel.* knob, grid; the per-entry tile
    # size rides each ladder entry's "nb" field) so historical ledger
    # entries are usable autotuner evidence and perfdiff's same-knob-
    # vector baselining compares like against like (a chain-vs-tree or
    # lookahead flip is visible in the ledger, not silent).
    from dplasma_tpu.tuning import resolved_knobs
    pipeline = resolved_knobs(grid=(1, 1))
    report.pipeline = pipeline
    # schema v18: attribution stamp (git SHA, jax/jaxlib, backend,
    # active MCA overrides) — rides the report AND every ledger doc
    # so the trend observatory can answer "what changed at this
    # changepoint" without forensic archaeology
    provenance = report.stamp_provenance(
        family="bench", mesh_shape=[1, 1], peaks_source="bench")

    def remaining():
        return deadline - time.monotonic()

    last_doc = {}   # newest emitted doc (the ledger/gate source)

    def emit():
        """Print the full cumulative JSON doc (one line, flushed).
        Called after every ladder mutation: if the driver's timeout
        kills the process, the last complete stdout line still parses
        (the r3 artifact was rc=124/parsed=null — never again)."""
        head = max((x for x in ladder
                    if "value" in x and "dpotrf_f64equiv" in x["metric"]),
                   key=lambda x: x["value"], default=None)
        if head is None:  # strongest measured entry as fallback
            head = max((x for x in ladder if "value" in x),
                       key=lambda x: x.get("vs_baseline", 0.0),
                       default={"metric": "none", "value": 0.0,
                                "unit": "GFlop/s", "vs_baseline": 0.0})
        doc = {
            "metric": head["metric"] + f"_{jax.default_backend()}",
            "value": head["value"],
            "unit": head["unit"],
            "vs_baseline": head["vs_baseline"],
            "budget_s": budget_s,
            "elapsed_s": round(budget_s - remaining(), 1),
            "ladder": ladder,
            "peaks": peaks,
            "pipeline": pipeline,
            "family": "bench",
            "provenance": provenance,
        }
        if report.extra.get("refine"):
            # IR-solver convergence record (iterations, per-precision
            # factor rates) — tracked in the ledger next to GFlop/s
            doc["refine"] = report.extra["refine"]
        report.extra["headline"] = {
            k: doc[k] for k in ("metric", "value", "unit",
                                "vs_baseline", "elapsed_s")}
        last_doc["doc"] = doc
        print(json.dumps(doc), flush=True)
        rp = os.environ.get("DPLASMA_BENCH_REPORT")
        if rp:
            try:
                report.write(rp)
            except OSError as exc:
                print(f"#! cannot write bench report: {exc}",
                      file=sys.stderr)

    def run_entry(name, fn, cfg_list, bound, cost_s=90.0, **fixed):
        """Measure one ladder entry with budget-gated size fallbacks.
        ``cost_s`` is the per-config worst-case estimate (compile +
        runs; a per-config ``cost_s`` key overrides it). Configs that
        don't fit the remaining budget are recorded as skipped, not
        attempted. The gate bounds what gets *started*; for a compile
        that hangs mid-flight the backstop is the external timeout plus
        the incremental emit() — the last stdout line still parses.
        One retry per config (budget permitting)."""
        errs = []
        for kw in cfg_list:
            kw = dict(kw)
            cost = kw.pop("cost_s", cost_s)
            attempts = 0
            while attempts < 2:
                if remaining() < cost:
                    errs.append(f"N={kw['N']}: skipped (budget: "
                                f"{remaining():.0f}s < {cost:.0f}s est)")
                    break
                attempts += 1
                try:
                    g = fn(**fixed, **kw)
                    # a name already carrying its unit suffix (the
                    # i8gemm_gops GOP/s ladder) keeps it verbatim
                    stem = name if name.endswith("_gops") \
                        else f"{name}_gflops"
                    entry = {"metric": f"{stem}_n{kw['N']}",
                             "value": round(g, 2),
                             "unit": ("GOP/s" if name.endswith("_gops")
                                      else "GFlop/s"),
                             "vs_baseline": round((g / bound) / 0.70, 4)}
                    if "nb" in kw:
                        # the per-entry tile size completes the knob
                        # vector (doc-level "pipeline" carries the
                        # MCA knobs; nb varies per ladder entry)
                        entry["nb"] = kw["nb"]
                    ladder.append(entry)
                    report.metrics.gauge(
                        "bench_gflops", metric=entry["metric"]).set(g)
                    emit()
                    return entry
                except Exception as exc:  # noqa: BLE001
                    errs.append(f"N={kw['N']}: {str(exc)[:120]}")
        ladder.append({"metric": name, "error": "; ".join(errs[-3:])})
        emit()
        return None

    if on_tpu:
        peak32 = measure_peak(n=4096, iters=60, dtype="float32",
                              precision=jax.lax.Precision.HIGHEST)
        bf16_peak = measure_peak(n=4096, iters=60, dtype="bfloat16",
                                 precision=None)
        # int8 needs more iterations than the float peaks for the
        # differenced loop's per-iteration work to rise above jitter
        i8_peak = measure_peak(n=4096, iters=300, dtype="int8",
                               precision=None)
        # largest size first; the budget gate (not retries) bounds cost
        cfgs32 = [
            ("spotrf", bench_potrf,
             [dict(N=16384, nb=1024), dict(N=8192, nb=1024)], 150.0),
            ("sgemm", bench_gemm, [dict(N=8192), dict(N=4096)], 90.0),
            ("sgeqrf", bench_geqrf,
             [dict(N=8192, nb=1024), dict(N=4096, nb=512)], 150.0),
            ("sgetrf", bench_getrf,
             [dict(N=16384, nb=1024), dict(N=8192, nb=1024)], 150.0),
        ]
        dd_gemm_cfgs = [dict(N=8192, cost_s=300), dict(N=4096),
                        dict(N=2048)]
        # known-good size first: the headline must land in the artifact
        # before anything speculative is attempted (r3 lesson). The
        # metric-of-record N=16384 upgrade runs at the END of the
        # ladder, budget permitting. dd QR/LU sizes track the measured
        # compile cost (~6-10 min at 2048/512 in r3); larger sizes get
        # their own cost_s so the gate prices them honestly.
        dd_potrf_cfgs = [dict(N=8192, nb=512), dict(N=4096, nb=512)]
        # dd QR rides EAGER per-step fused executables (one compile
        # per shrinking-window shape, persistent-cached); nb=1024
        # measured 671 GF/s at 8192 vs 582 at 512 via the bench
        # harness, and halves the cold-compile bill (8 steps vs 16 —
        # the 512 compile ate a full bench budget once; pre-warm the
        # EXACT ladder configs before the driver's run). dd LU at
        # nb=1024 stays at <= 8 panels and rides the traced monolith
        # (r5: 1324 GF/s at 8192/1024 vs 336 eager at 512).
        dd_geqrf_cfgs = [dict(N=8192, nb=1024, cost_s=500),
                         dict(N=4096, nb=1024, cost_s=350),
                         dict(N=2048, nb=512)]
        dd_getrf_cfgs = [dict(N=8192, nb=1024, cost_s=500),
                         dict(N=4096, nb=1024, cost_s=400),
                         dict(N=2048, nb=512)]
        # mixed-precision IR solves (ops.refine): f32 factor + dd
        # residuals — much cheaper to compile than the full dd routes
        ir_posv_cfgs = [dict(N=4096, nb=512, cost_s=350),
                        dict(N=2048, nb=512)]
        ir_gesv_cfgs = [dict(N=4096, nb=512, cost_s=400),
                        dict(N=2048, nb=512)]
        ir_rates_cfg = dict(N=2048, nb=512)
        ir_i8_cfgs = [dict(N=2048, nb=512)]
        i8gemm_cfgs = [dict(N=4096, cost_s=120), dict(N=2048)]
        dd_cost = 420.0
    else:  # CI / smoke path: tiny shapes, same code
        peak32 = measure_peak(n=1024, iters=20, dtype="float32",
                              precision=jax.lax.Precision.HIGHEST)
        bf16_peak = peak32
        i8_peak = peak32
        cfgs32 = [
            ("spotrf", bench_potrf, [dict(N=2048, nb=256)], 120.0),
            ("sgemm", bench_gemm, [dict(N=2048)], 120.0),
            ("sgeqrf", bench_geqrf, [dict(N=1024, nb=256)], 120.0),
            ("sgetrf", bench_getrf, [dict(N=1024, nb=256)], 120.0),
        ]
        dd_gemm_cfgs = [dict(N=1024)]
        dd_potrf_cfgs = [dict(N=1024, nb=256)]
        dd_geqrf_cfgs = [dict(N=512, nb=128)]
        dd_getrf_cfgs = [dict(N=512, nb=128)]
        ir_posv_cfgs = [dict(N=512, nb=128)]
        ir_gesv_cfgs = [dict(N=512, nb=128)]
        ir_rates_cfg = dict(N=256, nb=64)
        ir_i8_cfgs = [dict(N=512, nb=128)]
        i8gemm_cfgs = [dict(N=1024)]
        dd_cost = 60.0

    # Peak reads are sanity-gated against known hardware ratios
    # (HIGHEST f32 = six bf16 passes; the integer systolic path runs at
    # 2x the bf16 rate on v5e/v5p). Both the raw reading and the
    # estimate are recorded so a forced denominator is visible in the
    # artifact (ADVICE r3).
    peaks["f32_highest_gflops"] = round(peak32, 1)
    peaks["bf16_gflops_raw"] = round(bf16_peak, 1)
    peaks["int8_gops_raw"] = round(i8_peak, 1)
    if on_tpu:
        bf16_est = 6.0 * peak32
        if not (0.75 * bf16_est <= bf16_peak <= 1.5 * bf16_est):
            bf16_peak = bf16_est
            peaks["bf16_gflops_forced_estimate"] = True
        # upper band 1.05: the integer path is architecturally 2x the
        # bf16 rate — a raw reading ABOVE that is measurement luck and
        # would deflate every f64-equiv vs_baseline through the bound
        i8_est = 2.0 * bf16_peak
        if not (0.6 * i8_est <= i8_peak <= 1.05 * i8_est):
            i8_peak = i8_est
            peaks["int8_gops_forced_estimate"] = True
    dd_bound = i8_peak / _dd_bound_products(dd_gemm_cfgs[0]["N"])
    peaks["bf16_gflops"] = round(bf16_peak, 1)
    peaks["int8_gops"] = round(i8_peak, 1)
    peaks["f64equiv_bound_gflops"] = round(dd_bound, 1)

    # Headline FIRST (VERDICT r3 next-round item 1): the metric of
    # record must be in the artifact even if everything after times out.
    run_entry("dpotrf_f64equiv", bench_potrf, dd_potrf_cfgs, dd_bound,
              cost_s=dd_cost, dtype=jnp.float64, hi=4)
    run_entry("dgemm_f64equiv", bench_gemm, dd_gemm_cfgs, dd_bound,
              cost_s=dd_cost / 3, dtype=jnp.float64)

    # Mixed-precision IR solves: factor at the f32 MXU rate, refine
    # the O(n^2) residual on the dd rungs to f64-equivalent backward
    # error. Measured against the SAME f64-equiv bound as the dd
    # routes — vs_baseline > the dd entries' is the route's win. The
    # ladder additionally carries the iteration counts (lower-better:
    # --gate flags convergence regressions, not just GFlop/s) and the
    # doc's "refine" section the per-precision factor rates.
    refine_sec = report.extra.setdefault("refine", {})

    def run_ir_entry(name, kind, cfg_list, cost, precision=None):
        recs = {}

        def fn(N, nb, **kw):
            if precision is not None:
                kw.setdefault("precision", precision)
            g, rec = bench_ir_solver(kind, N, nb, **kw)
            recs[N] = rec
            return g

        e = run_entry(name, fn, cfg_list, dd_bound, cost_s=cost)
        if e is None:
            return
        n_val = int(e["metric"].rsplit("_n", 1)[1])
        rec = recs.get(n_val)
        if rec is None:
            return
        e["refine"] = rec
        refine_sec[name] = dict(rec, N=n_val)
        ladder.append({"metric": f"{name}_iters_n{n_val}",
                       "value": rec["iterations"],
                       "unit": "iterations", "better": "lower"})
        if rec.get("factor_gflops"):
            ladder.append(
                {"metric": f"{name}_factor_{rec['precision']}"
                           f"_gflops_n{n_val}",
                 "value": rec["factor_gflops"], "unit": "GFlop/s"})
        emit()

    run_ir_entry("dposv_ir_f64equiv", "posv", ir_posv_cfgs,
                 dd_cost * 0.8)
    run_ir_entry("dgesv_ir_f64equiv", "gesv", ir_gesv_cfgs, dd_cost)
    # int8 rung: the SAME f64-equivalent solves with block-scaled
    # quantized trailing updates (kernels.quant) — separate *_i8
    # ladder names so a rung flip gates same-vs-same, and the
    # factor-rate entry prices the quantized factorization
    run_ir_entry("dposv_ir_i8", "posv", ir_i8_cfgs, dd_cost * 0.5,
                 precision="int8")
    run_ir_entry("dgesv_ir_i8", "gesv", ir_i8_cfgs, dd_cost * 0.5,
                 precision="int8")
    # block-scaled int8 GEMM microbench vs the probed integer peak
    run_entry("i8gemm_gops", bench_i8gemm, i8gemm_cfgs, i8_peak,
              cost_s=dd_cost / 3)
    if remaining() > (120.0 if on_tpu else 30.0):
        try:
            refine_sec["factor_gflops"] = dict(
                bench_ir_factor_rates(**ir_rates_cfg),
                N=ir_rates_cfg["N"])
            emit()
        except Exception as exc:  # noqa: BLE001
            refine_sec["factor_gflops"] = {
                "error": str(exc)[:120]}

    for name, fn, cfg_list, cost in cfgs32:
        run_entry(name, fn, cfg_list, peak32,
                  cost_s=cost if on_tpu else 60.0, dtype=jnp.float32)
    run_entry("dgeqrf_f64equiv", bench_geqrf, dd_geqrf_cfgs, dd_bound,
              cost_s=dd_cost, dtype=jnp.float64, hi=3)
    run_entry("dgetrf_f64equiv", bench_getrf, dd_getrf_cfgs, dd_bound,
              cost_s=dd_cost, dtype=jnp.float64, hi=3)
    if on_tpu:
        # metric-of-record upgrade (BASELINE.md names N=10k-100k): only
        # after every mandatory entry has been captured; emit() keeps
        # the best dpotrf_f64equiv as the headline automatically.
        run_entry("dpotrf_f64equiv", bench_potrf,
                  [dict(N=16384, nb=1024)], dd_bound, cost_s=450.0,
                  dtype=jnp.float64, hi=3)
    emit()

    # cross-run ledger + regression gate: the newest PRIOR entry is
    # the baseline (read before this run appends itself)
    doc = last_doc.get("doc")
    rc = 0
    if doc is not None and history:
        prev = None
        if os.path.exists(history):
            try:
                # newest entry of THIS bench family (the ledger may
                # interleave servebench docs with no common metrics)
                prev = perfdiff.latest_comparable_entry(history, doc)
            except (OSError, ValueError) as exc:
                print(f"#! cannot read bench history: {exc}",
                      file=sys.stderr)
        try:
            perfdiff.append_ledger(history, doc)
        except OSError as exc:
            print(f"#! cannot append bench history: {exc}",
                  file=sys.stderr)
        if ns.gate:
            if prev is None:
                print("# bench gate: no prior ledger entry; skipped",
                      file=sys.stderr)
            else:
                res = perfdiff.compare(prev, doc,
                                       threshold=ns.gate_threshold)
                for line in perfdiff.format_result(res):
                    print(line, file=sys.stderr)
                if res["compared"] == 0 and not res.get("new"):
                    # every ladder entry errored/skipped: a gate that
                    # cannot compare anything must not pass vacuously
                    print("# bench gate: nothing comparable against "
                          "the prior entry; failing the gate",
                          file=sys.stderr)
                    rc = 1
                elif res["compared"] == 0:
                    # this run measured fine but the newest prior
                    # entry is a different bench family (e.g. a
                    # servebench serving.* doc sharing the ledger):
                    # informational, this entry seeds the next gate
                    print("# bench gate: prior entry shares no "
                          "metrics (different bench family); this "
                          "run seeds the next comparison",
                          file=sys.stderr)
                elif not res["ok"]:
                    rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
