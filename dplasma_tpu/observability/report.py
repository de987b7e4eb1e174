"""The versioned JSON run-report (``"schema": 18``).

One report per driver invocation (``--report[=file]``): the machine-
readable record of everything the ``[****] TIME(s)`` line summarizes
plus what it drops — per-run times (not just best), the phase breakdown
(ENQ/warmup/PROG/DEST), XLA's cost/memory analysis, the analytic
comm-volume model, and DAG analytics. ``bench.py`` sources its metric
lines from a report rather than scraping stdout.

Schema (stable keys; additive changes bump ``REPORT_SCHEMA``)::

    {"schema": 3, "name": ..., "created_unix_ns": ...,
     "iparam": {...},              # the parsed driver parameter block
     "env": {"backend": ..., "jax": ..., "device_count": ...},
     "ops": [{"label": ..., "prec": ...,
              "timings": {"enq_s", "warmup_s", "dest_s", "nruns",
                          "runs_s": [...],
                          "best_s", "min_s", "median_s", "max_s",
                          "mean_s", "stddev_s"},  # nruns=0 dry runs
                                     # carry explicit nulls, never NaN
              "model_flops": ..., "gflops": ...,
              "xla": {...} | null,  # observability.xla.capture_compiled
              "comm": {...} | null, # observability.comm model
              "dag": {...} | null,  # observability.dag.dag_stats
              "phases": {"attributed_run_s", "sum_s", "coverage",
                         "peaks_source",
                         "spans": [{"phase", "count", "measured_s",
                                    "expected_s", "achieved_frac",
                                    "bound"}]} | null}],  # (v5,
                                     # --phase-profile attribution)
     "metrics": [...],             # MetricsRegistry.snapshot()
     "checks": [{"what", "residual", "ok"}],   # -x verifications (v2)
     "resilience": [{"op", "enabled", "injection": {...} | null,
                     "attempts": [{"attempt", "action", "label", "ok",
                                   "classification", "health", "abft",
                                   "elapsed_s", "error"}],
                     "outcome": "clean|remediated|failed",
                     "winner": ..., "faults_detected": ...}],  # (v2)
     "dagcheck": [{"op", "ok", "tasks", "edges", "declared",
                   "checked_reads", "checked_pairs", "skipped",
                   "comm": {...} | null, "counts": {kind: n},
                   "diagnostics": [{"kind", "message", "tasks",
                                    "tile"}]}],            # (v3)
     "pipeline": {"sweep.lookahead": n, "qr.agg_depth": d,
                  "panel.kernel": raw, "panel.qr": k,
                  "panel.lu": k,
                  "lu.agg_depth": d, "panel.tree_leaf": h,
                  "panel.rec_base": w,
                  "tuning.source": s?} | absent,
                                   # (v4; panel.* keys v9; the full
                                   # knob vector + tuning.source v11)
     "roofline": [{"op", "op_class", "expected_s", "measured_s",
                   "achieved_frac", "bound", "components_s",
                   "peaks", "peaks_source"}],              # (v5)
     "spmdcheck": [{"op", "ok", "kernel", "shard_maps", "mesh_axes",
                    "collectives", "counts": {class: n},
                    "relation", "expected",
                    "diagnostics": [{"kind", "message", "kernel",
                                     "detail"}]}],         # (v6)
     "refine": [{"op", "precision", "iterations",
                 "backward_errors": [...], "converged",
                 "escalated", "tol",
                 "krylov_steps"}],     # (v7; krylov_steps: gesv_mxp)
     "serving": [{"requests", "batches", "mean_batch",
                  "latency_s": {"p50", "p99", "max"},
                  "cache": {"entries", "capacity", "hits", "misses",
                            "evictions", "invalidations", "hit_rate",
                            "compile_s"},
                  "remediated", "failed", "retries",
                  "escalations", ...}],                    # (v8)
     "hlocheck": [{"op", "ok", "kernel", "counts": {kind: n},
                   "expected",
                   "relation",  # ==|>=|mismatch|gspmd|
                                # unreconciled|no-collectives
                   "donated", "aliased",
                   "hbm_peak_bytes", "hbm_budget", "copy_bytes",
                   "total_bytes",
                   "diagnostics": [{"kind", "message", "kernel",
                                    "op", "detail"}]}],   # (v10)
     "memcheck": [{"op", "ok", "kernel", "tasks", "tiles",
                   "peak_bytes", "predicted_hbm_peak_bytes",
                   "peak_by_rank": {rank: bytes},
                   "peak_task", "live_at_peak", "budget",
                   "staging_factor", "stream",  # plan | null
                   "counts": {kind: n},
                   "diagnostics": [{"kind", "message", "task",
                                    "tile", "step"}]}],   # (v16)
     "tuning": [{"op", "key", "source",  # db|interpolated|default
                 "db",                   # DB path | null
                 "knobs",       # the consulted DB knob vector | null
                 "applied",     # MCA overrides actually applied
                 "nb",          # tile size applied | null
                 "measured_s",  # the DB winner's provenance | null
                 "entry_key"}],  # the DB entry consulted (may be a
                                 # neighbor under interpolation) (v11)
     "scaling": [{"op", "prec", "n", "nb",
                  "ring",                # the resolved ring.enable
                  "points": [{"chips", "grid": [P, Q], "median_s",
                              "gflops",
                              "parallel_efficiency"}]}],  # (v12,
                                 # tools/multichip.py per-chip-count
                                 # scaling curves; efficiency =
                                 # T_1 / (chips * T_chips), higher
                                 # is better)
     "telemetry": {"spans": {"enabled", "opened", "closed",
                             "recorded", "dropped", "balanced"},
                   "exporter": {"path", "interval_s",
                                "flushes"} | null,
                   "flight_recorder": {"capacity", "recorded",
                                       "dropped",
                                       "events": [{"seq", "t_ns",
                                                   "kind",
                                                   ...}]}},   # (v13,
                                 # observability.telemetry: the live
                                 # instruments' end-of-run summary —
                                 # tracing span ledger, streaming
                                 # exporter provenance, and the
                                 # flight recorder's event ring)
     "devprof": [{"label", "op", "backend",  # jax|synthetic
                  "nranks", "run_s",
                  "categories": {"compute", "collective", "ici",
                                 "host"},    # mean seconds per rank
                  "coverage",    # category sum / run_s
                  "timeline_ops",
                  "collectives": [{"cls",    # kind@axis (spmdcheck)
                                   "hlo", "count", "measured_s",
                                   "model_bytes",
                                   "achieved_bytes_per_s",
                                   "achieved_frac"}],
                  "reconciliation": {"relation",  # ==|mismatch|
                                     # unmodelled|no-collectives
                                     "expected", "ingested"},
                  "skew": {"value", "slowest_rank",
                           "dominating_category", "per_rank_s",
                           "ranks", "max_step_spread_s"},
                  "critical_path": {"length_s", "frac", "spans",
                                    "truncated"},
                  "diagnostics": [{"kind", "op", "message"}],
                  "ok"}],                                  # (v14)
     "admission": {"enabled", "max_queue", "max_inflight",
                   "slo_p99_ms", "ewma_p99_ms",
                   "admitted", "shed", "degraded",
                   "deadline_expired", "breaker_opens",
                   "breakers": {"op:rung": {"state", "failures",
                                            "opens", "probes"}},
                   "retry_budget": {"limit", "used"},
                   "audit": {"submitted", "admitted", "shed",
                             "resolved", "lost", "flight_shed_seen",
                             "flight_dropped",
                             "balanced"} | absent},        # (v15,
                                 # serving.admission: the overload
                                 # posture's end-of-run record; the
                                 # audit subkey is servebench --soak's
                                 # conservation proof)
     "provenance": {"schema": 1, "family",
                    "git": {"sha", "dirty"} | null,
                    "jax", "jaxlib", "backend", "device_count",
                    "mesh_shape": [P, Q]?, "peaks_source"?,
                    "mca": {...} | null},            # (v18,
                                 # observability.trend
                                 # .collect_provenance — every probe
                                 # guarded; absent when the writer
                                 # never stamped)
     "extra": {...}}               # free-form (bench ladder, peaks)

Schema history: 2 adds the ``"checks"`` and ``"resilience"``
sections; 3 adds ``"dagcheck"`` (--dagcheck static dataflow
verification, analysis.dagcheck); 4 adds ``"pipeline"`` (the active
lookahead/aggregation shape of the pipelined factorization sweeps);
5 adds ``"phases"`` per op entry and the ``"roofline"`` section
(--phase-profile / --peaks-file performance attribution,
observability.phases + observability.roofline) plus the ``nruns``
timing field; 6 adds ``"spmdcheck"`` (--spmdcheck collective-schedule
verification of the traced SPMD program, analysis.spmdcheck);
7 adds ``"refine"`` (the mixed-precision iterative-refinement
solvers' per-solve record — working precision, iteration count,
per-iteration normwise backward error, converged/escalated outcome,
ops.refine); 8 adds ``"serving"`` (the solver-as-a-service layer's
throughput/latency/cache record — request and batch counts, p50/p99
latency, executable-cache economics, per-request remediation
outcomes, dplasma_tpu.serving + tools/servebench.py); 9 adds the
``panel.*`` keys to ``"pipeline"`` (the panel-factorization engine's
raw knob + per-route resolution, kernels.panels — what perfdiff's
same-family baselining keys on); 10 adds ``"hlocheck"`` (--hlocheck
compiled-artifact verification of the post-GSPMD HLO — collective
reconciliation, precision/donation/HBM/anti-pattern audits,
analysis.hlocheck — whose ``hbm_peak_bytes`` perfdiff gates
lower-better); 11 adds ``"tuning"`` (the --autotune consultation
record — which tuning-DB entry resolved this run's knobs, with what
source/provenance, dplasma_tpu.tuning) plus the ``"tuning.source"``
and full-knob-vector keys (``lu.agg_depth``/``panel.tree_leaf``/
``panel.rec_base``) in ``"pipeline"``; 12 adds ``"scaling"`` (the
per-chip-count scaling curves of the cyclic factorizations —
``tools/multichip.py`` runs each op over 1/2/4/8 chips and records
median seconds, GFlop/s, and parallel efficiency per point, gated
higher-better through perfdiff) plus the ``ring.enable`` key in
``"pipeline"`` (the explicit-ICI-ring knob, kernels.pallas_ring);
13 adds ``"telemetry"`` (the live-instrument summary —
observability.telemetry/tracing: the always-on serving span ledger,
the streaming Prometheus exporter's provenance, and the flight
recorder's bounded event ring, dumped whole so an incident report
carries its own evidence; servebench's ``"serving"`` entries gain
``trace_overhead_frac``, which perfdiff gates lower-better); 14 adds
``"devprof"`` (the measured per-device timeline attribution —
observability.devprof: category seconds binned from the same HLO
op-name vocabulary hlocheck parses, per-collective measured seconds
+ achieved bytes/s reconciled against the spmd_comm_model pricing
and the roofline ``ici`` peak, per-rank skew/straggler attribution,
and the merged-timeline critical path; perfdiff gates
``devprof.ici_achieved_frac`` higher-better and ``devprof.skew``
lower-better); 15 adds ``"admission"`` (the serving overload
posture's end-of-run record — serving.admission: admission-control
counters (admitted/shed/degraded/deadline-expired), the EWMA-p99 SLO
tracker state, per-(op, rung) circuit-breaker states, the global
retry budget, and — from ``tools/servebench.py --soak`` — the
conservation audit proving submitted == resolved + shed with zero
lost futures, reconciled against the flight-recorder ring; perfdiff
gates ``serving.shed_frac`` and ``serving.deadline_miss_frac``
lower-better, and servebench's ``"serving"`` entries gain
``admission_overhead_frac``, gated like ``trace_overhead_frac``);
16 adds ``"memcheck"`` (the static tile-liveness & HBM-residency
verification — analysis.memcheck: per-rank structural resident peak
from the recorded DAG's live intervals, the predicted HBM peak under
the documented compiled-staging allowance, the budget gate vs MCA
``memcheck.hbm_budget`` with the peak-driving task/tile/live-set
diagnostics, and the streaming-simulator plan summary when the
budget forces spill/prefetch; perfdiff gates
``memcheck.peak_bytes`` lower-better);
17 adds ``"autopilot"`` (the precision-autopilot decision records —
dplasma_tpu.tuning.autopilot: one entry per consulted IR solve with
the condest pre-flight estimate, the condition-class bucket, the
selected ``ir.precision`` rung and its provenance
(db/interpolated/default), the 5-part ``|cond=<class>`` tuning key,
and the DB path; drivers under ``--autotune`` and the serving layer
both emit them, and runtime escalations land back in the tuning DB
as negative entries so the recorded verdicts converge);
18 adds ``"provenance"`` (the attribution stamp —
observability.trend.collect_provenance: git SHA + dirty flag,
jax/jaxlib versions, backend platform + device count + mesh shape,
the peaks source (bench/default/file), the active MCA override
snapshot, and the ladder family; written by ``bench.py``,
``tools/servebench.py``, ``tools/multichip.py``, and the drivers'
``Driver.close``, so every ledger entry is attributable and the
trend observatory splits series on real config changes instead of
silently mixing them).
All additive — v1 readers of the other keys are unaffected; this
reader accepts <= 18 (:func:`load_report` tolerates every v1-v18
vintage, filling the always-present keys).
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import List, Optional

from dplasma_tpu.observability.metrics import Histogram, MetricsRegistry

REPORT_SCHEMA = 18


def run_stats(runs_s: List[float]) -> dict:
    """min/median/max/mean/stddev of the per-run times (the reference
    prints per-run lines; ``best`` alone hides variance). The math is
    :meth:`Histogram.stats` — one statistics implementation for both
    the report timings and the metrics snapshot. A no-runs entry
    (``nruns=0`` dry runs) carries explicit nulls for every statistic
    so the document still serializes/round-trips cleanly."""
    # exact_cap = the run count: report statistics stay EXACT at any
    # nruns (the bounded default exists for unbounded serving
    # traffic, not for a list we hold in full right here)
    h = Histogram(exact_cap=len(runs_s))
    for v in runs_s:
        h.observe(v)
    s = h.stats()
    return {"nruns": len(runs_s), "runs_s": list(runs_s),
            "best_s": s["min"],
            "min_s": s["min"], "median_s": s["median"],
            "max_s": s["max"], "mean_s": s["mean"],
            "stddev_s": s["stddev"]}


class RunReport:
    """Accumulates per-op entries + metrics; writes versioned JSON."""

    def __init__(self, name: str, iparam=None):
        self.name = name
        self.iparam = iparam
        self.metrics = MetricsRegistry()
        self.ops: List[dict] = []
        self.entries: List[dict] = []   # free-form (bench ladder)
        self.checks: List[dict] = []    # -x verification outcomes
        self.resilience: List[dict] = []  # per-op ladder summaries
        self.dagcheck: List[dict] = []  # --dagcheck verification (v3)
        self.spmdcheck: List[dict] = []  # --spmdcheck verification (v6)
        self.refine: List[dict] = []    # IR-solver records (v7)
        self.serving: List[dict] = []   # serving-layer records (v8)
        self.hlocheck: List[dict] = []  # --hlocheck audits (v10)
        self.memcheck: List[dict] = []  # --memcheck residency (v16)
        self.tuning: List[dict] = []    # --autotune consultations (v11)
        self.autopilot: List[dict] = []  # precision-autopilot picks (v17)
        self.scaling: List[dict] = []   # per-chip-count curves (v12)
        self.telemetry: Optional[dict] = None  # live instruments (v13)
        self.devprof: List[dict] = []   # measured-timeline attribution (v14)
        self.admission: Optional[dict] = None  # overload posture (v15)
        self.pipeline: Optional[dict] = None  # sweep pipeline shape (v4)
        self.provenance: Optional[dict] = None  # attribution stamp (v18)
        self.roofline: List[dict] = []  # per-op roofline entries (v5)
        self.extra: dict = {}
        self._t0 = time.time_ns()

    def add_op(self, label: str, *, prec: str = "", flops: float = 0.0,
               enq_s: float = 0.0, warmup_s: Optional[float] = None,
               dest_s: float = 0.0, runs_s: Optional[List[float]] = None,
               gflops: Optional[float] = None, xla: Optional[dict] = None,
               comm: Optional[dict] = None, dag: Optional[dict] = None,
               phases: Optional[dict] = None) -> dict:
        timings = {"enq_s": enq_s, "warmup_s": warmup_s,
                   "dest_s": dest_s}
        timings.update(run_stats(runs_s or []))
        entry = {"label": label, "prec": prec, "model_flops": flops,
                 "gflops": gflops, "timings": timings,
                 "xla": xla, "comm": comm, "dag": dag,
                 "phases": phases}
        self.ops.append(entry)
        return entry

    def add_check(self, what: str, residual: float, ok: bool) -> dict:
        """Record one -x verification outcome (schema v2)."""
        entry = {"what": what, "residual": float(residual),
                 "ok": bool(ok)}
        self.checks.append(entry)
        return entry

    def add_resilience(self, summary: dict) -> dict:
        """Record one progress() call's resilience summary — the
        injection, every attempt's classification/action, and the
        outcome (schema v2; see resilience.guard.Ladder.summary)."""
        self.resilience.append(summary)
        return summary

    def add_dagcheck(self, op: str, summary: dict) -> dict:
        """Record one --dagcheck verification outcome (schema v3; see
        analysis.dagcheck.CheckResult.summary)."""
        entry = {"op": op, **summary}
        self.dagcheck.append(entry)
        return entry

    def add_spmdcheck(self, op: str, summary: dict) -> dict:
        """Record one --spmdcheck verification outcome (schema v6; see
        analysis.spmdcheck.SpmdResult.summary)."""
        entry = {"op": op, **summary}
        self.spmdcheck.append(entry)
        return entry

    def add_refine(self, summary: dict) -> dict:
        """Record one mixed-precision IR solve (schema v7; see
        ops.refine.summarize)."""
        self.refine.append(summary)
        return summary

    def add_serving(self, summary: dict) -> dict:
        """Record one serving-layer lifetime summary (schema v8; see
        serving.service.SolverService.summary)."""
        self.serving.append(summary)
        return summary

    def add_hlocheck(self, op: str, summary: dict) -> dict:
        """Record one --hlocheck compiled-artifact audit (schema v10;
        see analysis.hlocheck.HloResult.summary)."""
        entry = {"op": op, **summary}
        self.hlocheck.append(entry)
        return entry

    def add_memcheck(self, op: str, summary: dict) -> dict:
        """Record one --memcheck static residency verification
        (schema v16; see analysis.memcheck.MemResult.summary)."""
        entry = {"op": op, **summary}
        self.memcheck.append(entry)
        return entry

    def add_tuning(self, summary: dict) -> dict:
        """Record one --autotune tuning-DB consultation (schema v11;
        see drivers.common.Driver and dplasma_tpu.tuning.consult)."""
        self.tuning.append(summary)
        return summary

    def add_autopilot(self, summary: dict) -> dict:
        """Record one precision-autopilot consultation (schema v17;
        see dplasma_tpu.tuning.autopilot.consult)."""
        self.autopilot.append(summary)
        return summary

    def add_scaling(self, summary: dict) -> dict:
        """Record one op's per-chip-count scaling curve (schema v12;
        see tools/multichip.py)."""
        self.scaling.append(summary)
        return summary

    def add_telemetry(self, summary: dict) -> dict:
        """Record the live-instrument summary (schema v13; see
        observability.telemetry.Telemetry.summary — span ledger,
        exporter provenance, the flight recorder's event ring)."""
        self.telemetry = summary
        return summary

    def add_devprof(self, entry: dict) -> dict:
        """Record one op's measured-timeline attribution (schema v14;
        see observability.devprof.ingest/attribute — category
        seconds, measured-ICI reconciliation, skew/straggler
        attribution, critical path)."""
        self.devprof.append(entry)
        return entry

    def add_admission(self, summary: dict) -> dict:
        """Record the serving overload posture's end-of-run summary
        (schema v15; see serving.admission.AdmissionController.summary
        — servebench --soak adds the ``"audit"`` conservation
        subkey)."""
        self.admission = summary
        return summary

    def stamp_provenance(self, **kw) -> dict:
        """Collect and attach the attribution stamp (schema v18; see
        observability.trend.collect_provenance — git SHA + dirty
        flag, jax/jaxlib versions, platform + mesh shape, peaks
        source, active MCA snapshot, ladder family). Keyword
        arguments pass through (``family=``, ``mesh_shape=``,
        ``peaks_source=``)."""
        from dplasma_tpu.observability.trend import collect_provenance
        self.provenance = collect_provenance(**kw)
        return self.provenance

    def add_roofline(self, entry: dict) -> dict:
        """Record one per-op roofline ledger entry (schema v5; see
        observability.roofline.op_roofline)."""
        self.roofline.append(entry)
        return entry

    def snapshot(self) -> dict:
        env = {}
        try:
            import jax
            env = {"backend": jax.default_backend(),
                   "jax": jax.__version__,
                   "device_count": jax.device_count()}
        except Exception:
            env = {"backend": None, "jax": None, "device_count": None}
        ipd = None
        if self.iparam is not None:
            ipd = {k: v for k, v in
                   dataclasses.asdict(self.iparam).items()
                   if isinstance(v, (int, float, str, bool, type(None)))}
        doc = {"schema": REPORT_SCHEMA, "name": self.name,
               "created_unix_ns": self._t0, "iparam": ipd, "env": env,
               "ops": self.ops, "metrics": self.metrics.snapshot()}
        if self.checks:
            doc["checks"] = self.checks
        if self.resilience:
            doc["resilience"] = self.resilience
        if self.dagcheck:
            doc["dagcheck"] = self.dagcheck
        if self.spmdcheck:
            doc["spmdcheck"] = self.spmdcheck
        if self.refine:
            doc["refine"] = self.refine
        if self.serving:
            doc["serving"] = self.serving
        if self.hlocheck:
            doc["hlocheck"] = self.hlocheck
        if self.memcheck:
            doc["memcheck"] = self.memcheck
        if self.tuning:
            doc["tuning"] = self.tuning
        if self.autopilot:
            doc["autopilot"] = self.autopilot
        if self.scaling:
            doc["scaling"] = self.scaling
        if self.telemetry is not None:
            doc["telemetry"] = self.telemetry
        if self.devprof:
            doc["devprof"] = self.devprof
        if self.admission is not None:
            doc["admission"] = self.admission
        if self.pipeline is not None:
            doc["pipeline"] = self.pipeline
        if self.provenance is not None:
            doc["provenance"] = self.provenance
        if self.roofline:
            doc["roofline"] = self.roofline
        if self.entries:
            doc["entries"] = self.entries
        if self.extra:
            doc["extra"] = self.extra
        return doc

    def write(self, path: str) -> str:
        """Serialize to ``path`` (atomic rename); returns the path."""
        doc = self.snapshot()
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, default=_json_default)
            f.write("\n")
        os.replace(tmp, path)
        return path


def _json_default(o):
    for cast in (float, int):
        try:
            return cast(o)
        except (TypeError, ValueError):
            continue
    return str(o)


def load_report(path: str) -> dict:
    """Read a run-report back; raises on schema mismatch newer than
    this reader.

    Every older vintage (v1-v17) loads: the schema history is purely
    additive, so an old doc is a valid new doc minus the sections its
    writer didn't know about. The always-present keys (``schema``,
    ``ops``, ``metrics``) are filled with safe defaults when absent,
    so consumers (perfdiff, bench) can iterate them unconditionally;
    optional sections stay absent exactly as the writer left them.
    """
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: run-report is not a JSON object")
    if doc.get("schema", 0) > REPORT_SCHEMA:
        raise ValueError(
            f"run-report schema {doc.get('schema')} is newer than "
            f"supported ({REPORT_SCHEMA})")
    doc.setdefault("schema", 1)
    doc.setdefault("ops", [])
    doc.setdefault("metrics", [])
    return doc
