"""The request front-end: ``SolverService`` — submit/future handles,
a batching scheduler, and a per-request resilience ladder.

Requests (``submit(op, A, b) -> SolveFuture``) are grouped by their
executable-cache key (op, shape bucket, dtype, nrhs bucket, grid,
pipeline shape, IR precision — :func:`dplasma_tpu.serving.cache.
make_key`); a group dispatches as ONE batched executable when it
reaches ``serving.max_batch``, when ``serving.max_wait_ms`` expires,
when the caller blocks on a pending future, or on ``flush()``. Results
scatter back per request (each sliced to its exact pre-padding shape)
and are verified: a non-finite census plus a normwise backward-error
gate (and the per-element convergence mask for the IR solvers).

A failed request walks the PR 2 remediation ladder
(:class:`dplasma_tpu.resilience.guard.Ladder`) **individually** —
classify -> retry (a solo re-solve, clean under
``inject.suppressed``, exactly like the driver ladder's retry rung) ->
kernel fallback -> algorithm escalation (posv -> pivoted LU, gesv ->
QR least squares, the IR ops -> their trusted full-precision routes).
Batch-mates are untouched: their futures resolve from the batched
dispatch while the failed request heals on the side.

Fault injection: the serving layer adds a per-request ``"serving"``
tap (:mod:`dplasma_tpu.resilience.inject`) on each scattered result —
the soft-error model for a corrupted response slot, and the hook the
``--inject``/``DPLASMA_INJECT`` e2e path exercises. Kernel-stage taps
(gemm/trsm/...) fire at trace time inside the batched executable; the
cache marks such executables tainted and the service drops them after
dispatch, so retries re-compile clean (the serving analogue of
``inject.disarm`` clearing jax's trace caches).

Telemetry (:mod:`dplasma_tpu.observability.telemetry`): every submit
is stamped with a monotonically increasing ``request_id`` (on the
:class:`SolveFuture`, in ``meta``, and in every ``#+ serving:``
verbose line and remediation stderr note, so a failed batch-mate is
attributable); the always-on tracer records a span tree per request —
``queue_wait`` → ``batch`` (``batch_form``/``cache``/``dispatch``) →
``scatter_gate`` → each ``ladder:<rung>`` — and the flight recorder
keeps a bounded ring of structured events (submits, dispatches, gate
failures, ladder rungs, injections, cache evictions) that is dumped
to MCA ``telemetry.flight_path`` the moment a request fails its gate
and walks the ladder. Live gauges (``serving_queue_depth``,
``serving_inflight_batches``, ``serving_cache_entries``) feed the
streaming Prometheus exporter.

Overload posture (:mod:`dplasma_tpu.serving.admission`): every submit
passes an admission decision inside the same critical section —
queue-depth / inflight caps shed with :class:`AdmissionError`, SLO
pressure degrades IR requests to a cheaper ``ir.precision`` rung —
and each decision lands in the flight ring as an
``admit``/``shed``/``degrade`` event by request id. Requests carry an
optional deadline (``submit(deadline_s=)`` / MCA
``serving.default_deadline_s``) honored at dispatch and between
ladder rungs (:class:`DeadlineExceeded`); the ladder itself consults
a process-global retry budget and a per-(op, rung) circuit breaker,
so a deterministically failing rung is skipped instead of re-failed
per request. ``SolveFuture.result(timeout=)`` raises a structured
:class:`ServingTimeout` naming the request id when the future is
still unresolved at the timeout (e.g. its dispatch thread died) —
a blocked caller never hangs forever.

Conventions: ``A`` is the full matrix (posv reads the lower triangle
of a full symmetric operand); ``b`` may be 1-D (a single right-hand
side — the result is returned 1-D) or ``(n, nrhs)``. The IR ops
require float64 inputs (their contract in :mod:`dplasma_tpu.ops.
refine`).
"""
from __future__ import annotations

import dataclasses
import threading
import time
import types
from typing import Dict, List, Optional, Tuple

import numpy as np

from dplasma_tpu.observability import telemetry as tel_mod
from dplasma_tpu.observability.metrics import Histogram, MetricsRegistry
from dplasma_tpu.resilience import guard, inject
from dplasma_tpu.serving import admission as adm_mod
from dplasma_tpu.serving import batched
from dplasma_tpu.serving import cache as cache_mod
from dplasma_tpu.serving.admission import (AdmissionError,
                                           DeadlineExceeded,
                                           ServingTimeout)
from dplasma_tpu.utils import config as _cfg

_cfg.mca_register(
    "serving.verbose", "0",
    "Verbosity of the SolverService: >=1 prints '#+ serving:' lines "
    "(dispatches, gate failures, ladder rungs) with the request id "
    "every line is attributable to.")
_cfg.mca_register(
    "serving.max_batch", "16",
    "Batching bound of the SolverService scheduler: a compatible "
    "request group dispatches as one batched executable when it "
    "reaches this many requests.")
_cfg.mca_register(
    "serving.max_wait_ms", "5",
    "Batching window of the SolverService scheduler: an incomplete "
    "request group dispatches at most this many milliseconds after "
    "its first request arrived.")
_cfg.mca_register(
    "serving.max_retries", "1",
    "Per-request retry budget of the serving resilience ladder (the "
    "solo re-solve rung; fallback rungs are one-shot on top).")

#: residual gate scale of the per-request verification (check_axmb
#: style: THRESHOLD * eps * n)
_GATE = 60.0

#: serializes the tuning-DB override scope across dispatch threads:
#: the MCA override stack is process-global and strictly LIFO, and
#: _dispatch runs on caller AND timer threads — two concurrent
#: scoped pushes would interleave their pops into RuntimeErrors and
#: leaked overrides. Compiles already serialize under the cache's
#: own lock, so this costs nothing extra on the miss path.
_TUNE_LOCK = threading.Lock()


def percentile(sorted_vals, p: float):
    """Nearest-rank percentile of an ascending list (None when empty)
    — shared by the service summary and tools/servebench.py."""
    if not sorted_vals:
        return None
    k = min(int(round(p / 100.0 * (len(sorted_vals) - 1))),
            len(sorted_vals) - 1)
    return sorted_vals[k]


@dataclasses.dataclass
class _Request:
    op: str
    a: np.ndarray
    b: np.ndarray          # always (n, nrhs)
    vec: bool              # caller passed a 1-D b
    n: int
    nrhs: int
    future: "SolveFuture"
    t_submit: float
    kwargs: dict
    rid: int = 0           # the stamped request id
    t_submit_ns: int = 0   # wall-clock twin of t_submit (tracing)
    deadline: float = 0.0  # absolute perf_counter expiry; 0 = none
    autopilot: Optional[dict] = None  # precision pre-flight decision


class SolveFuture:
    """Handle for one submitted solve. ``result()`` drives the
    scheduler if the request is still pending (a blocked caller is a
    latency bound, not a deadlock), then returns the solution;
    ``request_id`` is the service-stamped monotone id every telemetry
    span, flight-recorder event, and verbose/stderr line about this
    request carries; ``meta`` carries latency, batch, verification,
    and the resilience summary when the request walked the ladder."""

    def __init__(self, service: "SolverService", group):
        self._service = service
        self._group = group
        self._event = threading.Event()
        # makes "first to settle" atomic: a racing resolve/fail pair
        # must count once on the conservation ledger
        self._settle_lock = threading.Lock()
        self._value = None
        self._error: Optional[BaseException] = None
        self.request_id: int = 0
        self.meta: dict = {}

    def done(self) -> bool:
        return self._event.is_set()

    def _resolve(self, value, meta: dict) -> None:
        with self._settle_lock:
            first = not self._event.is_set()
            self._value = value
            self.meta.update(meta)
            self._event.set()
        if first:
            # the conservation ledger: every admitted request resolves
            # exactly once (value or error) — the soak audit's
            # submitted == resolved + shed side
            self._service.metrics.counter(
                "serving_resolved_total").inc()

    def _fail(self, exc: BaseException) -> None:
        with self._settle_lock:
            first = not self._event.is_set()
            self._error = exc
            self._event.set()
        if first:
            self._service.metrics.counter(
                "serving_resolved_total").inc()

    def result(self, timeout: Optional[float] = None):
        if not self._event.is_set():
            self._service._drive(self._group)
        if not self._event.wait(timeout):
            # structured and attributable: the caller learns WHICH
            # request is stuck (a dead dispatch thread, a wedged
            # compile) instead of hanging forever on the bare event
            raise ServingTimeout(
                f"request {self.request_id} still pending after "
                f"{timeout:g}s (solve not dispatched or dispatch "
                f"thread died)", request_id=self.request_id)
        if self._error is not None:
            raise self._error
        return self._value


class SolverService:
    """Batched solver-as-a-service front-end (module docstring).

    ``nb`` is the tile size every batched sweep runs at (one compiled
    program per cache key); ``check=False`` disables the per-request
    verification gate (dispatch-rate benchmarking — the resilience
    ladder needs the gate on).
    """

    def __init__(self, nb: int = 8, *, max_batch: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 cache: Optional[cache_mod.ExecutableCache] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 max_retries: Optional[int] = None, check: bool = True,
                 telemetry: Optional[tel_mod.Telemetry] = None,
                 verbose: Optional[int] = None):
        self.nb = int(nb)
        self.max_batch = max(
            max_batch if max_batch is not None
            else _cfg.mca_get_int("serving.max_batch", 16), 1)
        if max_wait_ms is None:
            try:
                max_wait_ms = float(
                    _cfg.mca_get("serving.max_wait_ms", "5"))
            except ValueError:
                max_wait_ms = 5.0
        self.max_wait_ms = max(float(max_wait_ms), 0.0)
        self.max_retries = max(
            max_retries if max_retries is not None
            else _cfg.mca_get_int("serving.max_retries", 1), 0)
        self.metrics = metrics if metrics is not None \
            else MetricsRegistry()
        self.cache = cache if cache is not None \
            else cache_mod.ExecutableCache(metrics=self.metrics)
        self.check = bool(check)
        # the live instruments: always-on span tracer + flight
        # recorder (module docstring); cache evictions/invalidations
        # land in the same flight ring
        self.telemetry = telemetry if telemetry is not None \
            else tel_mod.Telemetry()
        self.cache.recorder = self.telemetry.flight
        # the overload posture: admission decisions, the SLO tracker,
        # circuit breakers, and the global retry budget (MCA
        # serving.* knobs; decisions/transitions land in the flight
        # ring by request id)
        self.admission = adm_mod.AdmissionController(
            metrics=self.metrics, flight=self.telemetry.flight)
        self.verbose = int(verbose) if verbose is not None \
            else _cfg.mca_get_int("serving.verbose", 0)
        self.resilience: List[dict] = []   # ladder summaries
        # per-cache-key tuning-DB consultation memo (the serving face
        # of dplasma_tpu.tuning: resolved ONCE per key so the same key
        # always compiles the same knobs; MCA tune.serving=off or no
        # DB -> every value is None)
        self._tuning: Dict[cache_mod.CacheKey, Optional[dict]] = {}
        self._pending: Dict[tuple, List[_Request]] = {}
        # (op, n, nrhs, dtype, kwargs) -> CacheKey memo: the key
        # context (grid, pipeline shape, ir precision, bucket policy)
        # is captured when a request shape is first seen — retune MCA
        # knobs, construct a new service
        self._keys: Dict[tuple, cache_mod.CacheKey] = {}
        self._timers: Dict[tuple, threading.Timer] = {}
        self._lock = threading.RLock()
        self._latencies: List[float] = []
        self._batches = 0
        self._requests = 0
        self._next_rid = 0      # monotone request-id stamp
        self._queued = 0        # live queue depth (gauge)
        self._inflight = 0      # live in-flight batches (gauge)

    # ------------------------------------------------------ submission
    def submit(self, op: str, A, b,
               deadline_s: Optional[float] = None,
               **kwargs) -> SolveFuture:
        """Queue one solve ``op(A) x = b``; returns a future. The
        request first passes admission: a shed raises
        :class:`AdmissionError` (the request id it carries matches
        the flight-recorder ``shed`` event), a degrade re-keys an IR
        request onto the next-cheaper ``ir.precision`` executable.
        ``deadline_s`` (default MCA ``serving.default_deadline_s``)
        bounds the request end to end: expired requests fail with
        :class:`DeadlineExceeded` instead of paying for a solve."""
        if op not in ("posv", "gesv", "posv_ir", "gesv_ir"):
            raise ValueError(f"unservable op {op!r}")
        a = np.asarray(A)
        bb = np.asarray(b)
        vec = bb.ndim == 1
        if vec:
            bb = bb[:, None]
        if a.ndim != 2 or a.shape[0] != a.shape[1]:
            raise ValueError(f"A must be (n, n), got {a.shape}")
        if bb.ndim != 2 or bb.shape[0] != a.shape[0]:
            raise ValueError(f"b {bb.shape} does not match A {a.shape}")
        if a.dtype != bb.dtype:
            raise TypeError(f"A ({a.dtype}) and b ({bb.dtype}) must "
                            "share a dtype")
        if op.endswith("_ir") and np.dtype(a.dtype).name != "float64":
            raise TypeError(f"{op} refines to f64-equivalent accuracy: "
                            f"inputs must be float64, got {a.dtype}")
        n, nrhs = a.shape[0], bb.shape[1]
        extra = tuple(sorted(kwargs.items()))
        memo = (op, n, nrhs, a.dtype.str, extra)
        deadline = adm_mod.resolve_deadline(deadline_s)
        # precision-autopilot pre-flight (IR ops, concrete matrix in
        # hand): condest sketch -> cond class -> stored rung. Runs
        # BEFORE the lock — O(n^2) host matvecs must not serialize
        # submission — and folds into the memo/cache key below so each
        # rung compiles its own executable.
        ap = self._autopilot_for(op, a) if op.endswith("_ir") else None
        ap_prec = (ap or {}).get("precision")
        dispatch_now = None
        degrade_prec: Optional[str] = None
        # one critical section per submit: the admission decision, the
        # key memo (the _tuning_for discipline — two threads racing
        # the same new shape must memoize exactly one key), the queue
        # mutation, and the gauge publish are all cheap host work,
        # cheap enough to hold the lock across
        with self._lock:
            decision, reason = self.admission.decide(
                op, self._queued, self._inflight)
            self._next_rid += 1
            rid = self._next_rid
            if decision == adm_mod.SHED:
                queued = self._queued
            else:
                if decision == adm_mod.DEGRADE:
                    # the cheaper-precision executable is a DIFFERENT
                    # program: its own memo slot and cache key (the
                    # key's precision field pins the compile in _run).
                    # An overload degrade outranks the autopilot — it
                    # is a load-shedding decision, not a tuning one.
                    degrade_prec = adm_mod.degraded_precision()
                    memo = memo + (("degrade", degrade_prec),)
                elif ap_prec:
                    # the autopilot's rung lands in the cache key the
                    # same way: per-rung memo slot, precision-pinned
                    # compile in _run
                    memo = memo + (("autopilot", ap_prec),)
                key = self._keys.get(memo)
                if key is None:
                    key = cache_mod.make_key(
                        op, n, a.dtype, 1, nrhs, extra=extra,
                        precision=(degrade_prec if degrade_prec
                                   else ap_prec))
                    self._keys[memo] = key
                group = key._replace(batch=0)  # batch bucket set at
                fut = SolveFuture(self, group)  # dispatch
                req = _Request(op=op, a=a, b=bb, vec=vec, n=n,
                               nrhs=nrhs, future=fut,
                               t_submit=time.perf_counter(),
                               kwargs=dict(kwargs),
                               t_submit_ns=time.time_ns(),
                               deadline=deadline, autopilot=ap)
                self._requests += 1
                req.rid = fut.request_id = rid
                self.metrics.counter("serving_requests_total",
                                     op=op).inc()
                lst = self._pending.setdefault(group, [])
                lst.append(req)
                self._queued += 1
                if len(lst) >= self.max_batch:
                    dispatch_now = self._pending.pop(group)
                    self._queued -= len(dispatch_now)
                    self._cancel_timer(group)
                elif len(lst) == 1 and self.max_wait_ms > 0:
                    t = threading.Timer(self.max_wait_ms / 1000.0,
                                        self._drive, args=(group,))
                    t.daemon = True
                    self._timers[group] = t
                    t.start()
                # published under the lock, like _drive's update: a
                # gauge set after release could land out of order
                # against a racing submit and stick a stale depth in
                # the exporter
                self.metrics.gauge("serving_queue_depth").set(
                    self._queued)
        if decision == adm_mod.SHED:
            self.telemetry.flight.record("shed", request=rid, op=op,
                                         reason=reason, queued=queued)
            self.telemetry.tracer.instant("shed", request=rid, op=op)
            if self.verbose >= 1:
                print(f"#+ serving: req={rid} SHED ({reason})",
                      flush=True)
            raise AdmissionError(f"request {rid} shed: {reason}",
                                 request_id=rid, reason=reason)
        self.telemetry.flight.record("submit", request=rid, op=op,
                                     n=n, nrhs=nrhs)
        if ap is not None:
            self.telemetry.flight.record(
                "autopilot", request=rid, op=op,
                precision=ap_prec, cond_class=ap["cond_class"],
                source=ap["source"])
            self.metrics.counter("serving_autopilot_consults_total",
                                 source=ap["source"]).inc()
            if self.verbose >= 1:
                print(f"#+ serving: req={rid} autopilot "
                      f"cond_class={ap['cond_class']} "
                      f"ir.precision={ap_prec or 'ambient'} "
                      f"({ap['source']})", flush=True)
        if decision == adm_mod.DEGRADE:
            self.telemetry.flight.record(
                "degrade", request=rid, op=op,
                precision=degrade_prec, reason=reason)
            if self.verbose >= 1:
                print(f"#+ serving: req={rid} DEGRADED to "
                      f"ir.precision={degrade_prec} ({reason})",
                      flush=True)
        else:
            self.telemetry.flight.record("admit", request=rid, op=op)
        if dispatch_now:
            self._dispatch(group, dispatch_now)
        return fut

    def _cancel_timer(self, group) -> None:
        t = self._timers.pop(group, None)
        if t is not None:
            t.cancel()

    def _drive(self, group) -> None:
        """Dispatch one group now (timer fired / caller blocked)."""
        with self._lock:
            reqs = self._pending.pop(group, None)
            self._cancel_timer(group)
            if reqs:
                self._queued -= len(reqs)
                self.metrics.gauge("serving_queue_depth").set(
                    self._queued)
        if reqs:
            self._dispatch(group, reqs)

    def flush(self) -> None:
        """Dispatch every pending group."""
        while True:
            with self._lock:
                if not self._pending:
                    return
                group = next(iter(self._pending))
            self._drive(group)

    def close(self) -> None:
        self.flush()
        with self._lock:
            for t in self._timers.values():
                t.cancel()
            self._timers.clear()
        self.telemetry.close()     # final exporter flush, if running

    # -------------------------------------------------------- dispatch
    def _stack(self, key: cache_mod.CacheKey, reqs: List[_Request]):
        """Assemble a bucket-shaped (As, bs) pair: identity everywhere
        first, so the overwritten top-left block leaves exactly the
        identity shape-padding (cache.pad_problem semantics) and empty
        batch slots carry whole identity problems — host-side numpy,
        no per-request device dispatches."""
        nB, rB, Bc = key.n, key.nrhs, key.batch
        dt = np.dtype(key.dtype)
        As = np.zeros((Bc, nB, nB), dt)
        bs = np.zeros((Bc, nB, rB), dt)
        idx = np.arange(nB)
        As[:, idx, idx] = 1.0
        for i, r in enumerate(reqs):
            As[i, :r.n, :r.n] = r.a
            bs[i, :r.n, :r.nrhs] = r.b
        return As, bs

    def _builder(self, key: cache_mod.CacheKey, kwargs: dict,
                 nb: Optional[int] = None):
        """The ONE executable body both the batched and the solo paths
        compile: solve + in-executable backward errors. ``nb``
        overrides the service tile size (the tuning-DB consultation's
        per-key winner)."""
        nb, op, kw = (nb or self.nb), key.op, dict(kwargs)

        def build():
            def fn(a, b):
                x, info = batched.solve_batched(op, a, b, nb, **kw)
                bwd = batched.backward_errors(a, b, x)
                return (x, bwd, info) if info is not None \
                    else (x, bwd)
            return fn
        return build

    def _tuning_for(self, key: cache_mod.CacheKey) -> Optional[dict]:
        """Resolve the tuning-DB consultation for one cache key
        (memoized — a key must always compile the same knobs): the
        per-op-class winner at this shape bucket, filtered by the
        precedence contract (:func:`dplasma_tpu.tuning.appliable`).
        None when no DB is configured or MCA ``tune.serving`` is
        off."""
        from dplasma_tpu.observability.comm import OP_CLASS
        from dplasma_tpu.tuning import db as tdb
        # the whole check-consult-store runs under the lock so
        # concurrent dispatch threads (caller + timer) racing the same
        # new key consult exactly once — the memo IS the "a key always
        # compiles the same knobs" invariant, and the consult counter
        # must agree with it
        with self._lock:
            if key in self._tuning:
                return self._tuning[key]
            tune = None
            if _cfg.mca_get("tune.serving", "on") != "off" \
                    and tdb.db_path():
                op = OP_CLASS.get(key.op, key.op)
                entry, source, tkey, _path = tdb.consult(
                    op, key.n, key.dtype, key.grid)
                if entry is not None \
                        and isinstance(entry.get("knobs"), dict):
                    knobs = entry["knobs"]
                    nb = knobs.get("nb")
                    tune = {"key": tkey, "source": source,
                            "applied": tdb.appliable(knobs),
                            "nb": (min(int(nb), key.n)
                                   if isinstance(nb, int) and nb > 0
                                   else None)}
                self.metrics.counter(
                    "serving_tuning_consults_total",
                    source=(tune or {}).get("source", "default")).inc()
            self._tuning[key] = tune
            return tune

    def _autopilot_for(self, op: str, a: np.ndarray) -> Optional[dict]:
        """Precision-autopilot pre-flight of one concrete IR request
        (:mod:`dplasma_tpu.tuning.autopilot`): condest sketch ->
        cond-class bucket -> the stored cheapest-converging rung for
        ``(op, n, dtype, cond_class)``. None when the autopilot is off,
        no DB is configured, or serving tuning is disabled. Failures
        degrade to None — a broken pre-flight must never fail a
        submit."""
        from dplasma_tpu.tuning import autopilot as ap_mod
        if _cfg.mca_get("tune.serving", "on") == "off":
            return None
        try:
            return ap_mod.consult(op, a.shape[0], a.dtype, a,
                                  spd=(op == "posv_ir"))
        except Exception as exc:
            import sys
            sys.stderr.write(f"#! serving: autopilot pre-flight "
                             f"failed ({exc!r}); ambient rung\n")
            return None

    def _autopilot_writeback(self, key: cache_mod.CacheKey,
                             r: _Request) -> None:
        """The negative write-back: this request's IR solve escalated
        at runtime, so the rung that ran it is insufficient for its
        cond class — record the next-stronger rung so the DB
        converges. Serialized under the service lock (load-modify-save
        of the JSON document)."""
        from dplasma_tpu.ops.refine import ir_params
        from dplasma_tpu.tuning import autopilot as ap_mod
        ap = r.autopilot
        ran = key.precision or ir_params()[0]
        try:
            with self._lock:
                ap_mod.record_escalation(
                    r.op, r.n, r.a.dtype, ap["cond_class"], ran,
                    cond_estimate=ap.get("cond_estimate"))
        except Exception as exc:
            import sys
            sys.stderr.write(f"#! serving: autopilot write-back "
                             f"failed ({exc!r})\n")
            return
        self.metrics.counter(
            "serving_autopilot_escalations_total", op=r.op).inc()
        self.telemetry.flight.record(
            "autopilot_writeback", request=r.rid, op=r.op,
            precision=ran, cond_class=ap["cond_class"])
        if self.verbose >= 1:
            print(f"#+ serving: req={r.rid} autopilot write-back "
                  f"(rung {ran} escalated, class "
                  f"{ap['cond_class']})", flush=True)

    def _run(self, key: cache_mod.CacheKey, reqs: List[_Request]):
        """Compile-or-hit + dispatch one bucket-shaped batch; returns
        (X, bwds, info, cache_hit). The tuning-DB consultation's knobs
        scope the compile (a cache hit never re-traces, so the
        overrides only matter on a miss — and the memoized
        consultation keeps them identical per key). Tainted entries
        (compiled while a fault plan fired — poisoned for life) are
        dropped so any retry re-compiles clean."""
        import jax.numpy as jnp
        tracer = self.telemetry.tracer
        with tracer.span("batch_form", op=key.op, batch=len(reqs)):
            As, bs = self._stack(key, reqs)
            Aj, bj = jnp.asarray(As), jnp.asarray(bs)  # ONE transfer
        with tracer.span("cache", op=key.op) as cattrs:
            # probed ONCE; the span attr, the flight event, and the
            # verbose line all reuse this answer (a racing eviction
            # between two probes would make them disagree)
            hit = cattrs["hit"] = key in self.cache
            tune = self._tuning_for(key)
            builder = self._builder(key, reqs[0].kwargs,
                                    nb=tune["nb"] if tune else None)
            overrides = dict(tune["applied"]) \
                if tune and tune["applied"] else {}
            if key.precision and key.op.endswith("_ir"):
                # pin the compile to the key's precision: key and
                # executable must agree even when the key carries a
                # degraded (admission-layer) rung instead of the
                # ambient ir.precision
                overrides["ir.precision"] = key.precision
            if overrides:
                # the override scope is process-global and LIFO: hold
                # _TUNE_LOCK for the whole push..pop so concurrent
                # dispatch threads never interleave their frames
                with _TUNE_LOCK, \
                        _cfg.override_scope(overrides,
                                            label="serving-tune"):
                    entry = self.cache.get(key, builder, Aj, bj)
            else:
                entry = self.cache.get(key, builder, Aj, bj)
        with tracer.span("dispatch", op=key.op, batch=len(reqs)):
            out = entry.fn(Aj, bj)
            res = (np.asarray(out[0]), np.asarray(out[1]),
                   out[2] if len(out) > 2 else None, hit)
        if entry.tainted:
            self.cache.invalidate(key)
        return res

    def _expire(self, r: _Request, where: str,
                fail_future: bool = True) -> None:
        """Account one expired deadline (counter + flight event +
        timeline marker, all by request id); optionally fail the
        future with the structured :class:`DeadlineExceeded`."""
        self.metrics.counter("serving_deadline_expired_total").inc()
        self.telemetry.flight.record("deadline_expired",
                                     request=r.rid, op=r.op,
                                     where=where)
        self.telemetry.tracer.instant("deadline_expired",
                                      request=r.rid, where=where)
        if self.verbose >= 1:
            print(f"#+ serving: req={r.rid} deadline expired at "
                  f"{where}", flush=True)
        if fail_future:
            r.future._fail(DeadlineExceeded(
                f"request {r.rid} deadline expired at {where}",
                request_id=r.rid))

    def _dispatch(self, group, reqs: List[_Request]) -> None:
        import jax.numpy as jnp
        tracer = self.telemetry.tracer
        # queue-wait spans close here, retroactively: the wait ended
        # the moment this dispatch picked the group up
        now_ns = time.time_ns()
        for r in reqs:
            # no attrs: the request's op is on its submit event, and
            # this add() runs per request on the always-on hot path
            tracer.add("queue_wait", r.t_submit_ns, now_ns,
                       request=r.rid)
        # deadline gate: a request that expired waiting in the queue
        # fails fast HERE, before anyone pays to solve it (and before
        # the batch bucket is sized, so the survivors compile small)
        now = time.perf_counter()
        expired = [r for r in reqs if r.deadline and now > r.deadline]
        if expired:
            for r in expired:
                self._expire(r, where="dispatch")
            reqs = [r for r in reqs
                    if not (r.deadline and now > r.deadline)]
            if not reqs:
                return
        key = group._replace(batch=cache_mod.bucket_batch(len(reqs)))
        rids = [r.rid for r in reqs]
        with self._lock:
            self._inflight += 1
            self.metrics.gauge("serving_inflight_batches").set(
                self._inflight)
        try:
            with tracer.span("batch", op=key.op, requests=rids,
                             batch=len(reqs)) as battrs:
                try:
                    X, bwds, info, hit = self._run(key, reqs)
                    battrs["cached"] = hit
                except Exception as exc:   # compile/dispatch failure:
                    for r in reqs:         # every request fails loudly
                        r.future._fail(exc)
                    self.telemetry.flight.record(
                        "dispatch_error", op=key.op, requests=rids,
                        error=repr(exc))
                    raise
                self.telemetry.flight.record(
                    "dispatch", op=key.op, batch=len(reqs),
                    requests=rids,
                    bucket=[key.n, key.nrhs, key.batch],
                    cache="hit" if hit else "miss")
                if self.verbose >= 1:
                    print(f"#+ serving: dispatch op={key.op} "
                          f"batch={len(reqs)} "
                          f"bucket=({key.n},{key.nrhs},{key.batch}) "
                          f"reqs={rids} "
                          f"cache={'hit' if hit else 'miss'}",
                          flush=True)
                with self._lock:
                    self._batches += 1
                self.metrics.counter("serving_batches_total").inc()
                self.metrics.histogram("serving_batch_size").observe(
                    len(reqs))
                first_exc: Optional[BaseException] = None
                failed_rids: List[int] = []
                for i, r in enumerate(reqs):
                    # per-request isolation: a raising remediation (the
                    # solo recompile, an escalation route) must fail
                    # THIS future only — the remaining batch-mates
                    # still resolve, and no caller blocks forever on
                    # an unresolved future
                    try:
                        self._scatter_one(key, reqs, r, i, X, bwds,
                                          info, jnp)
                    except Exception as exc:
                        r.future._fail(exc)
                        first_exc = first_exc or exc
                        failed_rids.append(r.rid)
                if first_exc is not None:
                    # delivered to the owning futures above; do NOT
                    # re-raise — dispatch may be running inside an
                    # INNOCENT batch-mate's result()/submit() call (or
                    # a timer thread), and a foreign request's failure
                    # must not surface there. One stderr note (request
                    # ids named) so timer-thread failures aren't
                    # invisible or unattributable.
                    import sys
                    sys.stderr.write(
                        f"#! serving: {len(failed_rids)} request(s) "
                        f"failed in dispatch "
                        f"(reqs={failed_rids}): {first_exc!r}\n")
        finally:
            with self._lock:
                self._inflight -= 1
                self.metrics.gauge("serving_inflight_batches").set(
                    self._inflight)

    def _scatter_one(self, key, reqs: List[_Request], r: _Request,
                     i: int, X, bwds, info, jnp) -> None:
        """Scatter + gate + (if needed) remediate ONE request of a
        dispatched batch, resolving its future."""
        tracer = self.telemetry.tracer
        with tracer.span("scatter_gate", request=r.rid,
                         op=r.op) as gattrs:
            x = X[i, :r.n, :r.nrhs]
            rejected = False
            if inject.armed():
                # per-request response tap (module docstring) — only
                # pay the round-trip while a plan is live. A 'reject'
                # fault raises here: treated as a failed response (not
                # a raw future failure) so it walks the ladder below
                nfaults0 = len(inject.faults())
                try:
                    x = np.asarray(
                        inject.tap("serving", jnp.asarray(x)))
                except inject.InjectedReject:
                    rejected = True
                if len(inject.faults()) > nfaults0:
                    self.telemetry.flight.record(
                        "inject", request=r.rid, op=r.op,
                        fault=inject.faults()[-1])
            meta = {"request_id": r.rid, "batch": len(reqs),
                    "batched": True,
                    "bucket": (key.n, key.nrhs, key.batch)}
            if info is not None:
                meta["refine"] = self._refine_meta(info, i)
                if r.autopilot is not None:
                    meta["autopilot"] = r.autopilot
                    # the batched executables run with in-executable
                    # escalation OFF (batched.py: a lax.cond under
                    # vmap would charge the whole batch), so the
                    # rung-failed verdict is non-convergence — the
                    # remediation ladder does the actual escalating,
                    # this records it so the DB converges
                    if (meta["refine"].get("escalated")
                            or not meta["refine"].get(
                                "converged", True)):
                        self._autopilot_writeback(key, r)
            if rejected:
                # no response to verify — synthesize a failing health
                # record and go straight to remediation
                health = {"nan": 0, "inf": 0, "leaves": 1, "ok": False}
                ok, verdict = False, {"ok": False,
                                      "error": "injected reject"}
            else:
                ok, health, verdict = self._verify(
                    r, x, meta.get("refine"),
                    bwd=None if inject.armed() else float(bwds[i]))
            meta.update(verdict)
            gattrs["ok"] = bool(ok)
        if not ok:
            self.telemetry.flight.record(
                "gate_fail", request=r.rid, op=r.op, verdict=verdict,
                health={k: health[k] for k in ("nan", "inf", "ok")})
            if self.verbose >= 1:
                print(f"#+ serving: req={r.rid} gate FAILED "
                      f"verdict={verdict} -> remediation ladder",
                      flush=True)
            if r.deadline and time.perf_counter() > r.deadline:
                # nobody is waiting anymore: fail fast instead of
                # paying for a ladder walk
                self._expire(r, where="ladder")
                return
            x, meta = self._remediate(r, x, health, meta,
                                      batch_key=key)
        # latency is the user-visible submit->resolve span, INCLUDING
        # any remediation walk this request took
        lat = time.perf_counter() - r.t_submit
        meta["latency_s"] = lat
        with self._lock:
            self._latencies.append(lat)
        self.metrics.histogram("serving_latency_s").observe(lat)
        # feed the admission SLO tracker from the telemetry histogram
        # (EWMA-smoothed p99 — the shed/degrade pressure signal)
        self.admission.observe(
            lat, self.metrics.histogram("serving_latency_s"))
        r.future._resolve(x[:, 0] if r.vec else x, meta)

    @staticmethod
    def _refine_meta(info, i: int) -> dict:
        hist = [float(v) for v in np.asarray(info["backward_errors"])[i]
                if v >= 0]
        return {"converged": bool(np.asarray(info["converged"])[i]),
                "escalated": bool(np.asarray(info["escalated"])[i]),
                "iterations": int(np.asarray(info["iterations"])[i]),
                "backward_errors": hist}

    # ---------------------------------------------------- verification
    def _verify(self, r: _Request, x: np.ndarray,
                refine_meta: Optional[dict], bwd: Optional[float] = None
                ) -> Tuple[bool, dict, dict]:
        """Per-request health gate: non-finite census + normwise
        backward error (and the IR convergence verdict). ``bwd`` is
        the error the batched executable computed in-line
        (:func:`serving.batched.backward_errors`); recomputed on the
        host when absent (remediation rungs) or when a fault plan is
        armed (the serving tap corrupts AFTER the executable measured
        its error — the gate must see the corruption)."""
        bad = int(np.size(x) - np.isfinite(x).sum())
        health = {"nan": int(np.isnan(x).sum()),
                  "inf": bad - int(np.isnan(x).sum()),
                  "leaves": 1, "ok": bad == 0}
        if not self.check:
            return health["ok"], health, {"ok": health["ok"]}
        verdict: dict = {}
        ok = health["ok"]
        if ok:
            if bwd is None:
                res = r.b - r.a @ x
                den = (max(np.max(np.abs(r.a)), 1.0)
                       * np.max(np.abs(x)) + np.max(np.abs(r.b)))
                tiny = float(np.finfo(r.a.dtype).tiny)
                bwd = float(np.max(np.abs(res)) / max(den, tiny))
            verdict["backward_error"] = float(bwd)
            gate = _GATE * float(np.finfo(r.a.dtype).eps) * r.n
            if refine_meta is not None:
                # the convergence mask was measured INSIDE the
                # executable, before the response left it — a
                # corrupted-in-flight (finite-but-wrong) IR response
                # must still fail the host-side residual gate
                ok = (refine_meta["converged"] and np.isfinite(bwd)
                      and bwd <= gate)
            else:
                ok = bwd <= gate
        verdict["ok"] = bool(ok)
        return bool(ok), health, verdict

    # ----------------------------------------------------- remediation
    def _solo_key(self, r: _Request) -> cache_mod.CacheKey:
        return cache_mod.make_key(
            r.op, r.n, r.a.dtype, 1, r.nrhs,
            extra=tuple(sorted(r.kwargs.items())))

    def _solo(self, r: _Request):
        """The retry rung: re-solve this one request alone (batch
        bucket 1) through the same stack/build path as the batched
        dispatch — a fresh executable when the batched one was dropped
        as tainted."""
        X, _bwds, info, _hit = self._run(self._solo_key(r), [r])
        return X[0, :r.n, :r.nrhs], (
            self._refine_meta(info, 0) if info is not None else None)

    def _escalate(self, r: _Request):
        """The algorithm-escalation rung: the trusted unbatched route
        — posv -> pivoted LU, gesv -> QR least squares, the IR ops ->
        their full-precision f64-equivalent solvers (exactly the
        escape :mod:`dplasma_tpu.ops.refine` wires internally)."""
        from dplasma_tpu.descriptors import TileMatrix
        from dplasma_tpu.ops import lu as lu_mod
        from dplasma_tpu.ops import potrf as potrf_mod
        from dplasma_tpu.ops import qr as qr_mod
        At = TileMatrix.from_dense(r.a, self.nb, self.nb)
        Bt = TileMatrix.from_dense(r.b, self.nb, self.nb)
        if r.op == "posv":
            _, _, X = lu_mod.gesv_1d(At, Bt)
        elif r.op == "gesv":
            X = qr_mod.gels(At, Bt)
        elif r.op == "posv_ir":
            _, X = potrf_mod.posv(At, Bt, "L")
        else:   # gesv_ir
            _, _, X = lu_mod.gesv_1d(At, Bt)
        return np.asarray(X.to_dense())[:r.n, :r.nrhs], None

    def _remediate(self, r: _Request, x: np.ndarray, health: dict,
                   meta: dict,
                   batch_key: Optional[cache_mod.CacheKey] = None
                   ) -> Tuple[np.ndarray, dict]:
        """Walk the PR 2 ladder for ONE request (classify -> retry ->
        kernel fallback -> algorithm escalation); batch-mates are
        never re-dispatched."""
        ip = types.SimpleNamespace(max_retries=self.max_retries,
                                   inject=None, abft=False,
                                   run_timeout=0.0)
        ladder = guard.Ladder(ip, r.op, fallbacks=[
            (f"{r.op}_escalate", self._escalate)])
        cls = ladder.classify(health, None, False)
        ladder.record(guard.ACTION_PRIMARY, f"batched[{meta['batch']}]",
                      ok=False, classification=cls, health=health)
        self.metrics.counter("serving_faults_total", op=r.op).inc()
        tracer = self.telemetry.tracer
        while True:
            if r.deadline and time.perf_counter() > r.deadline:
                # the walk is bounded by the request deadline: account
                # the expiry and surface DeadlineExceeded through the
                # dispatch isolation (which fails THIS future only)
                ladder.record("deadline", "deadline", ok=False,
                              classification=cls,
                              error="deadline expired mid-ladder")
                with self._lock:
                    self.resilience.append(
                        ladder.summary(injection=None))
                self._expire(r, where="ladder", fail_future=False)
                raise DeadlineExceeded(
                    f"request {r.rid} deadline expired mid-ladder",
                    request_id=r.rid)
            nxt = ladder.next_action(cls)
            if nxt is None:
                break
            action, label, fn = nxt
            if not self.admission.breaker_allow(r.op, action,
                                               request=r.rid):
                # the (op, rung) breaker is open: a rung that failed
                # serving.breaker_failures times in a row is skipped,
                # not re-failed per request — a poisoned executable
                # cannot consume the service
                ladder.record(action, label, ok=False,
                              classification=cls,
                              error="breaker open")
                if self.verbose >= 1:
                    print(f"#+ serving: req={r.rid} ladder rung "
                          f"{action}:{label} skipped (breaker open)",
                          flush=True)
                continue
            if action == guard.ACTION_RETRY \
                    and not self.admission.take_retry():
                # process-global retry budget exhausted: fall through
                # to the fallback rungs instead of multiplying load
                ladder.record(action, label, ok=False,
                              classification=cls,
                              error="retry budget exhausted")
                if self.verbose >= 1:
                    print(f"#+ serving: req={r.rid} ladder rung "
                          f"{action}:{label} skipped (retry budget "
                          f"exhausted)", flush=True)
                continue
            if action == guard.ACTION_KERNEL_FALLBACK:
                guard.kernel_fallback()
                # the demotion changes what a fresh trace compiles,
                # but not the cache keys: drop the solo executable the
                # retry rung cached so this rung actually re-traces on
                # the demoted kernel set, AND the batched executable
                # this request came from — otherwise every future
                # batch under that key replays the distrusted program
                # and walks the ladder forever
                self.cache.invalidate(self._solo_key(r))
                if batch_key is not None:
                    self.cache.invalidate(batch_key)
            if action == guard.ACTION_RETRY:
                self.metrics.counter("serving_retries_total",
                                     op=r.op).inc()
            if action == guard.ACTION_ALGO_FALLBACK:
                self.metrics.counter("serving_escalations_total",
                                     op=r.op).inc()
            # remediation runs clean, like the driver ladder's rungs
            # (a transient fault does not recur on recompute)
            try:
                with tracer.span(f"ladder:{action}", request=r.rid,
                                 op=r.op, label=label) as lattrs:
                    with inject.suppressed():
                        if fn is not None:
                            x2, rmeta = fn(r)
                        else:
                            x2, rmeta = self._solo(r)
                    ok2, health2, verdict2 = self._verify(r, x2, rmeta)
                    lattrs["ok"] = bool(ok2)
            except Exception:
                # a RAISING rung is a failure the breaker must see
                # (the exception still propagates to the dispatch
                # isolation, failing this future only)
                self.admission.breaker_record(r.op, action, False,
                                              request=r.rid)
                raise
            self.admission.breaker_record(r.op, action, bool(ok2),
                                          request=r.rid)
            self.telemetry.flight.record(
                "ladder", request=r.rid, op=r.op, action=action,
                label=label, ok=bool(ok2))
            if self.verbose >= 1:
                print(f"#+ serving: req={r.rid} ladder rung "
                      f"{action}:{label} "
                      f"{'ok' if ok2 else 'failed'}", flush=True)
            ladder.record(action, label, ok2,
                          classification=None if ok2
                          else ladder.classify(health2, None, False),
                          health=health2)
            if ok2:
                ladder.winner = label
                x = x2
                meta.update(verdict2)
                if rmeta is not None:
                    meta["refine"] = rmeta
                break
            cls = ladder.classify(health2, None, False)
        summary = ladder.summary(injection=None)
        meta["resilience"] = summary
        meta["ok"] = summary["outcome"] != "failed"
        with self._lock:
            self.resilience.append(summary)
        if summary["outcome"] == "failed":
            self.metrics.counter("serving_failed_total", op=r.op).inc()
        self.telemetry.flight.record(
            "remediation", request=r.rid, op=r.op,
            outcome=summary["outcome"], winner=summary["winner"],
            attempts=len(summary["attempts"]))
        if self.verbose >= 1:
            print(f"#+ serving: req={r.rid} remediation outcome="
                  f"{summary['outcome']} winner={summary['winner']}",
                  flush=True)
        # the incident carries its own evidence: a request that failed
        # its gate and walked the ladder dumps the flight ring to disk
        # (MCA telemetry.flight_path; empty = in-memory only, the ring
        # still lands in the run-report's telemetry section)
        dump_path = self.telemetry.flight_dump_path()
        if dump_path:
            self.telemetry.flight.dump(dump_path)
        return x, meta

    # --------------------------------------------------------- summary
    def reset_stats(self) -> None:
        """Zero the request/batch/latency/remediation records (the
        cache and its counters stay): benches call this after a
        warmup pass so the summary covers measured traffic only —
        a warmup compile latency is not service latency. The
        telemetry instruments reset with them (warmup spans/events
        and warmup latency observations are compile noise, not
        traffic), but the request-id stamp stays monotone."""
        with self._lock:
            self._latencies.clear()
            self.resilience.clear()
            self._batches = 0
            self._requests = 0
        self.telemetry.clear()
        for name in ("serving_latency_s", "serving_batch_size"):
            h = self.metrics.get(name)
            if isinstance(h, Histogram):
                h.reset()

    def summary(self) -> dict:
        """The run-report schema-v8 ``"serving"`` entry for this
        service's lifetime (requests, batching, latency percentiles,
        cache economics, remediation outcomes)."""
        with self._lock:
            lats = sorted(self._latencies)
            batches = self._batches
            requests = self._requests
            res = list(self.resilience)
            tunes = dict(self._tuning)
        tuning = None
        if any(v is not None for v in tunes.values()):
            sources: Dict[str, int] = {}
            for v in tunes.values():
                src = v["source"] if v else "default"
                sources[src] = sources.get(src, 0) + 1
            tuning = {"consulted": len(tunes), "sources": sources}
        return {"requests": requests, "batches": batches,
                "admission": self.admission.summary(),
                "tuning": tuning,
                "mean_batch": (requests / batches) if batches else None,
                "latency_s": {"p50": percentile(lats, 50),
                              "p99": percentile(lats, 99),
                              "max": lats[-1] if lats else None},
                "cache": self.cache.stats(),
                "remediated": sum(1 for s in res
                                  if s["outcome"] == "remediated"),
                "failed": sum(1 for s in res
                              if s["outcome"] == "failed"),
                "retries": sum(
                    1 for s in res for a in s["attempts"]
                    if a["action"] == guard.ACTION_RETRY),
                "escalations": sum(
                    1 for s in res for a in s["attempts"]
                    if a["action"] == guard.ACTION_ALGO_FALLBACK)}
