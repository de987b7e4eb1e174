"""Compiled-executable cache with shape bucketing — the serving
layer's answer to ragged traffic.

Every distinct (shape, dtype, batch, nrhs) would otherwise compile its
own executable; real request streams are ragged, so the cache first
*buckets* shapes (power-of-two-ish ladders: each bucket is at most
~1.33x the exact size, so padding waste is bounded) and pads inputs
into the bucket:

* ``A`` pads with IDENTITY blocks on the diagonal (the
  :meth:`TileMatrix.pad_diag` contract one level up): the padded
  system is ``blkdiag(A, I) [x; y] = [b; 0]`` whose ``x`` is EXACTLY
  the unpadded solution (tested — padding must not perturb);
* ``b`` pads with zeros (rows and right-hand-side columns);
* batch slots pad with identity problems (``A = I``, ``b = 0`` —
  solution zero, numerically inert).

Cache entries are ahead-of-time compiled executables
(``jax.jit(...).lower(...).compile()``), keyed by
:func:`make_key`'s full contract tuple: op, shape bucket, dtype,
batch bucket, nrhs bucket, device grid, pipeline shape
(``sweep.lookahead``/``qr.agg_depth`` — a different pipeline shape IS
a different program), and ``ir.precision`` for the IR solvers. An LRU
bound (MCA ``serving.cache_capacity``) evicts cold executables;
hit/miss/eviction counts and cumulative compile seconds land in the
metrics registry (``serving_cache_*``).

Every admitted executable is audited by the compiled-artifact checker
(:mod:`dplasma_tpu.analysis.hlocheck`: dropped donations, precision
demotions, HBM budget, host-callback anti-patterns — MCA
``hlocheck.serving``); the summary rides the :class:`Entry` and
``serving_hlocheck_*`` metrics, never fatal.

Fault-injection interplay: corruption taps fire at TRACE time
(:mod:`dplasma_tpu.resilience.inject`), so an executable compiled
while a fault plan is armed is *poisoned for its lifetime* — the
:class:`Entry` records ``tainted`` and the service drops the entry
after the fault is detected (the cache-level analogue of
``inject.disarm`` clearing jax's own trace caches).
"""
from __future__ import annotations

import collections
import dataclasses
import sys
import threading
import time
from typing import Callable, List, NamedTuple, Optional, Tuple

import jax.numpy as jnp

from dplasma_tpu.observability.metrics import MetricsRegistry
from dplasma_tpu.utils import config as _cfg

_cfg.mca_register(
    "serving.cache_capacity", "32",
    "LRU bound of the serving executable cache (compiled callables "
    "kept hot; least-recently-used entries are evicted past this).")
_cfg.mca_register(
    "serving.bucket", "pow2ish",
    "Shape-bucket policy of the serving layer: pow2ish (2^k and "
    "1.5*2^k rungs — padding waste bounded by ~33%), pow2 (pure "
    "powers of two), or exact (no shape bucketing; every distinct "
    "size compiles its own executable).")

#: smallest shape bucket (one 8-row tile quantum; tiny problems share)
MIN_BUCKET = 8
#: smaller floor for right-hand-side counts (nrhs=1 traffic is common;
#: an 8-wide floor would double every solve sweep's width)
MIN_NRHS_BUCKET = 4


def bucket_dim(n: int, policy: Optional[str] = None,
               floor: int = MIN_BUCKET) -> int:
    """Round a problem/nrhs dimension up into its shape bucket."""
    n = max(int(n), 1)
    policy = (policy or _cfg.mca_get("serving.bucket") or "pow2ish")
    if policy == "exact":
        return n
    b = max(int(floor), 1)
    while b < n:
        b2 = b + b // 2          # the 1.5*2^k rung
        if policy == "pow2ish" and n <= b2:
            return b2
        b *= 2
    return b


def bucket_batch(nreq: int) -> int:
    """Round a batch size up to the next power of two (batch slots are
    cheap — identity problems — and halving the distinct batch shapes
    halves the executables compiled)."""
    b = 1
    while b < max(int(nreq), 1):
        b *= 2
    return b


class CacheKey(NamedTuple):
    """The full compiled-program contract — two requests share an
    executable iff every field matches."""
    op: str
    n: int            # shape bucket (problem dimension)
    dtype: str
    batch: int        # batch bucket
    nrhs: int         # rhs bucket
    grid: Tuple[int, int]
    pipeline: Tuple[int, int]   # (sweep.lookahead, qr.agg_depth)
    precision: str    # ir.precision for *_ir ops, "" otherwise
    extra: Tuple = ()  # canonicalized solver kwargs (part of the trace)


def make_key(op: str, n: int, dtype, batch: int, nrhs: int,
             policy: Optional[str] = None,
             extra: Tuple = (),
             precision: Optional[str] = None) -> CacheKey:
    """Bucket a raw request shape into its executable key. Pure
    function of the arguments + the MCA tier (grid from the active
    mesh, pipeline shape from ``sweep.*``, ``ir.precision`` for IR
    ops) — determinism is load-bearing: the scheduler groups requests
    by this key. ``precision`` overrides the ambient ``ir.precision``
    for IR ops (the admission layer's degrade-under-pressure rung
    keys its cheaper executable separately); the service pins the
    key's precision back onto the compile, so key and executable
    always agree."""
    from dplasma_tpu.ops._sweep import sweep_params
    from dplasma_tpu.parallel import mesh as pmesh
    m = pmesh.active()
    grid = (1, 1)
    if m is not None:
        grid = (int(m.shape[pmesh.ROW_AXIS]),
                int(m.shape[pmesh.COL_AXIS]))
    la, agg = sweep_params()
    prec = ""
    if op.endswith("_ir"):
        from dplasma_tpu.ops.refine import ir_params
        prec, _, _ = ir_params(precision=precision)
    return CacheKey(op=op, n=bucket_dim(n, policy),
                    dtype=jnp.dtype(dtype).name,
                    batch=bucket_batch(batch),
                    nrhs=bucket_dim(nrhs, policy,
                                    floor=MIN_NRHS_BUCKET),
                    grid=grid, pipeline=(la, agg), precision=prec,
                    extra=tuple(extra))


# ------------------------------------------------------------- padding

def pad_problem(a, n_to: int):
    """Pad one ``(n, n)`` operand to ``(n_to, n_to)`` with identity
    blocks: zeros off-diagonal, ones on the padded diagonal. The
    padded system solves to the exact unpadded solution (module
    docstring); tested against the exact-shape solve."""
    n = a.shape[-1]
    assert n <= n_to, (n, n_to)
    if n == n_to:
        return a
    out = jnp.zeros(a.shape[:-2] + (n_to, n_to), a.dtype)
    out = out.at[..., :n, :n].set(a)
    idx = jnp.arange(n, n_to)
    return out.at[..., idx, idx].set(jnp.asarray(1.0, a.dtype))


def pad_rhs(b, n_to: int, nrhs_to: int):
    """Pad one ``(n, nrhs)`` right-hand side with zeros (rows AND
    columns — the padded rows belong to the identity block, the padded
    columns are discarded on scatter)."""
    n, nrhs = b.shape[-2], b.shape[-1]
    assert n <= n_to and nrhs <= nrhs_to, (b.shape, n_to, nrhs_to)
    if n == n_to and nrhs == nrhs_to:
        return b
    out = jnp.zeros(b.shape[:-2] + (n_to, nrhs_to), b.dtype)
    return out.at[..., :n, :nrhs].set(b)


# --------------------------------------------------------------- cache

@dataclasses.dataclass
class Entry:
    """One cached executable + its provenance."""
    fn: Callable
    key: CacheKey
    compile_s: float
    tainted: bool      # compiled while a fault plan was armed & firing
    hits: int = 0
    #: compiled-artifact audit of the admitted executable
    #: (analysis.hlocheck summary; None when the audit is off/failed)
    hlocheck: Optional[dict] = None


class ExecutableCache:
    """LRU cache of AOT-compiled batched solve executables.

    ``get(key, build, *args)`` returns the :class:`Entry` for ``key``,
    compiling ``build()``'s callable against ``args``' shapes on a
    miss. Counters (hits/misses/evictions/compile seconds) land in
    ``metrics`` (``serving_cache_*``), so the run-report's metrics
    section carries the cache economics of every serving run.
    """

    def __init__(self, capacity: Optional[int] = None,
                 metrics: Optional[MetricsRegistry] = None):
        self.capacity = max(
            capacity if capacity is not None
            else _cfg.mca_get_int("serving.cache_capacity", 32), 1)
        self.metrics = metrics if metrics is not None \
            else MetricsRegistry()
        #: optional flight recorder (observability.telemetry): the
        #: service points this at its ring so evictions/invalidations
        #: become structured events a production incident can replay
        self.recorder = None
        self._d: "collections.OrderedDict[CacheKey, Entry]" = \
            collections.OrderedDict()
        # the service dispatches from caller AND timer threads: every
        # OrderedDict access must hold this (an unlocked hit's
        # move_to_end races a concurrent eviction/invalidation into
        # KeyError). Compiles serialize under it too — coarse but
        # correct; a per-key compile lock is future work if compile
        # concurrency ever matters here.
        self._lock = threading.RLock()

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)

    def __contains__(self, key: CacheKey) -> bool:
        with self._lock:
            return key in self._d

    def get(self, key: CacheKey, build: Callable[[], Callable],
            *args) -> Entry:
        """The cached executable for ``key`` (LRU-refreshed), or
        compile ``build()`` against ``args`` and admit it."""
        with self._lock:
            entry = self._d.get(key)
            if entry is not None:
                self._d.move_to_end(key)
                entry.hits += 1
                self.metrics.counter("serving_cache_hits_total").inc()
                return entry
            self.metrics.counter("serving_cache_misses_total").inc()
            entry = self._compile(key, build, args)
            self._d[key] = entry
            while len(self._d) > self.capacity:
                old_key, old = self._d.popitem(last=False)
                self.metrics.counter(
                    "serving_cache_evictions_total").inc()
                if self.recorder is not None:
                    self.recorder.record(
                        "cache_evict", op=old_key.op, n=old_key.n,
                        batch=old_key.batch, hits=old.hits)
            self.metrics.gauge("serving_cache_entries").set(
                len(self._d))
            return entry

    def _compile(self, key: CacheKey, build: Callable[[], Callable],
                 args: Tuple) -> Entry:
        """Compile one admission (called with ``_lock`` held — the
        coarse serialize-compiles-under-the-cache-lock contract from
        the class docstring). Split out so the racefuzz ``cache_lru``
        probe can fuzz the LRU lock discipline with a stub Entry
        instead of paying XLA per schedule op."""
        import jax

        from dplasma_tpu.resilience import inject
        faults0 = len(inject.faults())
        t0 = time.perf_counter()
        lowered = jax.jit(build()).lower(*args)
        compiled = lowered.compile()
        dt = time.perf_counter() - t0
        tainted = len(inject.faults()) > faults0
        self.metrics.counter(
            "serving_cache_compile_seconds").inc(dt)
        return Entry(fn=compiled, key=key, compile_s=dt,
                     tainted=tainted,
                     hlocheck=self._audit(lowered, compiled, key))

    def _audit(self, lowered, compiled, key: CacheKey
               ) -> Optional[dict]:
        """Compiled-artifact audit (analysis.hlocheck) of a freshly
        admitted executable: dropped donations, precision demotions,
        the HBM budget, host-callback anti-patterns. Serving is a
        long-lived process — an executable that carries its batch
        twice or blocks on the host serves every future request worse,
        so the audit runs at the one moment the artifact is new. Never
        fatal: diagnostics land on the entry, in
        ``serving_hlocheck_*`` metrics, and on stderr (MCA
        ``hlocheck.serving`` = off disables)."""
        from dplasma_tpu.analysis import hlocheck as hc
        if _cfg.mca_get("hlocheck.serving", "on") == "off":
            return None
        prec = {"float32": "s", "float64": "d", "complex64": "c",
                "complex128": "z"}.get(key.dtype, "s")
        try:
            res = hc.check_executable(lowered, compiled,
                                      f"serving:{key.op}", prec=prec)
        except Exception as exc:
            # the audit must never take down a compile that succeeded
            sys.stderr.write(f"#! serving hlocheck audit failed for "
                             f"{key.op}: {exc!r}\n")
            return None
        self.metrics.counter("serving_hlocheck_audits_total").inc()
        if not res.ok:
            self.metrics.counter(
                "serving_hlocheck_diagnostics_total").inc(
                len(res.diagnostics))
            sys.stderr.write(res.format(f"serving:{key.op}") + "\n")
        self._residency_audit(res, key)
        return res.summary()

    def _residency_audit(self, res, key: CacheKey) -> None:
        """Residency gate on the MEASURED peak of an admitted
        executable (analysis.memcheck): serving has no recorded tile
        DAG to predict from, so the audit compares the compiled
        ``memory_analysis`` peak against MCA ``memcheck.hbm_budget``
        directly — a long-lived cache must not admit an executable
        whose working set already busts the device budget. Never
        fatal: ``serving_memcheck_*`` metrics + stderr (MCA
        ``memcheck.serving`` = off disables)."""
        if _cfg.mca_get("memcheck.serving", "on") == "off":
            return
        budget = _cfg.mca_get_int("memcheck.hbm_budget", 0)
        peak = getattr(res, "hbm_peak_bytes", None)
        if budget <= 0 or peak is None:
            return
        self.metrics.counter("serving_memcheck_audits_total").inc()
        if peak > budget:
            self.metrics.counter(
                "serving_memcheck_violations_total").inc()
            sys.stderr.write(
                f"#! memcheck[serving:{key.op}]: measured HBM peak "
                f"{peak}B exceeds memcheck.hbm_budget {budget}B "
                f"(n={key.n} batch={key.batch})\n")

    def invalidate(self, key: CacheKey) -> bool:
        """Drop one entry (a poisoned executable after a detected
        fault); True when something was evicted."""
        with self._lock:
            gone = self._d.pop(key, None) is not None
            if gone:
                self.metrics.counter(
                    "serving_cache_invalidations_total").inc()
                self.metrics.gauge("serving_cache_entries").set(
                    len(self._d))
                if self.recorder is not None:
                    self.recorder.record(
                        "cache_invalidate", op=key.op, n=key.n,
                        batch=key.batch)
            return gone

    def entries(self) -> List[Entry]:
        """A snapshot of the cached entries, least recently used
        first."""
        with self._lock:
            return list(self._d.values())

    def stats(self) -> dict:
        """The cache economics summary for the run-report ``"serving"``
        section."""
        def _c(name):
            m = self.metrics.get(name)
            return float(m.value) if m is not None else 0.0
        hits = _c("serving_cache_hits_total")
        misses = _c("serving_cache_misses_total")
        total = hits + misses
        with self._lock:
            entries = len(self._d)
        return {"entries": entries, "capacity": self.capacity,
                "hits": int(hits), "misses": int(misses),
                "evictions": int(_c("serving_cache_evictions_total")),
                "invalidations": int(
                    _c("serving_cache_invalidations_total")),
                "hit_rate": (hits / total) if total else None,
                "compile_s": _c("serving_cache_compile_seconds")}
