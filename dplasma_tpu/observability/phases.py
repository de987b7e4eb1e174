"""Scoped phase timers for performance attribution (``--phase-profile``).

The reference runtime attributes time to individual tasks (PaRSEC's
per-task trace is how DPLASMA tells a panel-latency-bound run from an
update-throughput-bound one); the TPU port executes whole sweeps as a
handful of large XLA dispatches, so the useful granularity here is the
*phase*: panel factorization, narrow lookahead applies, wide far
flushes, catch-up replays, assembly. The sweep engine and the eager op
routes wrap those regions in :func:`span`; a driver run with
``--phase-profile`` activates a :class:`PhaseLedger` around one
*attributed* eager pass and lands the per-phase times next to the
roofline expectations (:mod:`dplasma_tpu.observability.roofline`) in
the run-report (schema v5 ``"phases"`` per-op section).

Fencing contract: a span only measures truthfully if the async work it
issued has retired, so the values the instrumented region hands to the
span sink are fenced (``jax.block_until_ready``) at span exit — but
ONLY while a ledger is active. With no active ledger :func:`span`
yields a no-op sink and never fences, so the default path keeps XLA's
fusion/overlap behavior bit-for-bit (asserted by
``tests/test_phases.py``). Spans encountered while *tracing* (inside a
``jit``) are harmless either way: ``block_until_ready`` passes tracers
through untouched, and the ledger is only ever activated around eager
execution.

Named scopes: every span also opens ``jax.named_scope("dplasma.<name>")``
around its body, profiling or not. A named scope is trace-time metadata:
each HLO instruction emitted inside it carries ``dplasma.<name>`` in its
``op_name`` (outermost scope first), and so does each device op of a
profiler trace, so the phases of a compiled program can be read from its
trace. It changes no instruction. The names form one vocabulary,
:data:`SCOPES`. A span with ``timed=False`` is a scope only: the ledger
neither times nor fences it (the boundaries inside compiled programs
that the eager attributed pass does not price).

Usage (instrumented code)::

    with phases.span("panel") as fence:
        pack, state = panel(col)
        fence((pack, state))      # fenced at exit iff profiling is on

    with phases.span("update", timed=False):
        slab = trailing_update(...)

Usage (harness)::

    with phases.profiling() as ledger:
        out = fn(*args)
    ledger.summary()   # [{"phase", "count", "measured_s"}, ...]
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional


#: every span name: each opens the named scope ``dplasma.<name>``
SCOPES = (
    # factorizations and their sweeps
    "potrf", "getrf", "panel", "update", "lookahead", "far_flush",
    "catchup", "assemble",
    # the dd engine's limb scheme around its int8 products
    "split", "recombine",
    # solves
    "solve", "laswp",
    # the distributed LU: layout changes and the tournament panel
    "redistribute", "bcast", "elect", "playoff", "exchange", "ring",
    # iterative refinement and quantized updates
    "factor", "residual", "correct", "escalate", "quantize",
    "dequantize",
)
#: prefix of every scope in HLO ``op_name`` metadata
SCOPE_PREFIX = "dplasma."


class PhaseLedger:
    """Per-phase accumulator: dispatch count + wall seconds."""

    def __init__(self):
        self.phases: Dict[str, dict] = {}

    def add(self, name: str, seconds: float,
            total: Optional[float] = None) -> None:
        """Record one span: ``seconds`` is SELF time (disjoint across
        the ledger — these sum to at most the attributed run);
        ``total`` is the inclusive elapsed time (self + enclosed child
        spans, defaulting to ``seconds`` for leaf spans) — the wall
        time of the whole region, which is what a rate computed from
        an ENCLOSING span (e.g. the IR solvers' ``factor``, wrapping
        the inner sweep's panel/lookahead/... spans) must divide by."""
        e = self.phases.setdefault(
            name, {"count": 0, "seconds": 0.0, "total": 0.0})
        e["count"] += 1
        e["seconds"] += float(seconds)
        e["total"] += float(seconds if total is None else total)

    def total(self) -> float:
        return sum(e["seconds"] for e in self.phases.values())

    def summary(self) -> List[dict]:
        """Phases as JSON-able rows, heaviest first (ties: by name, so
        two identical runs serialize identically). ``measured_s`` is
        self time; ``total_s`` the inclusive elapsed (== measured_s
        for leaf spans)."""
        return [{"phase": name, "count": e["count"],
                 "measured_s": e["seconds"], "total_s": e["total"]}
                for name, e in sorted(self.phases.items(),
                                      key=lambda kv:
                                      (-kv[1]["seconds"], kv[0]))]


#: the active ledger; None = profiling off (spans are no-ops)
_active: Optional[PhaseLedger] = None


def active() -> Optional[PhaseLedger]:
    return _active


def _fence(values) -> None:
    """Block until every array in ``values`` has retired (tracers and
    non-arrays pass through). The single choke point the no-fencing
    test patches."""
    import jax
    jax.block_until_ready(values)


class _Sink:
    """Span sink: values passed in are fenced at span exit."""

    __slots__ = ("values",)

    def __init__(self):
        self.values = []

    def __call__(self, x):
        self.values.append(x)
        return x


class _NoopSink:
    """Inactive-profiling sink: identity, retains nothing."""

    __slots__ = ()

    def __call__(self, x):
        return x


_NOOP = _NoopSink()


#: enclosing-span child-time stack: spans may NEST (the IR solvers'
#: ``factor`` span wraps the whole inner factorization, whose own
#: sweep emits panel/lookahead/... spans) — each span records its
#: SELF time (elapsed minus enclosed spans), so the ledger's phase
#: seconds stay disjoint and sum to at most the attributed run
_nest: List[float] = []


def _named_scope(name: str):
    """The named scope a span opens (the single point the tests
    patch)."""
    import jax
    return jax.named_scope(SCOPE_PREFIX + name)


@contextlib.contextmanager
def span(name: str, *, timed: bool = True):
    """One phase region, under the named scope ``dplasma.<name>``.
    Yields a sink; values the region passes to the sink are fenced at
    exit *only when profiling is active* and the span is ``timed`` —
    otherwise nothing is timed or fenced. Nested spans attribute
    self-time only (child seconds are subtracted from the enclosing
    span)."""
    with _named_scope(name):
        led = _active
        if led is None or not timed:
            yield _NOOP
            return
        sink = _Sink()
        _nest.append(0.0)
        t0 = time.perf_counter()
        try:
            yield sink
        finally:
            try:
                if sink.values:
                    _fence(sink.values)
            finally:
                # balance _nest even when the fence raises (a poisoned
                # array's block_until_ready — the failure the driver
                # degrades to a warning): a leaked entry would corrupt
                # every later span's child-time subtraction
                # process-wide
                elapsed = time.perf_counter() - t0
                child = _nest.pop()
                if _nest:
                    _nest[-1] += elapsed
                led.add(name, max(elapsed - child, 0.0), total=elapsed)


@contextlib.contextmanager
def profiling(ledger: Optional[PhaseLedger] = None):
    """Activate a (fresh by default) ledger for the block; restores
    the previous one on exit, so nested/overlapping scopes compose."""
    global _active
    prev = _active
    led = ledger if ledger is not None else PhaseLedger()
    _active = led
    try:
        yield led
    finally:
        _active = prev
