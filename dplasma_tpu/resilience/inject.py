"""Deterministic fault injection — the testable half of resilience.

A :class:`FaultPlan` (CLI ``--inject=KIND@STAGE[:RATE[:COUNT]]``, env
``DPLASMA_INJECT``) corrupts the output of chosen kernel *stages* with
one of six fault models:

- ``bitflip`` — XOR one seeded bit of one seeded element (the classic
  soft-error model: a silent, finite, wrong value);
- ``nan`` / ``inf`` — poison one seeded element (a NaN-producing
  kernel / overflowed accumulation);
- ``zero`` — zero the whole tapped tile/panel (a torn write);
- ``delay`` — a *behavioral* fault: the tap sleeps MCA
  ``chaos.delay_ms`` and returns the value untouched (a straggler
  device / preempted host thread — exercises deadlines and SLO
  shedding, not checksums);
- ``reject`` — a behavioral fault: the tap raises
  :class:`InjectedReject` (a compile/dispatch failure surfacing as an
  exception — exercises the remediation ladder and circuit breakers).

Value kinds are pure ``jnp`` transforms applied at trace time; the
behavioral kinds act host-side in :func:`tap` itself and never touch
the traced program. :func:`parse_schedule` strings plans into a
scripted *chaos schedule* (comma-separated phases, ``off`` = quiet)
that ``tools/servebench.py --soak`` arms window by window.

Stages are the tile-kernel choke points in :mod:`kernels.blas`
(``gemm``, ``trsm``, ``potrf``, ``getrf``) plus the wildcard ``any``.
Each stage keeps a per-arm site counter; whether site ``i`` of a stage
faults is a pure function of (seed, stage, site, rate) via a SHA-256
hash, and the corrupted element/bit positions come from
``jax.random`` keys folded from the same triple — so the SAME seed and
plan produce BIT-IDENTICAL corruption on every run, jit or eager.

Corruption itself is a pure ``jnp`` transform applied at trace time,
so it composes with ``jit`` and ``shard_map``: the corrupted program is
what XLA compiles. Faults are *transient* (a soft error does not recur
on recompute): the guard's retry rungs re-trace under
:func:`suppressed`, and :func:`disarm` clears jax's trace caches after
an actual injection so no module-level ``@jax.jit`` keeps a poisoned
executable alive.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import time
from typing import List, Optional

from dplasma_tpu.utils import config as _cfg

_cfg.mca_register(
    "chaos.delay_ms", "50",
    "Straggler stall injected by the 'delay' fault kind, in "
    "milliseconds per faulting tap site.")

KINDS = ("bitflip", "nan", "inf", "zero", "delay", "reject")

#: kinds that act host-side in tap() (sleep / raise) instead of
#: corrupting the traced value — they skip the inexact-dtype check
#: and never reach corrupt()
BEHAVIORAL_KINDS = ("delay", "reject")


class InjectedReject(RuntimeError):
    """Raised by the ``reject`` fault kind at a tapped site — the
    deterministic stand-in for a compile/dispatch failure."""

#: stage names with a tap in the kernel layer, plus the serving
#: front-end's per-request response tap (``any`` matches all)
STAGES = ("gemm", "trsm", "potrf", "getrf", "serving", "any")


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """One deterministic corruption campaign.

    ``rate`` is the per-site fault probability (>= 1 means every
    matching site, subject to ``max_faults``); ``max_faults`` caps the
    campaign (0 = unbounded — every matching site by rate).
    """

    kind: str
    stage: str
    rate: float = 1.0
    max_faults: int = 1
    seed: int = 3872

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r} "
                             f"(choose from {KINDS})")
        if self.stage not in STAGES:
            # a typo'd stage would arm a plan whose tap never matches —
            # the run would claim "clean" while testing nothing
            raise ValueError(f"unknown fault stage {self.stage!r} "
                             f"(choose from {STAGES})")
        if not (self.rate > 0.0):
            raise ValueError(f"fault rate must be > 0, got {self.rate}")

    def spec(self) -> str:
        return f"{self.kind}@{self.stage}:{self.rate:g}:{self.max_faults}"


def parse_plan(spec: str, seed: int = 3872) -> FaultPlan:
    """Parse ``KIND@STAGE[:RATE[:COUNT]]`` (the ``--inject`` grammar).

    ``nan@trsm:1`` = poison the first trsm output; ``bitflip@gemm:0.25:0``
    = flip a bit in ~every 4th gemm output, unbounded count.
    """
    kind, at, rest = spec.strip().partition("@")
    if not at or not rest:
        raise ValueError(
            f"bad inject spec {spec!r}: expected KIND@STAGE[:RATE[:COUNT]]")
    if kind.lower() not in KINDS:
        # validate at PARSE time with the full spec in the message: a
        # typo'd DPLASMA_INJECT=bitlfip@gemm must die here, at the
        # boundary, not deep inside FaultPlan construction
        raise ValueError(
            f"bad inject spec {spec!r}: unknown fault kind "
            f"{kind.lower()!r} (valid kinds: {', '.join(KINDS)})")
    parts = rest.split(":")
    stage = parts[0]
    rate = float(parts[1]) if len(parts) > 1 and parts[1] else 1.0
    count = int(parts[2]) if len(parts) > 2 and parts[2] else 1
    return FaultPlan(kind.lower(), stage.lower(), rate, count, seed)


@dataclasses.dataclass(frozen=True)
class ChaosPhase:
    """One window of a scripted chaos schedule: the original spec text
    plus its parsed plan (``None`` for a quiet phase)."""

    spec: str
    plan: Optional[FaultPlan]


def parse_schedule(text: str, seed: int = 3872) -> List[ChaosPhase]:
    """Parse a comma-separated chaos schedule into phases.

    ``nan@serving:1:2,off,delay@serving:0.5:0`` = three equal traffic
    windows: poison two serving responses, run clean, then stall ~half
    the serving taps. ``off``/``none``/``-`` (or an empty field) is a
    quiet phase. Each armed phase gets a distinct seed (``seed + k``)
    so identical specs in different windows corrupt different sites.
    """
    if not text.strip():
        raise ValueError("empty chaos schedule")
    phases: List[ChaosPhase] = []
    for k, field in enumerate(text.split(",")):
        spec = field.strip()
        if not spec or spec.lower() in ("off", "none", "-"):
            phases.append(ChaosPhase(spec or "off", None))
        else:
            phases.append(ChaosPhase(spec, parse_plan(spec, seed + k)))
    if not phases:
        raise ValueError("empty chaos schedule")
    return phases


class _Session:
    """Module-global injection state (one armed plan at a time)."""

    def __init__(self):
        self.plan: Optional[FaultPlan] = None
        self.suppress = 0
        self.sites: dict = {}
        self.faults: List[dict] = []


_S = _Session()


def arm(plan: FaultPlan) -> None:
    """Activate ``plan``: site counters and the fault log reset, so a
    re-armed identical plan replays identical corruption."""
    _S.plan = plan
    _S.sites = {}
    _S.faults = []


def disarm() -> List[dict]:
    """Deactivate the armed plan; returns the fault records.

    If anything was injected, jax's trace/compile caches are cleared:
    a module-level ``@jax.jit`` traced while armed would otherwise keep
    serving the poisoned executable after the campaign ends.
    """
    faults = list(_S.faults)
    _S.plan = None
    _S.sites = {}
    _S.faults = []
    if faults:
        import jax
        jax.clear_caches()
    return faults


def armed() -> bool:
    return _S.plan is not None and _S.suppress == 0


def faults() -> List[dict]:
    return list(_S.faults)


@contextlib.contextmanager
def active(plan: FaultPlan):
    """Scoped :func:`arm`/:func:`disarm`; yields the fault-record list
    (filled in on exit)."""
    out: List[dict] = []
    arm(plan)
    try:
        yield out
    finally:
        out.extend(disarm())


@contextlib.contextmanager
def suppressed():
    """Scope where taps never fire — verification/remediation paths
    (ABFT checks, health scans, ladder retries) run clean under this."""
    _S.suppress += 1
    try:
        yield
    finally:
        _S.suppress -= 1


def _site_u01(seed: int, stage: str, site: int) -> float:
    """Deterministic U[0,1) draw for one (stage, site) — the fault
    lottery, stable across processes/backends."""
    h = hashlib.sha256(f"{seed}:{stage}:{site}".encode()).digest()
    return int.from_bytes(h[:8], "big") / 2.0 ** 64


def _site_rng(seed: int, stage: str, site: int):
    """Host-side RNG for positions/bits: NOT jax.random — under jit's
    omnistaging even constant-input jax ops would be staged as tracers,
    and positions must be trace-time constants."""
    import numpy as np
    h = hashlib.sha256(f"pos:{seed}:{stage}:{site}".encode()).digest()
    return np.random.default_rng(int.from_bytes(h[:8], "big"))


def _bitflip(val, bit: int):
    """Flip bit ``bit`` of a real scalar's IEEE representation (pure
    jnp transform; composes with jit)."""
    import jax.numpy as jnp
    from jax import lax
    bits = jnp.finfo(val.dtype).bits
    uint = {16: jnp.uint16, 32: jnp.uint32, 64: jnp.uint64}[bits]
    word = lax.bitcast_convert_type(val, uint)
    flipped = word ^ jnp.asarray(1 << (bit % bits), uint)
    return lax.bitcast_convert_type(flipped, val.dtype)


def corrupt(x, kind: str, rng):
    """Pure corruption transform: returns (corrupted x, element index).

    The element/bit positions are drawn host-side from ``rng``
    (deterministic trace-time constants); ``zero`` wipes the whole
    array and reports index (0, ...).
    """
    import jax.numpy as jnp

    if kind == "zero":
        return jnp.zeros_like(x), (0,) * max(x.ndim, 1)
    idx = tuple(int(rng.integers(0, max(int(d), 1))) for d in x.shape)
    if kind == "nan":
        bad = jnp.asarray(float("nan"), jnp.finfo(x.dtype).dtype)
    elif kind == "inf":
        bad = jnp.asarray(float("inf"), jnp.finfo(x.dtype).dtype)
    else:  # bitflip
        el = x[idx] if idx else x
        # flip within the significant half (sign/exponent/high mantissa):
        # a low-mantissa flip is indistinguishable from rounding noise —
        # undetectable by any checksum, and uninteresting to inject
        bits = jnp.finfo(jnp.finfo(x.dtype).dtype).bits
        bit = int(rng.integers(bits // 2, bits))
        if jnp.issubdtype(x.dtype, jnp.complexfloating):
            bad = (_bitflip(el.real, bit) + 1j * el.imag).astype(x.dtype)
        else:
            bad = _bitflip(el, bit)
    if jnp.issubdtype(x.dtype, jnp.complexfloating) and kind in (
            "nan", "inf"):
        bad = (bad + 0j).astype(x.dtype)
    else:
        bad = bad.astype(x.dtype)
    return (x.at[idx].set(bad) if idx else bad), idx


def tap(stage: str, x):
    """Fault tap on a kernel-stage output — the single entry point the
    kernel layer calls. No armed plan: one attribute check and out."""
    plan = _S.plan
    if plan is None or _S.suppress:
        return x
    if plan.stage != "any" and plan.stage != stage:
        return x
    site = _S.sites.get(stage, 0)
    _S.sites[stage] = site + 1
    if plan.max_faults and len(_S.faults) >= plan.max_faults:
        return x
    if _site_u01(plan.seed, stage, site) >= min(plan.rate, 1.0) \
            and plan.rate < 1.0:
        return x
    if plan.kind in BEHAVIORAL_KINDS:
        # host-side faults: no dtype requirement, nothing staged into
        # the traced program — record first so the campaign budget is
        # charged even when the tap raises
        _S.faults.append({"stage": stage, "site": site,
                          "kind": plan.kind})
        if plan.kind == "delay":
            time.sleep(
                max(_cfg.mca_get_float("chaos.delay_ms", 50.0), 0.0)
                / 1000.0)
            return x
        raise InjectedReject(
            f"injected reject at {stage} site {site}")
    import jax.numpy as jnp
    if not hasattr(x, "dtype") or not jnp.issubdtype(
            jnp.dtype(x.dtype), jnp.inexact):
        return x
    y, idx = corrupt(x, plan.kind, _site_rng(plan.seed, stage, site))
    _S.faults.append({"stage": stage, "site": site, "kind": plan.kind,
                      "shape": tuple(int(d) for d in x.shape),
                      "index": tuple(int(i) for i in idx)})
    return y
