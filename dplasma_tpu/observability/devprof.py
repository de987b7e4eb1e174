"""devprof: per-device timeline ingestion + cross-rank attribution.

The instrument stack above this module is *predictive* — roofline
pricing, spmdcheck/hlocheck schedule reconciliation, the analytic
comm-volume model — but none of it reads back what the devices
actually did. This module closes the loop, the TPU-world analogue of
PaRSEC's per-task profiling readback:

1. **capture** — :class:`DevprofCapture` wraps the driver's timed
   loop. Backend ``jax`` records a ``jax.profiler`` trace and ingests
   the device ops of its ``.xplane.pb`` (the ``XLA Ops`` line of every
   ``/device:TPU:<n>`` plane, each op with the ``dplasma.*`` named
   scopes of its op name); backend
   ``synthetic`` (the only one that produces a device timeline on the
   CPU host-platform mesh, where XLA's profiler has no device lanes)
   reconstructs the per-rank timeline from the measured run seconds,
   the spmdcheck collective schedule, and the
   :func:`~dplasma_tpu.parallel.cyclic.spmd_comm_model` wire-byte
   pricing — every rank's categories sum to the timed run *exactly*,
   so the ingestion/attribution contract is testable everywhere.
   ``auto`` picks ``jax`` on accelerator backends and ``synthetic``
   on the CPU mesh (an in-loop profiler capture there is pure
   overhead with no device events to show for it). A ``jax`` capture
   that yields no device ops falls back to ``synthetic`` with a
   ``note`` that says so.
2. **binning** — timeline ops land in ``compute`` / ``collective`` /
   ``ici`` / ``host`` categories by matching the same HLO op-name
   tables hlocheck parses (:mod:`dplasma_tpu.analysis.hlo_names` —
   one vocabulary, every reader).
3. **reconciliation** — measured collective seconds and achieved
   bytes/s per (kind, axis) class against the comm model's priced
   bytes and the roofline ``ici`` peak. A class the spmdcheck
   schedule expects that the ingested timeline lacks is a
   ``missing-collective`` diagnostic naming the exact class; an
   achieved fraction under MCA ``devprof.ici_floor`` is an
   ``ici-floor`` diagnostic naming the op.
4. **straggler attribution** — per-rank busy-seconds skew
   ``(max-min)/max``, the slowest rank and its dominating category
   named, per-step span spread across ranks, and a critical-path
   walk over the merged timeline (latest-ending span, chained
   backward through the latest span that ends by its begin).

Results land in the run-report schema v14 ``"devprof"`` section
(:meth:`~dplasma_tpu.observability.report.RunReport.add_devprof`);
``tools/perfdiff.py`` extracts ``<label>.devprof.ici_achieved_frac``
(higher-better) and ``<label>.devprof.skew`` (lower-better) from it,
and ``tools/tracecat.py --merge --devprof report.json`` renders the
category seconds as extra Perfetto lanes. Wired as ``--devprof`` on
every driver, per scaling point in ``tools/multichip.py``, and as
measured-ICI evidence on stored autotuner winners
(``tools/autotune.py sweep --devprof``).
"""
from __future__ import annotations

import glob
import os
import tempfile
import threading
from typing import Dict, List, Optional, Tuple

from dplasma_tpu.analysis.hlo_names import (JAXPR_TO_HLO, RING_MARKER,
                                            timeline_category)
from dplasma_tpu.utils import config as _cfg

_cfg.mca_register(
    "devprof.backend", "auto",
    "Timeline capture backend for --devprof: jax = wrap the timed "
    "loop in a jax.profiler trace and ingest the device ops of its "
    ".xplane.pb; synthetic = reconstruct the per-rank "
    "timeline from the measured run + the spmdcheck schedule + the "
    "spmd_comm_model pricing (the CPU-mesh path); auto = jax on "
    "accelerator backends, synthetic on the CPU host platform.")
_cfg.mca_register(
    "devprof.ici_floor", "0.05",
    "Minimum achieved-ICI fraction (measured bytes/s over the "
    "roofline ici peak) per collective class before devprof records "
    "an ici-floor diagnostic naming the op; 0 disables the check.")
_cfg.mca_register(
    "devprof.max_path", "32",
    "Maximum spans recorded for the critical-path extraction in the "
    "run-report (the walk itself is unbounded; only the reported "
    "span list truncates, keeping the longest spans).")

#: the category model every timeline op bins into
CATEGORIES = ("compute", "collective", "ici", "host")


def _ici_peak_bps(peaks: Optional[dict]) -> float:
    if not peaks:
        from dplasma_tpu.observability.roofline import DEFAULT_PEAKS
        peaks = DEFAULT_PEAKS
    try:
        return float(peaks.get("ici_gbps", 0.0)) * 1e9
    except (TypeError, ValueError):
        return 0.0


def timeline_op(name: str, rank: int, begin_ns: int, end_ns: int,
                cls: Optional[str] = None,
                step: Optional[int] = None,
                scope: Tuple[str, ...] = ()) -> dict:
    """One timeline op: a span on one rank's device lane. ``cls`` is
    the collective class key (``kind@axis``, spmdcheck's spelling)
    when known; the category bin always derives from the op *name*
    (the shared hlocheck vocabulary), never from the class. ``scope``
    is the op's ``dplasma.*`` named scopes, outermost first
    (:data:`dplasma_tpu.observability.phases.SCOPES`)."""
    return {"name": str(name), "rank": int(rank),
            "begin_ns": int(begin_ns), "end_ns": int(end_ns),
            "category": timeline_category(name),
            "cls": cls, "step": step, "scope": tuple(scope)}


class DevprofCollector:
    """Thread-safe timeline accumulator: capture backends append from
    whatever thread produced the event (the profiler callback thread,
    the driver loop, a test harness); ingestion snapshots once. All
    mutable state is guarded by ``_lock`` (registered in the
    threadcheck GUARDS registry)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ops: List[dict] = []

    def add(self, name: str, rank: int, begin_ns: int, end_ns: int,
            cls: Optional[str] = None,
            step: Optional[int] = None) -> None:
        op = timeline_op(name, rank, begin_ns, end_ns, cls=cls,
                         step=step)
        with self._lock:
            self._ops.append(op)

    def extend(self, ops) -> None:
        ops = [dict(o) for o in ops]
        with self._lock:
            self._ops.extend(ops)

    def snapshot(self) -> List[dict]:
        with self._lock:
            return list(self._ops)

    def clear(self) -> None:
        with self._lock:
            self._ops = []

    def __len__(self) -> int:
        with self._lock:
            return len(self._ops)


# ---------------------------------------------------------------------
# Capture backends
# ---------------------------------------------------------------------

#: the profiler's device planes and the line that holds their XLA ops
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
#: the event-metadata stat that carries an op's HLO op name
OP_NAME_STAT = "tf_op"


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, start: int = 0, end: Optional[int] = None):
    """(field number, value) of one serialized protobuf message:
    varints as ints, length-delimited fields as (start, end) offsets
    into ``buf``, fixed-width fields as their bytes."""
    i, end = start, len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, i = bytes(buf[i:i + n]), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _entry(buf, span):
    """(key, value span) of one protobuf map entry."""
    key = val = None
    for fn, v in _fields(buf, *span):
        if fn == 1:
            key = v
        elif fn == 2:
            val = v
    return key, val


def xplane_op_names(raw: bytes) -> Dict[str, Dict[str, str]]:
    """{device plane name: {event name: HLO op name}} of a serialized
    XSpace: the ``tf_op`` stat of each event's metadata (string or
    interned), which ``jax.profiler.ProfileData`` does not expose.
    Reads XSpace.planes (1) -> XPlane.name (2), event_metadata (4),
    stat_metadata (5); XEventMetadata.name (2), stats (5); XStat
    metadata_id (1), str_value (5), ref_value (7)."""
    buf = memoryview(raw)
    out: Dict[str, Dict[str, str]] = {}
    for fn, plane in _fields(buf):
        if fn != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(buf, *plane):
            if pf == 2:
                name = _text(buf, v)
            elif pf == 4:
                meta = _entry(buf, v)[1]
                if meta:                  # an entry without a value
                    events.append(meta)
            elif pf == 5:
                key, meta = _entry(buf, v)
                for mf, mv in _fields(buf, *(meta or (0, 0))):
                    if mf == 2:
                        stat_names[key] = _text(buf, mv)
        if not name.startswith(DEVICE_PLANE):
            continue
        want = {k for k, v in stat_names.items() if v == OP_NAME_STAT}
        ops: Dict[str, str] = {}
        for ev in events:
            ev_name, op = "", None
            for ef, ev_val in _fields(buf, *ev):
                if ef == 2:
                    ev_name = _text(buf, ev_val)
                elif ef == 5:
                    stat = dict(_fields(buf, *ev_val))
                    if stat.get(1) not in want:
                        continue
                    if 5 in stat:
                        op = _text(buf, stat[5])
                    elif 7 in stat:
                        op = stat_names.get(stat[7])
            if op:
                ops[ev_name] = op
        out[name] = ops
    return out


def op_scopes(op_name: str) -> Tuple[str, ...]:
    """The ``dplasma.*`` named scopes of an HLO op name, outermost
    first, without the prefix."""
    from dplasma_tpu.observability.phases import SCOPE_PREFIX
    return tuple(part.split("[")[0].rstrip(":")[len(SCOPE_PREFIX):]
                 for part in op_name.split("/")
                 if part.startswith(SCOPE_PREFIX))


def _jax_timeline(logdir: str) -> List[dict]:
    """The device ops of a ``jax.profiler`` capture under ``logdir``:
    every event of the ``XLA Ops`` line of each ``/device:TPU:<n>``
    plane of its ``.xplane.pb`` files (read with
    ``jax.profiler.ProfileData``), on rank ``n``, named by its HLO
    instruction (``fusion.12``) and carrying its named scopes. An
    empty list means the capture holds no device ops."""
    from jax.profiler import ProfileData
    out: List[dict] = []
    for path in sorted(glob.glob(os.path.join(
            logdir, "**", "*.xplane.pb"), recursive=True)):
        with open(path, "rb") as f:
            raw = f.read()
        names = xplane_op_names(raw)
        for plane in ProfileData.from_serialized_xspace(raw).planes:
            if not plane.name.startswith(DEVICE_PLANE):
                continue
            rank = int(plane.name[len(DEVICE_PLANE):])
            ops = names.get(plane.name, {})
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    hlo = ev.name.split(" = ", 1)[0].strip().lstrip("%")
                    out.append(timeline_op(
                        hlo, rank, ev.start_ns,
                        ev.start_ns + ev.duration_ns,
                        scope=op_scopes(ops.get(ev.name, ""))))
    return out


class DevprofCapture:
    """Context manager around the timed loop: starts/stops the
    ``jax.profiler`` trace when the resolved backend is ``jax``,
    otherwise a no-op whose caller synthesizes the timeline
    afterwards. ``self.events`` holds the captured timeline ops
    (empty on the synthetic path or an event-less capture);
    ``self.used`` names the backend that actually produced them."""

    def __init__(self, backend: Optional[str] = None,
                 logdir: Optional[str] = None):
        want = (backend or _cfg.mca_get("devprof.backend")
                or "auto").strip().lower()
        self.backend = want
        self.logdir = logdir
        self.events: List[dict] = []
        self.used = "synthetic"
        self.note = ""
        self._active = False

    def _resolve(self) -> str:
        if self.backend == "auto":
            try:
                import jax
                return ("jax" if jax.default_backend() != "cpu"
                        else "synthetic")
            except Exception as exc:  # noqa: BLE001 — no jax at all
                self.note = f"auto: no jax backend ({exc!r})"
                return "synthetic"
        return self.backend

    def __enter__(self) -> "DevprofCapture":
        if self._resolve() == "jax":
            try:
                import jax
                self.logdir = self.logdir or tempfile.mkdtemp(
                    prefix="devprof_")
                jax.profiler.start_trace(self.logdir)
                self._active = True
            except Exception as exc:  # noqa: BLE001 — capture is
                # best-effort observability; a profiler that cannot
                # start must not kill the timed run it watches
                self.note = f"jax profiler unavailable: {exc!r}"
        return self

    def __exit__(self, *exc_info) -> bool:
        if self._active:
            self._active = False
            try:
                import jax
                jax.profiler.stop_trace()
                self.events = _jax_timeline(self.logdir)
            except Exception as exc:  # noqa: BLE001 — same contract
                self.note = f"jax profiler stop failed: {exc!r}"
                self.events = []
            if self.events:
                self.used = "jax"
            elif not self.note:
                self.note = ("jax capture holds no device ops (no "
                             f"{OPS_LINE!r} events on a "
                             f"{DEVICE_PLANE}<n> plane); synthetic "
                             "timeline used")
        return False


# ---------------------------------------------------------------------
# Synthetic timeline (the CPU-mesh backend)
# ---------------------------------------------------------------------

def _class_of_model_key(key: str) -> str:
    """``spmd_comm_model`` byte key -> spmdcheck class key, the same
    parse rule :func:`dplasma_tpu.analysis.spmdcheck.model_classes`
    applies (``panel_bcast_psum_q`` -> ``psum@q``,
    ``pivot_row_ring_shift_p`` -> ``ring_shift@p``)."""
    base, _, axis = key.rpartition("_")
    kind = base.rsplit("_", 1)[-1]
    kind = {"allgather": "all_gather", "bcast": "ring_bcast",
            "shift": "ring_shift"}.get(kind, kind)
    return f"{kind}@{axis}"


def model_bytes_by_class(model: Optional[dict]) -> Dict[str, float]:
    """Collapse a ``spmd_comm_model`` result's per-collective bytes
    onto spmdcheck class keys (several model keys may share one class:
    potrf's panel and diagonal broadcasts are both ``psum`` classes on
    different axes)."""
    out: Dict[str, float] = {}
    for key, val in ((model or {}).get("bytes_by_collective")
                     or {}).items():
        cls = _class_of_model_key(key)
        out[cls] = out.get(cls, 0.0) + float(val)
    return out


def _span_name(cls: str, seq: int) -> str:
    """An HLO-shaped op name for one synthetic collective instance —
    the names must round-trip through the shared hlocheck vocabulary
    (``psum@q`` -> ``all-reduce.7``; ring classes -> the
    ``dplasma_ring_`` custom-call marker)."""
    kind = cls.split("@", 1)[0]
    hlo = JAXPR_TO_HLO.get(kind, kind)
    if hlo == "ring-dma":
        leg = kind[5:] if kind.startswith("ring_") else kind
        return f"custom-call.{seq} {RING_MARKER}{leg}"
    return f"{hlo}.{seq}"


def synthesize_timeline(run_s: float, nranks: int,
                        counts: Optional[Dict[str, int]] = None,
                        bytes_by_class: Optional[Dict[str, float]] = None,
                        peaks: Optional[dict] = None,
                        base_ns: int = 0) -> List[dict]:
    """Reconstruct a per-rank device timeline from one timed run.

    Each rank's lane covers exactly ``[base_ns, base_ns + run_s)``:
    every expected collective instance (``counts``, spmdcheck class
    keys) becomes one span whose duration is its class's per-rank
    modeled wire bytes (``bytes_by_class``, TOTAL bytes across ranks)
    over the roofline ICI peak, instances interleaved round-robin
    across classes in the panel-step order the kernels emit; the
    remaining time fills with compute spans (``fusion.N``) between
    them. Category seconds therefore sum to ``run_s`` per rank by
    construction — the property the devprof smoke gate asserts. With
    no expected collectives the lane is one compute span."""
    R = max(int(nranks), 1)
    run_ns = max(float(run_s), 0.0) * 1e9
    counts = {k: int(v) for k, v in (counts or {}).items() if v > 0}
    bb = bytes_by_class or {}
    bps = _ici_peak_bps(peaks)
    cls_s: Dict[str, float] = {}
    for cls in sorted(counts):
        per_rank_bytes = float(bb.get(cls, 0.0)) / R
        cls_s[cls] = per_rank_bytes / bps if bps > 0 else 0.0
    total_coll = sum(cls_s.values())
    if total_coll > 0.0 and run_s > 0 and total_coll > 0.9 * run_s:
        # the model pricing exceeding the measured run means the
        # run beat the ICI peak assumption — clamp the synthetic
        # collective share so the lane still fits the measurement
        scale = 0.9 * run_s / total_coll
        cls_s = {k: v * scale for k, v in cls_s.items()}
        total_coll = sum(cls_s.values())
    # round-robin instance order across classes (panel-step shaped)
    order: List[str] = []
    if counts:
        for step in range(max(counts.values())):
            for cls in sorted(counts):
                if step < counts[cls]:
                    order.append(cls)
    n_inst = len(order)
    comp_ns = ((run_ns - total_coll * 1e9) / (n_inst + 1)
               if run_ns > 0 else 0.0)
    ops: List[dict] = []
    for r in range(R):
        cursor = float(base_ns)
        seq = 0
        for step, cls in enumerate(order):
            end = cursor + comp_ns
            ops.append(timeline_op(f"fusion.{seq}", r,
                                   round(cursor), round(end),
                                   step=step))
            cursor, seq = end, seq + 1
            dur_ns = cls_s[cls] / counts[cls] * 1e9
            end = cursor + dur_ns
            ops.append(timeline_op(_span_name(cls, seq), r,
                                   round(cursor), round(end),
                                   cls=cls, step=step))
            cursor, seq = end, seq + 1
        ops.append(timeline_op(f"fusion.{seq}", r, round(cursor),
                               round(base_ns + run_ns),
                               step=n_inst))
    return ops


def stretch_rank(timeline: List[dict], rank: int, factor: float,
                 categories: Tuple[str, ...] = ("collective", "ici")
                 ) -> List[dict]:
    """Stretch one rank's spans of the given categories by ``factor``,
    shifting its later spans so the lane stays contiguous — the
    straggler-injection helper the skew tests (and docs examples)
    share. Other ranks pass through untouched."""
    out: List[dict] = []
    shift = 0.0
    for op in sorted(timeline,
                     key=lambda o: (o["rank"], o["begin_ns"])):
        op = dict(op)
        if op["rank"] == rank:
            dur = op["end_ns"] - op["begin_ns"]
            op["begin_ns"] = round(op["begin_ns"] + shift)
            if op.get("category") in categories:
                grow = dur * (factor - 1.0)
                shift += grow
                dur += grow
            op["end_ns"] = round(op["begin_ns"] + dur)
        out.append(op)
    return out


# ---------------------------------------------------------------------
# Ingestion + attribution
# ---------------------------------------------------------------------

def _derive_cls(name: str) -> str:
    """Class key for a captured (non-synthetic) collective span whose
    axis the profiler does not know: the HLO opcode with a wildcard
    axis."""
    low = str(name).lower()
    if RING_MARKER in low:
        return ("ring_shift@?" if "shift" in low else "ring_bcast@?")
    opcode = low.split(" ", 1)[0].split(".", 1)[0].lstrip("%")
    return f"{opcode}@?"


def _critical_path(spans: List[dict], run_s: float,
                   max_path: int) -> dict:
    """Greedy longest back-chain over the merged timeline: start at
    the latest-ending span, repeatedly hop (across ranks) to the
    latest-ending span that finishes by the current span's begin."""
    if not spans:
        return {"length_s": 0.0, "frac": 0.0, "spans": [],
                "truncated": False}
    import bisect
    ordered = sorted(spans, key=lambda s: s["end_ns"])
    ends = [s["end_ns"] for s in ordered]
    at = len(ordered) - 1
    chain = [ordered[at]]
    while True:
        # only spans ordered before the current one: a zero-length op
        # (a captured event shorter than the clock's tick) ends where
        # it begins and would otherwise be its own predecessor
        at = bisect.bisect_right(ends, ordered[at]["begin_ns"], 0, at) - 1
        if at < 0:
            break
        chain.append(ordered[at])
    chain.reverse()
    length_s = sum((s["end_ns"] - s["begin_ns"]) for s in chain) / 1e9
    rows = [{"name": s["name"], "rank": s["rank"],
             "category": s.get("category")
             or timeline_category(s["name"]),
             "dur_s": (s["end_ns"] - s["begin_ns"]) / 1e9}
            for s in chain]
    truncated = len(rows) > max_path
    if truncated:
        keep = sorted(sorted(range(len(rows)),
                             key=lambda i: -rows[i]["dur_s"])
                      [:max_path])
        rows = [rows[i] for i in keep]
    return {"length_s": length_s,
            "frac": (length_s / run_s if run_s > 0 else 0.0),
            "spans": rows, "truncated": truncated}


def ingest(timeline: List[dict], run_s: float, nranks: int,
           peaks: Optional[dict] = None,
           expected: Optional[Dict[str, int]] = None,
           bytes_by_class: Optional[Dict[str, float]] = None,
           op: str = "", label: str = "",
           backend: str = "synthetic",
           floor: Optional[float] = None,
           max_path: Optional[int] = None) -> dict:
    """Ingest one captured/synthesized timeline into the run-report
    ``"devprof"`` entry: category seconds, per-collective
    measured seconds + achieved bytes/s + achieved-ICI fraction,
    schedule reconciliation, skew/straggler attribution, and the
    critical path. ``expected`` is the spmdcheck schedule (class key
    -> per-rank count); ``bytes_by_class`` the comm model's TOTAL
    wire bytes per class."""
    if floor is None:
        floor = _cfg.mca_get_float("devprof.ici_floor", 0.05)
    if max_path is None:
        max_path = max(_cfg.mca_get_int("devprof.max_path", 32), 1)
    run_s = float(run_s)
    by_rank: Dict[int, List[dict]] = {}
    for span in timeline:
        by_rank.setdefault(int(span["rank"]), []).append(span)
    R = max(int(nranks) or len(by_rank), 1)
    ranks = sorted(by_rank) or [0]
    n_lanes = max(len(ranks), 1)
    diagnostics: List[dict] = []

    # -- category seconds (mean across rank lanes) --------------------
    rank_cat = {r: dict.fromkeys(CATEGORIES, 0.0) for r in ranks}
    for r in ranks:
        for s in by_rank.get(r, ()):
            cat = s.get("category") or timeline_category(s["name"])
            if cat not in rank_cat[r]:
                cat = "compute"
            rank_cat[r][cat] += (s["end_ns"] - s["begin_ns"]) / 1e9
    categories = {c: sum(rank_cat[r][c] for r in ranks) / n_lanes
                  for c in CATEGORIES}
    busy = sum(categories.values())
    coverage = busy / run_s if run_s > 0 else 0.0

    # -- per-collective reconciliation --------------------------------
    cls_spans: Dict[str, List[dict]] = {}
    for span in timeline:
        cat = span.get("category") or timeline_category(span["name"])
        if cat not in ("collective", "ici"):
            continue
        cls = span.get("cls") or _derive_cls(span["name"])
        cls_spans.setdefault(cls, []).append(span)
    ici_bps = _ici_peak_bps(peaks)
    bb = bytes_by_class or {}
    collectives: List[dict] = []
    ingested: Dict[str, int] = {}
    for cls in sorted(cls_spans):
        spans = cls_spans[cls]
        per_rank_n: Dict[int, int] = {}
        for s in spans:
            per_rank_n[s["rank"]] = per_rank_n.get(s["rank"], 0) + 1
        count = max(per_rank_n.values())
        ingested[cls] = count
        measured_s = sum((s["end_ns"] - s["begin_ns"])
                         for s in spans) / 1e9 / n_lanes
        kind = cls.split("@", 1)[0]
        row = {"cls": cls, "hlo": JAXPR_TO_HLO.get(kind, kind),
               "count": count,
               "measured_s": measured_s,
               "model_bytes": None, "achieved_bytes_per_s": None,
               "achieved_frac": None}
        if cls in bb:
            per_rank_bytes = float(bb[cls]) / R
            row["model_bytes"] = float(bb[cls])
            if measured_s > 0:
                achieved = per_rank_bytes / measured_s
                row["achieved_bytes_per_s"] = achieved
                if ici_bps > 0:
                    frac = achieved / ici_bps
                    row["achieved_frac"] = frac
                    if 0.0 < floor and frac < floor:
                        diagnostics.append({
                            "kind": "ici-floor", "op": cls,
                            "message":
                                f"{label or op}: collective {cls} "
                                f"achieved {achieved:.4g} B/s = "
                                f"{frac:.4f} of the ICI peak "
                                f"({ici_bps:.4g} B/s), under the "
                                f"devprof.ici_floor {floor:g}"})
        collectives.append(row)

    if expected is None:
        relation = "unmodelled" if ingested else "no-collectives"
    else:
        bad = False
        for cls in sorted(expected):
            want = int(expected[cls])
            got = ingested.get(cls, 0)
            if got == 0:
                bad = True
                diagnostics.append({
                    "kind": "missing-collective", "op": cls,
                    "message":
                        f"{label or op}: collective {cls} expected "
                        f"{want} instance(s) by the spmdcheck "
                        f"schedule, ingested 0 — the timeline lost "
                        f"a priced collective"})
            elif got != want:
                bad = True
                diagnostics.append({
                    "kind": "count-mismatch", "op": cls,
                    "message":
                        f"{label or op}: collective {cls} expected "
                        f"{want} instance(s), ingested {got}"})
        for cls in sorted(set(ingested) - set(expected)):
            diagnostics.append({
                "kind": "unmodelled-collective", "op": cls,
                "message":
                    f"{label or op}: ingested collective {cls} "
                    f"({ingested[cls]} instance(s)) is absent from "
                    f"the spmdcheck schedule (informational)"})
        relation = "==" if not bad else "mismatch"

    # -- skew / straggler attribution ---------------------------------
    rank_busy = {r: sum(rank_cat[r].values()) for r in ranks}
    slowest = max(ranks, key=lambda r: (rank_busy[r], r))
    b_max = rank_busy[slowest]
    b_min = min(rank_busy.values())
    skew_v = (b_max - b_min) / b_max if b_max > 0 else 0.0
    others = [r for r in ranks if r != slowest]
    dom, dom_excess = None, 0.0
    for c in CATEGORIES:
        mean_other = (sum(rank_cat[r][c] for r in others)
                      / len(others)) if others else 0.0
        excess = rank_cat[slowest][c] - mean_other
        if dom is None or excess > dom_excess:
            dom, dom_excess = c, excess
    if dom_excess <= 0:
        dom = max(CATEGORIES, key=lambda c: rank_cat[slowest][c])
    step_rank: Dict[int, Dict[int, float]] = {}
    for span in timeline:
        st = span.get("step")
        if st is None:
            continue
        d = step_rank.setdefault(int(st), {})
        r = int(span["rank"])
        d[r] = d.get(r, 0.0) + (span["end_ns"] - span["begin_ns"]) / 1e9
    spreads = [max(d.values()) - min(d.values())
               for d in step_rank.values() if len(d) > 1]
    skew = {"value": skew_v, "slowest_rank": int(slowest),
            "dominating_category": dom,
            "per_rank_s": [rank_busy[r] for r in ranks],
            "ranks": [int(r) for r in ranks],
            "max_step_spread_s": max(spreads) if spreads else 0.0}

    critical = _critical_path(timeline, run_s, max_path)
    ok = not any(d["kind"] in ("missing-collective", "count-mismatch")
                 for d in diagnostics)
    return {"label": label, "op": op, "backend": backend,
            "nranks": R, "run_s": run_s,
            "categories": categories, "coverage": coverage,
            "timeline_ops": len(timeline),
            "collectives": collectives,
            "reconciliation": {"relation": relation,
                               "expected": expected,
                               "ingested": ingested},
            "skew": skew, "critical_path": critical,
            "diagnostics": diagnostics, "ok": ok}


# ---------------------------------------------------------------------
# The one-call front door (drivers / multichip / autotune)
# ---------------------------------------------------------------------

def attribute(label: str, op_class: Optional[str], run_s: float,
              grid: Tuple[int, int], M: int, N: int, nb: int,
              itemsize: int = 8, kt: Optional[int] = None,
              ring: bool = False, lookahead: int = 0,
              peaks: Optional[dict] = None,
              timeline: Optional[List[dict]] = None,
              backend: str = "synthetic") -> dict:
    """Model-assemble and ingest one op's attribution: the spmdcheck
    expected schedule + the spmd_comm_model pricing for
    ``(op_class, grid, M, N, nb)``, a synthetic timeline when the
    capture produced none, and the full :func:`ingest` pass. A 1x1
    grid (or an unmodelled op) attributes honestly as all-compute
    with no reconciliation rather than guessing."""
    P, Q = max(int(grid[0]), 1), max(int(grid[1]), 1)
    R = P * Q
    expected = None
    bytes_by_class = None
    if op_class and R > 1:
        from dplasma_tpu.analysis import spmdcheck
        KT = kt if kt is not None else max(
            min(-(-int(M) // int(nb)), -(-int(N) // int(nb))), 1)
        expected = spmdcheck.expected_counts(
            op_class, KT, lookahead, ring=ring, grid=(P, Q))
        try:
            from dplasma_tpu.descriptors import Dist
            from dplasma_tpu.parallel.cyclic import (CyclicDesc,
                                                     spmd_comm_model)
            model = spmd_comm_model(
                CyclicDesc(int(M), int(N), int(nb), int(nb),
                           Dist(P=P, Q=Q)),
                op_class, int(itemsize), kt=kt, ring=ring)
            bytes_by_class = model_bytes_by_class(model)
        except KeyError:
            bytes_by_class = None
    if peaks is None:
        from dplasma_tpu.observability.roofline import DEFAULT_PEAKS
        peaks = DEFAULT_PEAKS
    if timeline is None:
        timeline = synthesize_timeline(run_s, R, counts=expected,
                                       bytes_by_class=bytes_by_class,
                                       peaks=peaks)
        backend = "synthetic"
    return ingest(timeline, run_s, R, peaks=peaks, expected=expected,
                  bytes_by_class=bytes_by_class, op=op_class or "",
                  label=label, backend=backend)
