"""Compiled-artifact auditor: verify the HLO the device actually runs.

dagcheck proves the analytic tile DAG, spmdcheck proves the
jaxpr-level collective schedule, palcheck proves the Pallas kernel
contracts — but the artifact the TPU executes is the post-GSPMD
compiled HLO, and nothing above this module inspects it. GSPMD can
silently insert resharding all-gathers the jaxpr never showed, drop a
requested buffer donation (doubling HBM at scale), or demote precision
through ``convert`` chains the f64-equivalent routes never authorized.
This module closes the jaxpr -> HLO verification gap with five static
checks over the *exact* executables a driver is about to run (the
``lowered``/``compiled`` pair :meth:`Driver._lower_compile` already
produces):

1. **collective reconciliation** — parse ``all-reduce`` /
   ``all-gather`` / ``reduce-scatter`` / ``collective-permute`` /
   ``all-to-all`` ops out of the compiled module text and reconcile
   per-kind counts against the jaxpr-level schedule spmdcheck
   extracts from the same program (exact ``==`` by default) and
   against :func:`dplasma_tpu.parallel.cyclic.spmd_comm_model`'s
   priced classes (exact-or-dominating) — a GSPMD-*inserted* hidden
   collective is a failure naming the op and the surplus kind;
2. **precision contract** — scan ``convert`` ops for float demotions
   below the route's working precision outside the registered dd/limb
   sites (:data:`PRECISION_SITES` — the HLO-level twin of jaxlint
   J005 and palcheck's f64 rule);
3. **donation audit** — requested ``donate_argnums``
   (``lowered.args_info``) must have produced real input-output
   aliasing in the compiled header (``input_output_alias``); a
   dropped donation is flagged with the buffer size;
4. **HBM budget** — ``memory_analysis`` peak bytes vs the MCA
   ``hlocheck.hbm_budget`` knob, naming the worst temp buffer;
5. **anti-pattern sweep** — host callbacks / infeed / outfeed in the
   hot path, and ``copy``/``transpose`` byte volume above the MCA
   ``hlocheck.copy_frac`` fraction of all bytes the module produces.

Wired as ``--hlocheck`` on every driver (verify-before-timed-loop,
abort via :class:`HloCheckError`, run-report schema v10 ``"hlocheck"``
section + ``hlocheck_*`` metrics), into the serving executable cache
(every compiled entry is audited on admission, MCA
``hlocheck.serving``), and into ``tools/lint_all.py`` as the
``hlocheck-smoke`` gate over the cyclic kernels and one serving
batched executable.
"""
from __future__ import annotations

import re
from collections import Counter
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from dplasma_tpu.utils import config as _cfg

_cfg.mca_register(
    "hlocheck.hbm_budget", "0",
    "Device-memory budget (bytes) the compiled executable's peak "
    "(memory_analysis) must fit under; 0 disables the check. The "
    "diagnostic names the worst temp buffer in the module.")
_cfg.mca_register(
    "hlocheck.copy_frac", "0.5",
    "Maximum fraction of the module's produced bytes that may come "
    "out of copy/transpose ops (data movement XLA inserted, not "
    "math); above it the biggest copy is named. The cyclic kernels "
    "measure <= ~8% and the GSPMD-partitioned drivers <= ~29% at "
    "tiny shapes (the ratio shrinks as compute grows cubically).")
_cfg.mca_register(
    "hlocheck.serving", "on",
    "on = audit every executable the serving cache compiles "
    "(donation/precision/HBM/anti-patterns; diagnostics are recorded "
    "on the entry and in serving_hlocheck_* metrics, never fatal); "
    "off = skip.")

# The opcode vocabulary is shared with the measured-timeline side
# (observability.devprof bins profiler rows against the same names) —
# one table, every reader: dplasma_tpu.analysis.hlo_names. The
# module-private aliases keep this module's established spellings.
from dplasma_tpu.analysis.hlo_names import (  # noqa: E402
    CALLBACK_MARKERS as _SHARED_CALLBACK_MARKERS,
    HLO_COLLECTIVES as _HLO_COLLECTIVES,
    JAXPR_TO_HLO as _JAXPR_TO_HLO,
    RING_MARKER as _RING_MARKER,
)

#: repo-relative module suffixes whose converts are the AUTHORIZED
#: precision ladder: the dd/limb emulation (f64 <-> f32 limb splits
#: are the route), the panel engine's f32 tree seed, and the IR
#: solvers' deliberate factor-in-low working precision
PRECISION_SITES = [
    "kernels/dd.py", "kernels/pallas_dd.py", "kernels/panels.py",
    "ops/refine.py",
]

#: declared float->integer demotions: exact ``(site_suffix, src,
#: dst)`` triples the precision audit accepts — narrower than
#: PRECISION_SITES on purpose (a site may quantize f32 to s8 and
#: nothing else; an f64->s8 convert there is still a bug). The one
#: registered demotion is the block-scaled int8 quantizer's
#: round-to-int8 store (kernels.quant, the ir.precision=int8 rung).
DECLARED_DEMOTIONS = [
    ("kernels/quant.py", "f32", "s8"),
]

#: integer dtype -> carried width in bits: a float CONVERTING into one
#: of these is a precision demotion the audit must see (f32 -> s8 is
#: the quantizer's defining move — and an accident anywhere else)
_INT_BITS = {"s8": 8, "u8": 8, "s4": 4, "u4": 4, "s16": 16, "u16": 16}

#: custom-call targets that are host round-trips in disguise
_CALLBACK_MARKERS = _SHARED_CALLBACK_MARKERS

#: float/complex dtype -> mantissa-carrying width in bits (complex
#: compares by component width: c128 -> c64 loses half the mantissa
#: exactly as f64 -> f32 does)
_FLOAT_BITS = {
    "f64": 64, "f32": 32, "f16": 16, "bf16": 16,
    "f8e5m2": 8, "f8e4m3fn": 8, "f8e4m3b11fnuz": 8, "f8e5m2fnuz": 8,
    "f8e4m3fnuz": 8,
    "c128": 64, "c64": 32,
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
    "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
    "f8e5m2": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1,
    "f8e4m3fnuz": 1,
}

#: working float width per driver precision letter (complex tracks the
#: component width)
PREC_BITS = {"s": 32, "d": 64, "c": 32, "z": 64}


class HloCheckError(ValueError):
    """A compiled executable failed artifact verification."""

    def __init__(self, result: "HloResult"):
        self.result = result
        lines = [d.message for d in result.diagnostics[:8]]
        more = len(result.diagnostics) - len(lines)
        if more > 0:
            lines.append(f"... and {more} more")
        super().__init__("HLO artifact verification failed:\n  " +
                         "\n  ".join(lines))


@dataclass(frozen=True)
class HloDiagnostic:
    """One verification failure, naming the offending HLO op/buffer."""

    kind: str        # surplus-collective|missing-collective|
    #                # model-mismatch|precision-demotion|
    #                # dropped-donation|hbm-budget|host-callback|
    #                # copy-volume
    message: str
    kernel: str = ""
    op: str = ""     # HLO instruction name (%all-gather.5, ...)
    detail: Optional[dict] = None

    def as_dict(self) -> dict:
        return {"kind": self.kind, "message": self.message,
                "kernel": self.kernel, "op": self.op,
                "detail": self.detail}


@dataclass(frozen=True)
class HloOp:
    """One parsed HLO instruction (result side + opcode + raw line)."""

    name: str                 # result name without the leading %
    opcode: str
    dtype: str                # result element type ('' for tuples)
    shape: Tuple[int, ...]    # result dims (() for tuples/scalars)
    bytes: int                # result buffer bytes (tuple = sum)
    line: str                 # the full instruction line (attrs)
    source: str = ""          # innermost user frame's file, from the
    source_line: int = 0      # module's stack-frame tables


@dataclass
class HloModule:
    """Light structural view of one compiled module's text."""

    name: str = ""
    ops: List[HloOp] = field(default_factory=list)
    #: output-index-string -> parameter number, from the header's
    #: input_output_alias={ {idx}: (param, {...}, kind), ... }
    aliased_params: Dict[str, int] = field(default_factory=dict)
    num_partitions: int = 1
    #: parameter count of the ENTRY computation (reduce regions etc.
    #: have their own parameters — those don't count)
    entry_params: int = 0
    #: instruction name -> result element type (operands print bare)
    dtypes: Dict[str, str] = field(default_factory=dict)

    def count(self, opcode: str) -> int:
        return sum(1 for o in self.ops if o.opcode == opcode)

    @property
    def collective_counts(self) -> Dict[str, int]:
        c: Counter = Counter()
        for o in self.ops:
            kind = _HLO_COLLECTIVES.get(o.opcode)
            if kind:
                # the collective combiner merges independent exchanges
                # into one op with several operands: count each
                m = re.search(re.escape(o.opcode) + r"\(([^)]*)\)",
                              o.line)
                c[kind] += max(m.group(1).count("%") if m else 1, 1)
            elif o.opcode == "custom-call" and _RING_MARKER in o.line:
                # a Mosaic-lowered explicit ICI-ring kernel: wire
                # traffic exactly like the named collectives
                c["ring-dma"] += 1
        return dict(c)


@dataclass
class HloResult:
    """Outcome of :func:`check_executable` (JSON-able via summary())."""

    kernel: str = ""
    ok: bool = True
    counts: Dict[str, int] = field(default_factory=dict)
    expected: Optional[Dict[str, int]] = None
    #: == (exact match) | >= (dominating: compiled implements the
    #: pinned schedule plus partitioner-owned extras) | mismatch
    #: (failed reconciliation) | gspmd (pure-GSPMD program, the
    #: partitioner owns the schedule) | unreconciled (no schedule
    #: given, collectives present) | no-collectives
    relation: Optional[str] = None
    donated: int = 0                 # requested donations
    aliased: int = 0                 # delivered aliases
    hbm_peak_bytes: Optional[int] = None
    hbm_budget: int = 0
    copy_bytes: int = 0
    total_bytes: int = 0
    diagnostics: List[HloDiagnostic] = field(default_factory=list)

    def add(self, kind: str, message: str, op: str = "",
            detail=None) -> None:
        self.ok = False
        self.diagnostics.append(
            HloDiagnostic(kind, message, self.kernel, op, detail))

    def summary(self) -> dict:
        return {"ok": self.ok, "kernel": self.kernel,
                "counts": dict(self.counts),
                "expected": self.expected, "relation": self.relation,
                "donated": self.donated, "aliased": self.aliased,
                "hbm_peak_bytes": self.hbm_peak_bytes,
                "hbm_budget": self.hbm_budget,
                "copy_bytes": self.copy_bytes,
                "total_bytes": self.total_bytes,
                "diagnostics": [d.as_dict() for d in self.diagnostics]}

    def format(self, label: str = "") -> str:
        head = f"#+ hlocheck[{label or self.kernel}]: "
        if self.ok:
            total = sum(self.counts.values())
            rel = f", schedule {self.relation}" if self.relation else ""
            peak = (f", peak {self.hbm_peak_bytes} B"
                    if self.hbm_peak_bytes is not None else "")
            return (head + f"OK ({total} collective(s){rel}, "
                    f"{self.aliased}/{self.donated} donation(s) "
                    f"delivered{peak})")
        lines = [head + f"{len(self.diagnostics)} violation(s)"]
        lines += [f"#!   {d.kind}: {d.message}"
                  for d in self.diagnostics]
        return "\n".join(lines)


# ---------------------------------------------------------------------
# HLO text parsing
# ---------------------------------------------------------------------

#: one instruction: `  [ROOT] %name = TYPE opcode(...), attrs...`
#: where TYPE is `f32[4,4]{1,0}` or a tuple `(f32[4]{0}, s32[])`
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*"
    r"((?:\([^=]*?\))|(?:[a-zA-Z0-9]+\[[^\]]*\](?:\{[^ ]*\})?))\s+"
    r"([a-zA-Z][\w\-]*)\(")

_SHAPE_RE = re.compile(r"([a-zA-Z][a-zA-Z0-9]*)\[([0-9,]*)\]")

_ALIAS_ENTRY_RE = re.compile(r"\{([0-9, ]*)\}:\s*\((\d+)")


def _alias_block(header: str) -> str:
    """The brace-balanced body of ``input_output_alias={...}`` (the
    entries nest braces, so a non-greedy regex would stop early)."""
    i = header.find("input_output_alias={")
    if i < 0:
        return ""
    j = i + len("input_output_alias={")
    depth = 1
    for k in range(j, len(header)):
        if header[k] == "{":
            depth += 1
        elif header[k] == "}":
            depth -= 1
            if depth == 0:
                return header[j:k]
    return header[j:]


def shape_bytes(type_str: str) -> Tuple[str, Tuple[int, ...], int]:
    """(dtype, dims, bytes) of one HLO type string; tuples sum their
    element bytes and report dtype '' / dims ()."""
    total = 0
    first = None
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims_s = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims_s.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
        if first is None:
            first = (dt, tuple(int(d) for d in dims_s.split(",")
                               if d.strip()))
    if first is None:
        return "", (), 0
    if type_str.lstrip().startswith("("):
        return "", (), total
    return first[0], first[1], total


def _stack_frames(text: str) -> Dict[int, Tuple[str, int]]:
    """stack_frame_id -> (file, line) from the module's FileNames /
    FileLocations / StackFrames tables (where op metadata points)."""
    tables: Dict[str, Dict[int, str]] = {}
    cur = None
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            cur = tables.setdefault(line, {})
            continue
        m = re.match(r"(\d+) (.*)$", line) if cur is not None else None
        if m is None:
            cur = None
            continue
        cur[int(m.group(1))] = m.group(2)
    files = {k: v.strip('"') for k, v in
             tables.get("FileNames", {}).items()}
    locs = {}
    for k, v in tables.get("FileLocations", {}).items():
        f = re.search(r"file_name_id=(\d+)", v)
        ln = re.search(r"\bline=(\d+)", v)
        locs[k] = (files.get(int(f.group(1)), "") if f else "",
                   int(ln.group(1)) if ln else 0)
    frames = {}
    for k, v in tables.get("StackFrames", {}).items():
        loc = re.search(r"file_location_id=(\d+)", v)
        frames[k] = locs.get(int(loc.group(1)), ("", 0)) if loc \
            else ("", 0)
    return frames


def parse_module(text: str) -> HloModule:
    """Parse one compiled module's text (``compiled.as_text()``) into
    its structural view: header aliasing + every instruction's result
    type, opcode and source frame. Parsing is line-based and forgiving
    — an HLO line the grammar does not recognize is skipped, never
    fatal (the checks only reason about ops that parsed)."""
    mod = HloModule()
    frames = _stack_frames(text)
    header, _, body = text.partition("\n")
    m = re.search(r"HloModule\s+([\w.\-]+)", header)
    if m:
        mod.name = m.group(1)
    m = re.search(r"num_partitions=(\d+)", header)
    if m:
        mod.num_partitions = int(m.group(1))
    for e in _ALIAS_ENTRY_RE.finditer(_alias_block(header)):
        mod.aliased_params[e.group(1).strip()] = int(e.group(2))
    in_entry = False
    for line in body.splitlines():
        if line.startswith("ENTRY "):
            in_entry = True
        elif in_entry and line.rstrip() == "}":
            in_entry = False
        # long tuple types carry /*index=N*/ position comments
        om = _OP_RE.match(re.sub(r"/\*index=\d+\*/", "", line))
        if not om:
            continue
        name, type_str, opcode = om.groups()
        dtype, shape, nbytes = shape_bytes(type_str)
        if in_entry and opcode == "parameter":
            mod.entry_params += 1
        sf = re.search(r"stack_frame_id=(\d+)", line)
        source, source_line = frames.get(int(sf.group(1)), ("", 0)) \
            if sf else ("", 0)
        mod.ops.append(HloOp(name=name, opcode=opcode, dtype=dtype,
                             shape=shape, bytes=nbytes, line=line,
                             source=source, source_line=source_line))
        mod.dtypes[name] = dtype
    return mod


def _convert_types(mod: HloModule, op: HloOp
                   ) -> Optional[Tuple[str, str]]:
    """(src_dtype, dst_dtype) of a convert instruction, None when the
    operand's type is unknown."""
    m = re.search(r"convert\(%([\w.\-]+)\)", op.line)
    src = mod.dtypes.get(m.group(1)) if m else None
    if not src or not op.dtype:
        return None
    return src, op.dtype


# ---------------------------------------------------------------------
# the five checks
# ---------------------------------------------------------------------

def schedule_counts(schedule) -> Dict[str, int]:
    """Collapse a spmdcheck :class:`SpmdResult`'s per-(kind, axis)
    collective schedule to the per-HLO-opcode counts its lowering
    produces (psum/pmax/pmin all become ``all-reduce``)."""
    c: Counter = Counter()
    for col in schedule.collectives:
        kind = _JAXPR_TO_HLO.get(col.kind)
        if kind:
            c[kind] += col.count
    return dict(c)


def check_collectives(mod: HloModule, res: HloResult,
                      expected: Optional[Dict[str, int]],
                      exact: bool = True,
                      model: Optional[Dict[str, int]] = None) -> None:
    """Reconcile the compiled module's per-kind collective counts
    against the jaxpr-level schedule of the same program under the
    exact-or-dominating contract: ``exact=True`` (the cyclic kernels
    themselves — the program IS the shard_map kernel) demands ``==``
    in both directions, so a GSPMD-inserted hidden collective OR a
    dropped one is a named failure; ``exact=False`` (driver programs
    that wrap a kernel in GSPMD-sharded conversions) demands
    ``compiled >= traced`` per kind — the pinned schedule must be
    fully implemented, while the partitioner may add collectives for
    the sharded wrapping it owns. When given, the analytic comm
    model's priced per-kind counts must also be dominated (every
    priced class present at full multiplicity)."""
    got = mod.collective_counts
    res.counts = got
    if expected is None or (not expected and got
                            and mod.num_partitions > 1):
        # no traced schedule to reconcile against, or a pure-GSPMD
        # partitioned program (no explicit shard_map collectives in
        # the jaxpr): the partitioner OWNS that schedule — record the
        # counts, don't second-guess them (spmdcheck draws the same
        # line). The reconciliation contract binds exactly where the
        # jaxpr pinned a schedule: a shard_map program GSPMD must
        # neither add to nor subtract from.
        if expected is None:
            res.relation = "unreconciled" if got else "no-collectives"
        else:
            res.relation = "gspmd"
    else:
        res.expected = dict(expected)
        bad = False
        for kind in sorted(set(got) | set(expected)):
            g, e = got.get(kind, 0), expected.get(kind, 0)
            if g > e and exact:
                bad = True
                first = next((o for o in mod.ops
                              if _HLO_COLLECTIVES.get(o.opcode)
                              == kind), None)
                res.add("surplus-collective",
                        f"compiled module carries {g} {kind} op(s) "
                        f"but the traced schedule has {e} — GSPMD "
                        f"inserted {g - e} hidden collective(s) "
                        f"(e.g. %{first.name if first else '?'}); a "
                        f"resharding the jaxpr never showed",
                        op=first.name if first else "",
                        detail={"kind": kind, "compiled": g,
                                "traced": e})
            elif g < e:
                bad = True
                res.add("missing-collective",
                        f"compiled module carries {g} {kind} op(s) "
                        f"but the traced schedule has {e} — the "
                        f"compiler dropped {e - g} collective(s) the "
                        f"schedule pinned; a rank waiting on the "
                        f"dropped exchange desynchronizes",
                        detail={"kind": kind, "compiled": g,
                                "traced": e})
        if bad:
            res.relation = "mismatch"
        else:
            res.relation = "==" if got == expected else ">="
    if model:
        for kind, n in sorted(model.items()):
            g = got.get(kind, 0)
            if g < n:
                res.add("model-mismatch",
                        f"compiled module carries {g} {kind} op(s) "
                        f"but the analytic comm model prices "
                        f"{n} — the executable cannot implement the "
                        f"collective structure the model charges for",
                        detail={"kind": kind, "compiled": g,
                                "model": n})


def model_counts(op: Optional[str], KT: int, lookahead: int = 0,
                 ring: bool = False,
                 grid: Tuple[int, int] = (1, 1)
                 ) -> Optional[Dict[str, int]]:
    """Per-HLO-kind collective counts the analytic comm model prices
    for one cyclic kernel (spmdcheck's per-(kind, axis) table,
    collapsed through the same lowering map). ``ring``/``grid``
    select the explicit ICI-ring schedule's table — its ring classes
    land on the "ring-dma" kind the custom-call counter produces."""
    from dplasma_tpu.analysis import spmdcheck as sp
    if not op or KT <= 0:
        return None
    exp = sp.expected_counts(op, KT, lookahead, ring=ring, grid=grid)
    if exp is None:
        return None
    c: Counter = Counter()
    for key, n in exp.items():
        kind = _JAXPR_TO_HLO.get(key.split("@", 1)[0])
        if kind:
            c[kind] += n
    return dict(c)


def check_precision(mod: HloModule, res: HloResult,
                    working_bits: int,
                    sites: Optional[List[str]] = None) -> None:
    """Every ``convert`` that narrows a float below the route's
    working precision must come from a registered dd/limb site
    (matched on the instruction's ``source_file`` metadata) — the
    compiled twin of jaxlint J005. Float->INTEGER narrowing (the
    quantizer's f32 -> s8 store) is held to the stricter
    :data:`DECLARED_DEMOTIONS` allowlist: the exact (site, src, dst)
    triple must be declared, so the intentional int8 trailing updates
    pass while an accidental quantize anywhere else still fails."""
    sites = PRECISION_SITES if sites is None else sites
    for op in mod.ops:
        if op.opcode != "convert":
            continue
        ct = _convert_types(mod, op)
        if ct is None:
            continue
        src, dst = ct
        sb = _FLOAT_BITS.get(src)
        db = _FLOAT_BITS.get(dst)
        source = op.source.replace("\\", "/")
        if sb is not None and db is None and dst in _INT_BITS:
            # float -> integer narrowing: declared-demotion triples
            # only (PRECISION_SITES does not cover these)
            if _INT_BITS[dst] >= working_bits:
                continue
            if any(source.endswith(s) and src == ds and dst == dd
                   for s, ds, dd in DECLARED_DEMOTIONS):
                continue
            where = (f"{source}:{op.source_line}" if source
                     else "unknown site")
            res.add("precision-demotion",
                    f"%{op.name} quantizes {src} -> {dst} below the "
                    f"route's working precision ({working_bits}-bit) "
                    f"at {where} — not a declared demotion "
                    f"(DECLARED_DEMOTIONS)",
                    op=op.name,
                    detail={"src": src, "dst": dst, "source": source,
                            "source_line": op.source_line})
            continue
        if sb is None or db is None:
            continue               # integer/pred casts are not demotions
        if db >= sb or db >= working_bits:
            continue               # widening, or still at/above working
        if any(source.endswith(s) for s in sites):
            continue
        where = f"{source}:{op.source_line}" if source else "unknown site"
        res.add("precision-demotion",
                f"%{op.name} demotes {src} -> {dst} below the "
                f"route's working precision ({working_bits}-bit) at "
                f"{where} — not a registered dd/limb site "
                f"(PRECISION_SITES)",
                op=op.name,
                detail={"src": src, "dst": dst, "source": source,
                        "source_line": op.source_line})


def donation_requests(lowered) -> List[Tuple[int, bool, int]]:
    """``[(param_number, donated, buffer_bytes)]`` from a
    ``jax.stages.Lowered``'s args_info — the REQUEST side of the
    donation contract (jax keeps ``donated=True`` even when it warned
    and dropped the donation, which is exactly what this audit must
    see)."""
    import numpy as np

    import jax
    out = []
    infos = [x for x in jax.tree_util.tree_leaves(
        lowered.args_info, is_leaf=lambda x: hasattr(x, "donated"))]
    for i, info in enumerate(infos):
        try:
            nbytes = int(np.prod(info.shape, dtype=np.int64)
                         * np.dtype(info.dtype).itemsize)
        except (TypeError, ValueError):
            nbytes = 0
        out.append((i, bool(info.donated), nbytes))
    return out


def map_to_compiled_params(requests: List[Tuple[int, bool, int]],
                           compiled, mod: HloModule
                           ) -> List[Tuple[int, bool, int]]:
    """Renumber flat-argument donation requests into COMPILED
    parameter numbers: jax prunes unused arguments from the
    executable, so the header's ``input_output_alias`` counts kept
    parameters only. A pruned argument carries no buffer at all
    (donated or not) and drops out of the audit. Falls back to the
    identity map when the executable exposes no kept-index set — and
    to skipping the audit entirely when identity provably disagrees
    with the module's entry parameter count (pruning happened but is
    unmappable: better no check than a phantom dropped-donation)."""
    ex = getattr(compiled, "_executable", None)
    kept = getattr(ex, "_kept_var_idx", None)
    if kept is None:
        kept = getattr(getattr(ex, "unsafe_call", None),
                       "kept_var_idx", None)
    if kept is None:
        if mod.entry_params and mod.entry_params != len(requests):
            return []
        return requests
    pos = {flat: p for p, flat in
           enumerate(sorted(int(i) for i in kept))}
    return [(pos[i], d, nb) for i, d, nb in requests if i in pos]


def check_donation(mod: HloModule, res: HloResult,
                   requests: List[Tuple[int, bool, int]]) -> None:
    """Requested donations must appear as input-output aliases in the
    compiled header; a dropped one is flagged with the buffer size
    (the silent HBM doubling this check exists for)."""
    delivered = set(mod.aliased_params.values())
    res.donated = sum(1 for _, d, _ in requests if d)
    res.aliased = len(delivered)
    for pnum, donated, nbytes in requests:
        if donated and pnum not in delivered:
            res.add("dropped-donation",
                    f"donate_argnums requested donation of parameter "
                    f"{pnum} ({nbytes} bytes) but the compiled module "
                    f"has no input_output_alias for it — the buffer "
                    f"is carried twice (input + output live "
                    f"simultaneously)",
                    detail={"param": pnum, "bytes": nbytes})


def check_hbm(mod: HloModule, res: HloResult,
              peak_bytes: Optional[int], budget: int) -> None:
    """``memory_analysis`` peak bytes against the device budget knob;
    the diagnostic names the module's worst (largest-output)
    non-parameter op as the worst temp buffer candidate."""
    res.hbm_peak_bytes = peak_bytes
    res.hbm_budget = budget
    if budget <= 0 or peak_bytes is None or peak_bytes <= budget:
        return
    worst = None
    for op in mod.ops:
        if op.opcode in ("parameter", "constant"):
            continue
        if worst is None or op.bytes > worst.bytes:
            worst = op
    wname = f"%{worst.name}" if worst else "?"
    wdesc = (f"{wname} ({worst.dtype}"
             f"{list(worst.shape)}, {worst.bytes} bytes)"
             if worst else wname)
    res.add("hbm-budget",
            f"peak memory {peak_bytes} bytes exceeds the "
            f"hlocheck.hbm_budget of {budget} bytes; worst temp "
            f"buffer: {wdesc}",
            op=worst.name if worst else "",
            detail={"peak_bytes": peak_bytes, "budget": budget,
                    "worst_op": worst.name if worst else None,
                    "worst_bytes": worst.bytes if worst else None})


def check_antipatterns(mod: HloModule, res: HloResult,
                       copy_frac: float) -> None:
    """Host callbacks / infeed / outfeed never belong in a timed hot
    path, and copy/transpose volume above ``copy_frac`` of the bytes
    the module produces means XLA is moving data instead of computing
    (a layout/sharding mismatch upstream)."""
    copy_bytes = 0
    total_bytes = 0
    biggest = None
    for op in mod.ops:
        if op.opcode in ("infeed", "outfeed"):
            res.add("host-callback",
                    f"%{op.name} is an {op.opcode} op: the hot path "
                    f"round-trips through the host every execution",
                    op=op.name, detail={"opcode": op.opcode})
            continue
        if op.opcode == "custom-call":
            m = re.search(r'custom_call_target="([^"]+)"', op.line)
            target = m.group(1) if m else ""
            if any(k in target.lower() for k in _CALLBACK_MARKERS):
                res.add("host-callback",
                        f"%{op.name} is a host callback custom-call "
                        f"({target!r}): the hot path blocks on "
                        f"Python every execution",
                        op=op.name, detail={"target": target})
            continue
        if op.opcode == "parameter":
            continue
        total_bytes += op.bytes
        if op.opcode in ("copy", "transpose"):
            copy_bytes += op.bytes
            if biggest is None or op.bytes > biggest.bytes:
                biggest = op
    res.copy_bytes = copy_bytes
    res.total_bytes = total_bytes
    if total_bytes > 0 and copy_frac > 0 \
            and copy_bytes > copy_frac * total_bytes:
        bname = f"%{biggest.name}" if biggest else "?"
        res.add("copy-volume",
                f"copy/transpose ops produce {copy_bytes} of "
                f"{total_bytes} bytes "
                f"({100.0 * copy_bytes / total_bytes:.1f}% > "
                f"hlocheck.copy_frac {100.0 * copy_frac:.1f}%); "
                f"biggest: {bname} "
                f"({biggest.bytes if biggest else 0} bytes) — XLA is "
                f"moving data the layout should have avoided",
                op=biggest.name if biggest else "",
                detail={"copy_bytes": copy_bytes,
                        "total_bytes": total_bytes,
                        "frac": copy_bytes / total_bytes,
                        "biggest_op": biggest.name if biggest
                        else None})


# ---------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------

def check_executable(lowered, compiled, kernel: str = "", *,
                     schedule=None, exact: bool = True,
                     op: Optional[str] = None, KT: int = 0,
                     lookahead: int = 0, prec: str = "s",
                     ring: bool = False,
                     grid: Tuple[int, int] = (1, 1),
                     xla_info: Optional[dict] = None,
                     hbm_budget: Optional[int] = None,
                     copy_frac: Optional[float] = None) -> HloResult:
    """Audit one (lowered, compiled) executable pair.

    ``schedule`` is the spmdcheck :class:`~dplasma_tpu.analysis.
    spmdcheck.SpmdResult` of the SAME program (enables the exact
    jaxpr-vs-HLO collective reconciliation); ``op``/``KT`` name the
    comm-model class for the dominating model leg; ``prec`` the driver
    precision letter (working-precision floor of the convert scan);
    ``xla_info`` an :func:`dplasma_tpu.observability.xla.
    capture_compiled` dict (captured fresh when absent). Knobs default
    to the MCA tier (``hlocheck.hbm_budget``/``hlocheck.copy_frac``).
    """
    res = HloResult(kernel=kernel)
    mod = parse_module(compiled.as_text())
    expected = schedule_counts(schedule) if schedule is not None \
        else None
    check_collectives(mod, res, expected, exact=exact,
                      model=model_counts(op, KT, lookahead,
                                         ring=ring, grid=grid))
    check_precision(mod, res, PREC_BITS.get(prec, 32))
    requests = donation_requests(lowered) if lowered is not None \
        else []
    check_donation(mod, res,
                   map_to_compiled_params(requests, compiled, mod))
    if xla_info is None:
        from dplasma_tpu.observability.xla import capture_compiled
        xla_info = capture_compiled(compiled)
    peak = xla_info.get("peak_bytes")
    budget = hbm_budget if hbm_budget is not None \
        else _cfg.mca_get_int("hlocheck.hbm_budget", 0)
    check_hbm(mod, res, int(peak) if peak is not None else None,
              budget)
    if copy_frac is None:
        try:
            copy_frac = float(_cfg.mca_get("hlocheck.copy_frac",
                                           "0.5"))
        except (TypeError, ValueError):
            copy_frac = 0.5
    check_antipatterns(mod, res, copy_frac)
    return res


def verify_executable(lowered, compiled, kernel: str = "",
                      **kw) -> HloResult:
    """:func:`check_executable` that raises :class:`HloCheckError` on
    any diagnostic (the ``--hlocheck`` driver path)."""
    res = check_executable(lowered, compiled, kernel, **kw)
    if not res.ok:
        raise HloCheckError(res)
    return res
