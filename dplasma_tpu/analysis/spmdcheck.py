"""SPMD collective-schedule verifier for the shard_map kernels.

PR 3's dagcheck proves the *logical* tile DAGs race/deadlock-free; the
cyclic ``shard_map`` programs live one layer down, where a different
failure class hides: SPMD deadlocks. Every rank traces the SAME
program, so the per-rank collective sequence is uniform *unless*
collectives sit behind rank-divergent control flow — a ``lax.cond``
whose branches emit different collectives, or a data-dependent
``while`` with a collective in its body. XLA's only feedback for those
is compile-or-hang. This module extracts the collective schedule of a
traced program (jaxpr-level, tiny shapes, CPU-only — no TPU needed)
and proves, per kernel:

* **axis binding** — every collective's axis name is bound by the
  enclosing shard_map mesh (an unbound name is a trace-time error at
  best, a silently global reduction at worst);
* **uniform per-rank sequence** — no collective behind rank-divergent
  control flow: ``cond`` branches must carry *identical* collective
  subsequences, and a data-dependent ``while`` must carry none (a
  rank that skips a collective the others enter deadlocks the ring);
* **ppermute bijection** — every ``ppermute`` permutation must be a
  bijection on the axis: duplicate sources/destinations or
  out-of-range ranks leave some rank waiting on a send that never
  comes;
* **count reconciliation** — per-(kind, axis) collective counts must
  reconcile against the analytic comm model
  (:func:`dplasma_tpu.parallel.cyclic.spmd_comm_model`), the same
  exact-or-dominating contract ``check_comm`` established for DAGs:
  exact for the cyclic kernels (:func:`expected_counts` mirrors the
  per-step collective structure the model prices), dominating for
  driver programs that wrap them in conversions.

Plus an abstract **ring-schedule simulator** (:func:`simulate_ring`)
for explicit send/recv/semaphore programs — the contract future
ICI-ring kernels (``pltpu.make_async_remote_copy`` panel-broadcast
rings, ROADMAP item 2) must pass before they exist: per-device op
interleaving is executed abstractly, and a deadlock or an unpaired
DMA semaphore is a diagnostic naming the kernel, step, and rank pair.

Wired into the drivers as ``--spmdcheck`` (verify the traced program
before the timed loop; summary in run-report schema v6) and into
``tools/lint_all.py`` as a smoke gate over the cyclic kernels.
"""
from __future__ import annotations

from collections import Counter
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

#: jaxpr primitive name -> normalized collective kind (psum_invariant
#: is what psum becomes under shard_map's varying-axes check)
_COLLECTIVE_PRIMS = {
    "psum": "psum", "psum_invariant": "psum", "pmax": "pmax",
    "pmin": "pmin",
    "all_gather": "all_gather", "ppermute": "ppermute",
    "all_to_all": "all_to_all", "reduce_scatter": "reduce_scatter",
}

#: pvary is shard_map's varying-axes bookkeeping, not wire traffic
_IGNORED_PRIMS = {"pvary"}

#: pallas_call name prefix marking an explicit ICI-ring kernel
#: (kernels.pallas_ring): ``dplasma_ring_{bcast|shift}_{axis}``. These
#: are wire traffic exactly like the named collectives — the walk
#: counts them as kind ``ring_bcast``/``ring_shift`` over their axis.
_RING_PREFIX = "dplasma_ring_"


def _ring_collective(eqn) -> Optional[Tuple[str, str]]:
    """(kind, axis) of a pallas_call eqn that is a named ring kernel,
    None otherwise. The kernel name rides the eqn's name_and_src_info
    param (jax >= 0.4.31) or the debug name."""
    name = str(eqn.params.get("name_and_src_info", "") or
               eqn.params.get("name", ""))
    name = name.split(" ", 1)[0]
    if not name.startswith(_RING_PREFIX):
        return None
    rest = name[len(_RING_PREFIX):]
    what, _, axis = rest.partition("_")
    if what not in ("bcast", "shift") or not axis:
        return None
    return f"ring_{what}", axis


class SpmdCheckError(ValueError):
    """A traced SPMD program failed collective-schedule verification."""

    def __init__(self, result: "SpmdResult"):
        self.result = result
        lines = [d.message for d in result.diagnostics[:8]]
        more = len(result.diagnostics) - len(lines)
        if more > 0:
            lines.append(f"... and {more} more")
        super().__init__("SPMD verification failed:\n  " +
                         "\n  ".join(lines))


@dataclass(frozen=True)
class SpmdDiagnostic:
    """One verification failure: kind, kernel, and the offending
    collective / step / rank pair."""

    kind: str        # unbound-axis|divergent-cond|while-collective|
    #                # bad-permutation|count-mismatch|deadlock|
    #                # unpaired-semaphore|model-mismatch
    message: str
    kernel: str = ""
    detail: Optional[dict] = None

    def as_dict(self) -> dict:
        return {"kind": self.kind, "message": self.message,
                "kernel": self.kernel, "detail": self.detail}


@dataclass(frozen=True)
class Collective:
    """One collective in program order inside a shard_map body."""

    kind: str                   # psum|all_gather|ppermute|...
    axes: Tuple[str, ...]       # mesh axis names it runs over
    count: int = 1              # static multiplicity (scan length)
    perm: Optional[tuple] = None  # ppermute (src, dst) pairs

    @property
    def key(self) -> str:
        return f"{self.kind}@{','.join(self.axes)}"


@dataclass
class SpmdResult:
    """Outcome of :func:`check_kernel` (JSON-able via summary())."""

    kernel: str = ""
    ok: bool = True
    collectives: List[Collective] = field(default_factory=list)
    mesh_axes: Dict[str, int] = field(default_factory=dict)
    shard_maps: int = 0
    relation: Optional[str] = None   # ==|>=|unmodelled|no-collectives
    expected: Optional[dict] = None
    diagnostics: List[SpmdDiagnostic] = field(default_factory=list)

    def add(self, kind: str, message: str, detail=None) -> None:
        self.ok = False
        self.diagnostics.append(
            SpmdDiagnostic(kind, message, self.kernel, detail))

    @property
    def counts(self) -> Dict[str, int]:
        c: Counter = Counter()
        for col in self.collectives:
            c[col.key] += col.count
        return dict(c)

    def summary(self) -> dict:
        return {"ok": self.ok, "kernel": self.kernel,
                "shard_maps": self.shard_maps,
                "mesh_axes": dict(self.mesh_axes),
                "collectives": sum(c.count for c in self.collectives),
                "counts": self.counts,
                "relation": self.relation,
                "expected": self.expected,
                "diagnostics": [d.as_dict() for d in self.diagnostics]}

    def format(self, label: str = "") -> str:
        head = f"#+ spmdcheck[{label or self.kernel}]: "
        if self.ok:
            total = sum(c.count for c in self.collectives)
            rel = f", model {self.relation}" if self.relation else ""
            return (head + f"OK ({total} collectives over "
                    f"{self.shard_maps} shard_map region(s){rel})")
        lines = [head + f"{len(self.diagnostics)} violation(s)"]
        lines += [f"#!   {d.kind}: {d.message}"
                  for d in self.diagnostics]
        return "\n".join(lines)


# ---------------------------------------------------------------------
# jaxpr walk: collective schedule extraction
# ---------------------------------------------------------------------

def _axes_of(params: dict) -> Tuple[str, ...]:
    """Normalized mesh-axis-name tuple of a collective eqn (positional
    int axes from vmap-style uses are not mesh axes and are dropped)."""
    ax = params.get("axes", params.get("axis_name", ()))
    if isinstance(ax, str):
        ax = (ax,)
    return tuple(a for a in ax if isinstance(a, str))


def _sub_jaxprs(v):
    """Yield every (Closed)Jaxpr reachable from one eqn param value."""
    import jax.extend.core as jc
    vs = v if isinstance(v, (tuple, list)) else (v,)
    for x in vs:
        if isinstance(x, jc.ClosedJaxpr):
            yield x.jaxpr
        elif isinstance(x, jc.Jaxpr):
            yield x


def _walk(jaxpr, res: SpmdResult, mesh_axes: Optional[Dict[str, int]],
          mult: int, out: List[Collective]) -> None:
    """Append the collective schedule of ``jaxpr`` (program order) to
    ``out``; ``mesh_axes`` is the enclosing shard_map's axis->size map
    (None outside any shard_map), ``mult`` the static trip multiplier
    of enclosing scans."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _IGNORED_PRIMS:
            continue
        if name in _COLLECTIVE_PRIMS:
            kind = _COLLECTIVE_PRIMS[name]
            axes = _axes_of(eqn.params)
            col = Collective(kind, axes, mult,
                             perm=eqn.params.get("perm"))
            if mesh_axes is None:
                res.add("unbound-axis",
                        f"collective {col.key} outside any shard_map "
                        f"region (no mesh binds its axis)")
            else:
                unbound = [a for a in axes if a not in mesh_axes]
                if unbound:
                    res.add("unbound-axis",
                            f"collective {col.key}: axis name(s) "
                            f"{unbound} not bound by the mesh axes "
                            f"{sorted(mesh_axes)}")
            if kind == "ppermute":
                _check_perm(col, mesh_axes, res)
            out.append(col)
            continue
        if name == "pallas_call":
            rc = _ring_collective(eqn)
            if rc is not None:
                kind, axis = rc
                col = Collective(kind, (axis,), mult)
                if mesh_axes is None:
                    res.add("unbound-axis",
                            f"ring kernel {col.key} outside any "
                            f"shard_map region (no mesh binds its "
                            f"axis)")
                elif axis not in mesh_axes:
                    res.add("unbound-axis",
                            f"ring kernel {col.key}: axis name "
                            f"[{axis!r}] not bound by the mesh axes "
                            f"{sorted(mesh_axes)}")
                out.append(col)
            continue
        if name == "shard_map":
            mesh = eqn.params.get("mesh")
            axes = {}
            if mesh is not None:
                axes = {str(a): int(s) for a, s in
                        zip(mesh.axis_names, mesh.devices.shape)} \
                    if hasattr(mesh, "devices") else \
                    {str(a): int(s) for a, s in
                     dict(getattr(mesh, "shape", {})).items()}
            res.shard_maps += 1
            res.mesh_axes.update(axes)
            for sub in _sub_jaxprs(eqn.params.get("jaxpr")):
                _walk(sub, res, axes, mult, out)
            continue
        if name == "cond":
            _walk_cond(eqn, res, mesh_axes, mult, out)
            continue
        if name == "while":
            _walk_while(eqn, res, mesh_axes, mult, out)
            continue
        if name == "scan":
            length = int(eqn.params.get("length", 1))
            for sub in _sub_jaxprs(eqn.params.get("jaxpr")):
                _walk(sub, res, mesh_axes, mult * length, out)
            continue
        # transparent containers: pjit, closed_call, custom_jvp/vjp,
        # remat, ... — descend into every jaxpr-valued param
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                _walk(sub, res, mesh_axes, mult, out)


def _check_perm(col: Collective, mesh_axes, res: SpmdResult) -> None:
    """A ppermute permutation must be a bijection on its axis: every
    rank exactly once as source and once as destination, in range."""
    perm = tuple(col.perm or ())
    size = None
    if mesh_axes and len(col.axes) == 1:
        size = mesh_axes.get(col.axes[0])
    srcs = [int(s) for s, _ in perm]
    dsts = [int(d) for _, d in perm]
    dup_s = sorted({s for s in srcs if srcs.count(s) > 1})
    dup_d = sorted({d for d in dsts if dsts.count(d) > 1})
    oob = sorted({r for r in srcs + dsts
                  if size is not None and not (0 <= r < size)})
    missing = sorted(set(range(size)) - set(srcs)) \
        if size is not None else []
    missing_d = sorted(set(range(size)) - set(dsts)) \
        if size is not None else []
    if dup_s or dup_d or oob or missing or missing_d:
        parts = []
        if dup_s:
            parts.append(f"duplicate sources {dup_s}")
        if dup_d:
            parts.append(f"duplicate destinations {dup_d}")
        if oob:
            parts.append(f"out-of-range ranks {oob} (axis size {size})")
        if missing or missing_d:
            parts.append(f"ranks missing as source {missing} / "
                         f"destination {missing_d} — a rank with no "
                         f"incoming send deadlocks waiting")
        res.add("bad-permutation",
                f"ppermute over axis {col.axes} is not a bijection: "
                + "; ".join(parts),
                detail={"perm": [list(p) for p in perm],
                        "axis_size": size})


def _schedule_sig(cols: Sequence[Collective]) -> tuple:
    # perm is part of the signature: two ppermutes over the same axis
    # with different permutations are DIFFERENT schedules (ranks would
    # exchange with different partners across cond branches)
    return tuple((c.kind, c.axes, c.count, c.perm) for c in cols)


def _walk_cond(eqn, res, mesh_axes, mult, out) -> None:
    """Collectives under ``cond`` are SPMD-safe only when every branch
    emits the identical collective subsequence: a shard_map cond
    predicate is in general rank-varying (sharded data, axis_index),
    so differing branches mean some ranks enter a collective the
    others skip — deadlock."""
    branches = []
    for sub in _sub_jaxprs(eqn.params.get("branches")):
        sub_out: List[Collective] = []
        _walk(sub, res, mesh_axes, mult, sub_out)
        branches.append(sub_out)
    if not branches:
        return
    sigs = {_schedule_sig(b) for b in branches}
    if len(sigs) > 1:
        seqs = [[c.key for c in b] for b in branches]
        res.add("divergent-cond",
                f"rank-divergent collective sequence: cond branches "
                f"emit different collectives {seqs} — a rank taking "
                f"the poorer branch deadlocks the others "
                f"(make the branches collective-identical or hoist "
                f"the collective out of the cond)",
                detail={"branches": seqs})
    # uniform branches contribute once (all ranks run one of them)
    out.extend(branches[0])


def _walk_while(eqn, res, mesh_axes, mult, out) -> None:
    """A collective inside a data-dependent ``while`` (trip count not
    statically known) cannot be proven uniform across ranks."""
    subs: List[Collective] = []
    for key in ("cond_jaxpr", "body_jaxpr"):
        for sub in _sub_jaxprs(eqn.params.get(key)):
            _walk(sub, res, mesh_axes, mult, subs)
    if subs:
        res.add("while-collective",
                f"collective(s) {[c.key for c in subs]} inside a "
                f"data-dependent while loop: the trip count may "
                f"differ across ranks — a rank that exits early "
                f"abandons the others mid-collective (use a static "
                f"trip count / lax.scan, or hoist the collective)",
                detail={"collectives": [c.key for c in subs]})
    out.extend(subs)


def extract_schedule(fn, *args, kernel: str = "") -> SpmdResult:
    """Trace ``fn(*args)`` abstractly (tiny shapes; CPU-only, nothing
    executes) and extract its collective schedule with the structural
    checks applied: axis binding, cond/while uniformity, ppermute
    bijections. ``fn`` may be jit-wrapped; bind static arguments with
    ``functools.partial``."""
    import jax
    res = SpmdResult(kernel=kernel)
    jaxpr = jax.make_jaxpr(fn)(*args)
    _walk(jaxpr.jaxpr, res, None, 1, res.collectives)
    return res


# ---------------------------------------------------------------------
# Collective-count reconciliation against the analytic comm model
# ---------------------------------------------------------------------

#: per-step (kind, axis-role) multiplicities of the cyclic shard_map
#: kernels — the collective structure spmd_comm_model prices. Axis
#: roles 'row'/'col' resolve to the mesh axis constants at check time.
_STEP_COUNTS = {
    # panel bcast psum_q + diag bcast psum_p + row-panel all_gather_p
    "potrf": {("psum", "col"): 1, ("psum", "row"): 1,
              ("all_gather", "row"): 1},
    # panel bcast psum_q + candidate/gid all_gathers + pivot-row psum_p
    "getrf": {("psum", "col"): 1, ("all_gather", "row"): 2,
              ("psum", "row"): 1},
    # panel bcast psum_q + CholeskyQR2 grams/top (3) + V^H C psum_p
    "geqrf": {("psum", "col"): 1, ("psum", "row"): 4},
    # SUMMA: A-column psum_q + B-row psum_p per contraction step
    "gemm": {("psum", "col"): 1, ("psum", "row"): 1},
}


def expected_counts(op: str, KT: int, lookahead: int = 0,
                    ring: bool = False,
                    grid: Tuple[int, int] = (1, 1)
                    ) -> Optional[Dict[str, int]]:
    """Expected per-class collective counts of one cyclic kernel over
    ``KT`` panel steps. The lookahead pipeline *relocates* the panel
    broadcast (step k pre-broadcasts column k+1) but never changes
    the totals — the schedule is count-invariant in the pipeline
    shape, which is exactly why this check can be exact.

    ``ring=True`` expects the explicit ICI-ring schedule
    (kernels.pallas_ring under MCA ``ring.enable``): the panel
    broadcast class moves from ``psum@q`` to ``ring_bcast@q`` (one
    ring kernel per step) and the LU winner-row exchange from
    ``psum@p`` to ``ring_shift@p`` at P-1 hops per step — which is
    why the ring schedule needs the ``grid`` shape (a size-1 axis
    keeps its psum class: the kernels fall back per axis)."""
    from dplasma_tpu.parallel import mesh as pmesh
    tbl = _STEP_COUNTS.get(op)
    if tbl is None:
        return None
    axis = {"row": pmesh.ROW_AXIS, "col": pmesh.COL_AXIS}
    P, Q = int(grid[0]), int(grid[1])
    out: Dict[str, int] = {}
    for (kind, role), n in tbl.items():
        key = f"{kind}@{axis[role]}"
        cnt = n * KT
        if ring and kind == "psum" and role == "col" and Q > 1 \
                and op in ("potrf", "getrf", "geqrf"):
            key, cnt = f"ring_bcast@{axis[role]}", KT
        elif ring and op == "getrf" and kind == "psum" \
                and role == "row" and P > 1:
            key, cnt = f"ring_shift@{axis[role]}", KT * (P - 1)
        out[key] = out.get(key, 0) + cnt
    return out


def model_classes(op: str, ring: bool = False,
                  grid: Tuple[int, int] = (2, 2)) -> Optional[set]:
    """The (kind, axis) collective classes the analytic comm model
    (:func:`dplasma_tpu.parallel.cyclic.spmd_comm_model`) prices for
    one op — parsed from its per-collective key names, so the checker
    and the observability model can never drift apart silently. Ring
    classes (``panel_ring_bcast_q``/``pivot_row_ring_shift_p``) parse
    to ``ring_bcast``/``ring_shift`` kinds; the ``grid`` shape must
    match the count table's (per-axis psum fallback)."""
    from dplasma_tpu.descriptors import Dist
    from dplasma_tpu.parallel.cyclic import CyclicDesc, spmd_comm_model
    P, Q = max(int(grid[0]), 1), max(int(grid[1]), 1)
    desc = CyclicDesc(8, 8, 4, 4, Dist(P=P, Q=Q))
    try:
        model = spmd_comm_model(desc, op, 4, ring=ring)
    except KeyError:
        return None
    classes = set()
    for key in model["bytes_by_collective"]:
        base, _, axis = key.rpartition("_")
        kind = base.rsplit("_", 1)[-1]
        kind = {"allgather": "all_gather", "bcast": "ring_bcast",
                "shift": "ring_shift"}.get(kind, kind)
        classes.add(f"{kind}@{axis}")
    return classes


def reconcile_counts(res: SpmdResult, op: Optional[str], KT: int,
                     lookahead: int = 0, exact: bool = True,
                     ring: bool = False,
                     grid: Tuple[int, int] = (1, 1)) -> None:
    """Reconcile the traced collective counts against the analytic
    model: exact (``==``) for the cyclic kernels themselves,
    dominating (``>=``, conversions around them may add collectives)
    for driver programs. A class the model prices that the trace
    lacks — the dropped-psum defect — is a hard diagnostic naming the
    kernel and the collective class. ``ring``/``grid`` select the
    explicit ICI-ring schedule's count table (kernels.pallas_ring)."""
    exp = expected_counts(op, KT, lookahead, ring=ring, grid=grid) \
        if op else None
    if exp is None:
        res.relation = ("no-collectives"
                        if not res.collectives else "unmodelled")
        return
    res.expected = exp
    got = res.counts
    bad = []
    for key, n in exp.items():
        g = got.get(key, 0)
        if g < n or (exact and g != n):
            bad.append((key, g, n))
    if exact:
        for key, g in got.items():
            if key not in exp:
                bad.append((key, g, 0))
    if bad:
        for key, g, n in bad:
            res.add("count-mismatch",
                    f"collective count mismatch for {key}: traced "
                    f"{g}, analytic model expects "
                    f"{'exactly' if exact else 'at least'} {n} over "
                    f"{KT} panel steps (lookahead={lookahead}) — a "
                    f"{'dropped' if g < n else 'surplus'} collective "
                    f"desynchronizes the rank schedule",
                    detail={"class": key, "traced": g, "expected": n})
        res.relation = "mismatch"
    else:
        res.relation = "==" if got == exp else ">="
    # tie to the priced model: the expected classes must be exactly
    # what spmd_comm_model prices (guards the two models against
    # drift). Strip the mesh-axis names back to the model's p/q
    # roles via the same mapping expected_counts applied.
    mc = model_classes(op, ring=ring,
                       grid=grid if ring else (2, 2))
    if mc is not None and mc != set(exp):
        res.add("model-mismatch",
                f"collective classes of the count table {sorted(exp)} "
                f"disagree with the priced comm model {sorted(mc)} — "
                f"update spmd_comm_model and expected_counts together")


def check_kernel(fn, args, kernel: str, op: Optional[str] = None,
                 KT: int = 0, lookahead: int = 0,
                 exact: bool = True, ring: bool = False,
                 grid: Tuple[int, int] = (1, 1)) -> SpmdResult:
    """Extract + verify one program's collective schedule. ``op`` (a
    comm-model op class: potrf/getrf/geqrf/gemm) and ``KT`` enable the
    count reconciliation; without them only the structural checks run.
    ``ring``/``grid`` select the explicit ICI-ring count table.
    """
    res = extract_schedule(fn, *args, kernel=kernel)
    if op is not None and KT > 0:
        reconcile_counts(res, op, KT, lookahead, exact=exact,
                         ring=ring, grid=grid)
    elif not res.collectives:
        res.relation = "no-collectives"
    else:
        res.relation = "unmodelled"
    return res


def verify_kernel(fn, args, kernel: str, **kw) -> SpmdResult:
    """:func:`check_kernel` that raises :class:`SpmdCheckError` on any
    diagnostic (the --spmdcheck driver path)."""
    res = check_kernel(fn, args, kernel, **kw)
    if not res.ok:
        raise SpmdCheckError(res)
    return res


# ---------------------------------------------------------------------
# Abstract ring-schedule simulator (future ICI-ring kernels)
# ---------------------------------------------------------------------

@dataclass(frozen=True)
class RingOp:
    """One abstract step of a per-device ring program.

    * ``send(dst, sem)`` — start an async copy to rank ``dst``; its
      arrival signals ``sem`` at the destination (the
      ``make_async_remote_copy`` recv-semaphore contract);
    * ``wait(sem, count, src)`` — block until the local ``sem`` has
      been signaled ``count`` times, then drain it (``src`` names the
      rank the data is expected from, for diagnostics);
    * ``compute`` — local work (always runnable; keeps step indices
      aligned with the real kernel's program order).
    """

    kind: str                # send | wait | compute
    dst: int = -1            # send: destination rank
    src: int = -1            # wait: expected source rank (diagnostic)
    sem: str = "dma"
    count: int = 1


def send(dst: int, sem: str = "dma") -> RingOp:
    return RingOp("send", dst=dst, sem=sem)


def wait(src: int, sem: str = "dma", count: int = 1) -> RingOp:
    return RingOp("wait", src=src, sem=sem, count=count)


def compute() -> RingOp:
    return RingOp("compute")


def ring_shift_program(n: int, steps: int,
                       sem: str = "dma") -> Dict[int, List[RingOp]]:
    """The canonical neighbor-shift ring (the panel-broadcast /
    row-exchange shape of ROADMAP item 2): per step every rank sends
    to (r+1) % n, waits on the signal from (r-1) % n, computes."""
    return {r: [op for _ in range(steps)
                for op in (send((r + 1) % n, sem),
                           wait((r - 1) % n, sem), compute())]
            for r in range(n)}


def simulate_ring(kernel: str,
                  programs: Dict[int, List[RingOp]]
                  ) -> List[SpmdDiagnostic]:
    """Execute the per-device programs abstractly: sends signal their
    destination's semaphore, waits block until signaled. Returns the
    diagnostics (empty = the schedule drains):

    * **deadlock** — no device can make progress while some are
      unfinished; names the kernel, the stuck step, and the rank pair
      (the waiter and the rank it expects the signal from);
    * **unpaired-semaphore** — signals left undrained at completion
      (a send with no matching wait): the next kernel invocation
      inherits a stale semaphore count and desynchronizes.
    """
    diags: List[SpmdDiagnostic] = []
    pcs = {r: 0 for r in programs}
    sems: Counter = Counter()
    while True:
        progressed = False
        for r, prog in programs.items():
            while pcs[r] < len(prog):
                op = prog[pcs[r]]
                if op.kind == "wait":
                    if sems[(r, op.sem)] < op.count:
                        break
                    sems[(r, op.sem)] -= op.count
                elif op.kind == "send":
                    sems[(op.dst, op.sem)] += 1
                pcs[r] += 1
                progressed = True
        if all(pcs[r] >= len(programs[r]) for r in programs):
            break
        if not progressed:
            for r, prog in programs.items():
                if pcs[r] >= len(prog):
                    continue
                op = prog[pcs[r]]
                peer = op.src if op.kind == "wait" else op.dst
                diags.append(SpmdDiagnostic(
                    "deadlock",
                    f"ring deadlock in {kernel}: rank {r} stuck at "
                    f"step {pcs[r]} ({op.kind} sem={op.sem!r}) "
                    f"waiting on rank {peer} — its matching "
                    f"{'send' if op.kind == 'wait' else 'wait'} "
                    f"never executes", kernel,
                    {"rank": r, "step": pcs[r], "peer": peer,
                     "sem": op.sem}))
            return diags
    for (r, sem_name), n in sorted(sems.items()):
        if n > 0:
            diags.append(SpmdDiagnostic(
                "unpaired-semaphore",
                f"unpaired DMA semaphore in {kernel}: {n} signal(s) "
                f"on sem {sem_name!r} at rank {r} never awaited — "
                f"the next invocation inherits a stale count",
                kernel, {"rank": r, "sem": sem_name, "undrained": n}))
    return diags


def check_ring(kernel: str,
               programs: Dict[int, List[RingOp]]) -> SpmdResult:
    """Ring-schedule verification as a :class:`SpmdResult` (the gate
    future ICI-ring kernels run before first execution)."""
    res = SpmdResult(kernel=kernel)
    for d in simulate_ring(kernel, programs):
        res.ok = False
        res.diagnostics.append(d)
    res.relation = "ring"
    return res
