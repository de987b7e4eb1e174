"""Shared driver harness — the analog of the reference's tests/common.c/h.

Every ``testing_<prec><algo>`` driver accepts the reference CLI vocabulary
(ref tests/common.c:73-259): sizes ``-N/-M/-K``, tile shape ``-t/-T``,
process grid ``-p/-q`` with k-cyclic supertiles ``--kp/--kq``, inner
blocking ``-i``, checks ``-x/-X``, verbosity ``-v[=n]``, HQR tree knobs
(``--qr_a/--qr_p/--treel/--treeh/-d/-r``), LU/QR criteria
(``--criteria/-a``), butterfly level ``-y``, seed/nruns, scheduler/cores/
gpus/vpmap accepted-and-recorded (scheduling is XLA's job here), and
``--dot`` for the trace-time DAG dump.

Timing/printing mirrors tests/common.h:233-288 — the ``[****] TIME(s)``
line with ``PxQxg= .. NB= .. N= .. : .. gflops`` so existing log parsers
work unchanged, the ENQ/PROG/DEST phase breakdown (here: trace+compile /
device execution / teardown), and the CDash ``DartMeasurement`` XML at
verbosity >= 5.
"""
from __future__ import annotations

import contextlib
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np


PRECISIONS = {"s": "float32", "d": "float64", "c": "complex64",
              "z": "complex128"}

SCHEDULERS = ("LFQ", "LTQ", "AP", "LHQ", "GD", "PBQ", "IP", "RND")

# Implicit DAG-analytics cap (--report / -v>=3): the analytic tile-DAG
# builders materialize O(tiles^1.5) tasks in Python, so past this many
# tiles the run-report carries an explicit null instead (an explicit
# --dot always builds the DAG).
_DAG_TILE_CAP = 4096


@dataclass
class IParam:
    """Driver parameter block (the iparam[] array of tests/common.c)."""
    rank: int = 0
    nodes: int = 1
    P: int = 1
    Q: int = 1
    kp: int = 1
    kq: int = 1
    M: int = 0
    N: int = 0
    K: int = 1          # NRHS for solves, K for gemm
    LDA: int = 0
    LDB: int = 0
    LDC: int = 0
    IB: int = 32
    MB: int = 0
    NB: int = 0
    HMB: int = 0        # recursive inner blocking (-z/--HNB)
    HNB: int = 0
    check: bool = False
    check_inv: bool = False
    sync: bool = False
    loud: int = 1       # verbosity ladder (-v[=n])
    seed: int = 3872
    mtx: int = 0
    nruns: int = 1
    warmup: bool = True  # rank-local warm run excluded from stats
    # HQR trees (--qr_a/--qr_p/--treel/--treeh/-d/-r)
    qr_a: int = -1
    qr_p: int = -1
    lowlvl_tree: int = -1
    highlvl_tree: int = -1
    qr_domino: int = -1
    qr_tsrr: int = 0
    # LU/QR hybrid (--criteria/-a)
    criteria: int = 0
    alpha: float = -1.0
    # pipelined-sweep lookahead (--lookahead; -1 = MCA sweep.lookahead)
    lookahead: int = -1
    # tuning-DB consultation (--autotune; dplasma_tpu.tuning)
    autotune: bool = False
    # did the CLI pin the tile shape (-t/-T)? --autotune may only
    # apply a DB tile size when it did not (CLI > DB precedence)
    nb_explicit: bool = False
    # butterfly (-y)
    butterfly_level: int = 0
    # accepted-for-compat knobs (scheduling/threads are XLA's job on TPU)
    cores: int = 0
    gpus: int = 0
    scheduler: str = "LFQ"
    thread_multi: bool = False
    dot: Optional[str] = None
    dagcheck: bool = False           # static dataflow verification
    spmdcheck: bool = False          # SPMD collective-schedule check
    hlocheck: bool = False           # compiled-HLO artifact audit
    memcheck: bool = False           # static HBM-residency check
    # observability outputs (--profile/--report/--jaxtrace)
    profile: Optional[str] = None    # DTPUPROF1 binary trace
    report: Optional[str] = None     # versioned JSON run-report
    jaxtrace: Optional[str] = None   # JAX/XLA profiler logdir
    # live telemetry (--telemetry[=prom-file]): streaming metrics
    # exporter + flight recorder, v13 "telemetry" report section
    telemetry: Optional[str] = None
    # performance attribution (--phase-profile/--devprof/--peaks-file)
    phase_profile: bool = False      # per-phase attributed pass (v5)
    devprof: bool = False            # per-device timeline attribution (v14)
    peaks_file: Optional[str] = None  # roofline peaks source
    # resilience (--abft/--inject/--max-retries/--run-timeout)
    abft: bool = False               # checksum-carried op variants
    inject: Optional[str] = None     # fault plan KIND@STAGE[:RATE[:COUNT]]
    max_retries: int = 2             # remediation-ladder rung budget
    run_timeout: float = 0.0         # watchdog on the timed loop (s)
    extra: list = field(default_factory=list)   # args after `--` (MCA-style)

    @property
    def prec_dtype(self):
        import jax.numpy as jnp
        return getattr(jnp, PRECISIONS[self.prec])

    prec: str = "d"


_USAGE = """\
Mandatory argument:
 -N                : dimension (N) of the matrices
Optional arguments:
 -p -P --grid-rows : rows (P) in the PxQ device grid (default: 1)
 -q -Q --grid-cols : columns (Q) in the PxQ device grid (default: 1;
                     the single-device path needs no mesh)
 -M                : dimension (M) of the matrices (default: N)
 -K --NRHS         : dimension (K) / right-hand-side count (default: 1)
 -A --LDA -B --LDB -C --LDC : leading dimensions (recorded)
 -i --IB           : inner blocking (default: 32)
 -t --MB           : rows in a tile (default: autotuned)
 -T --NB           : columns in a tile (default: MB)
 -s --SMB --kp     : row k-cyclicity (supertiles) (default: 1)
 -S --SNB --kq     : column k-cyclicity (supertiles) (default: 1)
 -z --HNB --HMB    : inner NB/MB for recursive algorithms
 -x --check        : verify the results
 -X --check_inv    : verify against the inverse
 -b --sync         : step-by-step (synchronous) variant
 --qr_a --qr_p     : HQR TS-domain size / high-level tree size
 -d --domino -r --tsrr : HQR domino / TS round-robin toggles
 --treel --treeh   : HQR low/high level tree (0 flat 1 greedy 2 fibonacci 3 binary 4 greedy1p)
 --criteria -a --alpha : LU/QR switch criteria and threshold
 --lookahead       : pipelined-sweep lookahead depth (panels updated
                     ahead of the wide trailing update; 0 = the
                     serialized baseline; default: MCA sweep.lookahead,
                     1). QR far-update aggregation rides MCA
                     qr.agg_depth.
 --autotune        : resolve knobs (tile size, sweep.lookahead,
                     qr/lu.agg_depth, panel.*) from the persistent
                     tuning database (MCA tune.db / env
                     DPLASMA_TUNE_DB; populated by tools/autotune.py)
                     for this run's (op, N, dtype, grid) key —
                     nearest-key interpolation for unmeasured shapes.
                     Precedence: explicit CLI flags (-t/-T,
                     --lookahead, --mca-style env) beat the DB; the
                     DB beats the registered defaults. The
                     consultation (source: db/interpolated/default)
                     lands in the run-report (v11 "tuning" section)
                     and the scoped overrides restore at close
 --seed --mtx      : generator seed / matrix kind
 -y --butlvl       : butterfly level
 --nruns           : number of timed runs
 --nowarmup        : skip the untimed warm run before the timed loop
 -v --verbose[=n]  : verbosity ladder
 -c --cores -g --gpus -o --scheduler -V --vpmap -m : accepted for
                     compatibility (scheduling is compiled into XLA)
 --dot[=file]      : dump the trace-time tile DAG as graphviz
 --dagcheck        : statically verify the analytic tile DAG before
                     executing (acyclicity, def-before-use flow
                     coverage, WAW/WAR races, owner-computes ranks,
                     comm-model reconciliation); violations abort the
                     run and the result lands in the run-report (v3)
 --spmdcheck       : verify the traced SPMD program's collective
                     schedule before the timed loop (every collective
                     axis bound by its shard_map mesh, per-rank
                     sequence uniform — no collectives behind rank-
                     divergent cond/while, every ppermute a
                     bijection); violations abort the run and the
                     summary lands in the run-report (v6). The cyclic
                     kernels' exact collective-count contract is
                     additionally enforced by tools/lint_all.py
 --hlocheck        : audit the COMPILED executable before the timed
                     loop (the post-GSPMD HLO that actually runs):
                     per-kind collective counts reconciled exactly
                     against the traced schedule (a GSPMD-inserted
                     hidden collective is named), float demotions
                     below the working precision outside the
                     registered dd/limb sites, requested buffer
                     donations that produced no input-output alias,
                     peak memory vs MCA hlocheck.hbm_budget, and
                     host-callback / copy-volume anti-patterns;
                     violations abort the run and the summary lands
                     in the run-report (v10)
 --memcheck        : statically verify the schedule's HBM residency
                     before anything executes (analysis.memcheck):
                     per-tile live intervals over the wavefront
                     linearization, per-rank peak resident bytes
                     under the block-cyclic distribution (dd limb
                     widths priced in), predicted HBM peak gated
                     against MCA memcheck.hbm_budget with the
                     peak-driving task/tile/live-set named, and a
                     spill/prefetch streaming plan derived when the
                     budget forces one; violations abort the run and
                     the summary lands in the run-report (v16).
                     With --hlocheck also on, the prediction is
                     cross-validated against the measured
                     memory_analysis peak (a compiled temp the model
                     missed is a named finding)
 --profile[=file]  : write the binary DTPUPROF1 run trace (convert with
                     tools/tracecat.py; default file: run.prof)
 --report[=file]   : write the versioned JSON run-report (timings,
                     per-run stats, XLA cost/memory analysis, comm
                     model, DAG analytics; default file: report.json)
 --jaxtrace[=dir]  : capture a device-side JAX/XLA profiler trace into
                     dir (default: jax_trace)
 --telemetry[=file]: live telemetry for this run: a streaming metrics
                     exporter rewrites the Prometheus text snapshot
                     in file (default: telemetry.prom) every MCA
                     telemetry.interval_s seconds, and a bounded
                     flight recorder of structured events (op starts/
                     finishes, remediation rungs, injected faults)
                     lands in the run-report (schema v13 "telemetry"
                     section) — and on disk (MCA telemetry.flight_path)
                     whenever a remediation ladder walks
 --phase-profile   : phase-level performance attribution: one extra
                     eager attributed pass after the timed loop, with
                     scoped phase timers (panel/lookahead/far_flush/
                     catchup/assemble) fenced at span exit and met
                     with roofline expectations; the per-phase table
                     prints at -v>=2 and lands in the run-report
                     (schema v5 "phases"/"roofline"). The timed loop
                     itself stays fence-free
 --devprof         : per-device timeline attribution around the timed
                     loop: a hardware profile (jax.profiler events
                     when the runtime writes any; otherwise a
                     synthetic timeline reconstructed from the
                     measured run + the spmdcheck schedule + the
                     spmd_comm_model pricing — MCA devprof.backend)
                     binned into compute/collective/ici/host, measured
                     collective seconds + achieved ICI bytes/s
                     reconciled per (kind, axis) against the comm
                     model (MCA devprof.ici_floor), per-rank skew with
                     the slowest rank and its dominating category
                     named, and the critical path; lands in the
                     run-report (schema v14 "devprof" section) and in
                     devprof_* metrics
 --peaks-file=FILE : hardware peaks for the roofline ledger (a bench
                     JSON doc/report with a "peaks" section, or a raw
                     {mxu_gflops, hbm_gbps, ici_gbps, latency_us}
                     dict); default: conservative built-ins
 --abft            : checksum-carried (ABFT) op variants where
                     available (gemm/potrf/getrf): detect + locate a
                     corrupted tile in O(n^2), correct it for GEMM
 --inject=SPEC     : deterministic fault injection,
                     SPEC = KIND@STAGE[:RATE[:COUNT]] with KIND in
                     bitflip|nan|inf|zero and STAGE a kernel stage
                     (gemm/trsm/potrf/getrf/any); seeded by --seed
 --max-retries     : retry-rung budget of the remediation ladder
                     (default: 2; the kernel/algorithm fallback rungs
                     are one-shot and not counted)
 --run-timeout     : watchdog limit (seconds) on the timed loop;
                     overruns classify as timeout for the ladder
 -h --help         : this message
ENVIRONMENT
  [SDCZ]<FUNCTION> : per-precision priority limit (recorded, trace-time)
  DPLASMA_INJECT   : default fault plan when --inject is not given
"""


def _int(v: str) -> int:
    return int(v, 0)


def default_tile(n: int) -> int:
    """The defaults-cascade tile size for an ``n``-sized problem —
    ONE formula, shared with the autotuner's mandatory default-first
    candidate (:func:`dplasma_tpu.tuning.search.default_nb`), so the
    tuner's out-of-the-box baseline is exactly what an un-pinned
    driver runs."""
    return min(max(n, 1), 192 if n >= 1024 else 64)


# option name -> (iparam field, converter or None-for-flag)
_LONG = {
    "grid-rows": ("P", _int), "grid-cols": ("Q", _int),
    "P": ("P", _int), "Q": ("Q", _int),
    "N": ("N", _int), "M": ("M", _int), "K": ("K", _int),
    "NRHS": ("K", _int),
    "LDA": ("LDA", _int), "LDB": ("LDB", _int), "LDC": ("LDC", _int),
    "IB": ("IB", _int), "MB": ("MB", _int), "NB": ("NB", _int),
    "SMB": ("kp", _int), "SNB": ("kq", _int),
    "kp": ("kp", _int), "kq": ("kq", _int),
    "HNB": ("HNB", _int), "HMB": ("HMB", _int),
    "check": ("check", None), "check_inv": ("check_inv", None),
    "sync": ("sync", None),
    "qr_a": ("qr_a", _int), "qr_p": ("qr_p", _int),
    "treel": ("lowlvl_tree", _int), "treeh": ("highlvl_tree", _int),
    "domino": ("qr_domino", _int), "tsrr": ("qr_tsrr", _int),
    "criteria": ("criteria", _int), "alpha": ("alpha", float),
    "lookahead": ("lookahead", _int),
    "autotune": ("autotune", None),
    "seed": ("seed", _int), "mtx": ("mtx", _int),
    "butlvl": ("butterfly_level", _int),
    "nruns": ("nruns", _int),
    "cores": ("cores", _int), "gpus": ("gpus", _int),
    "scheduler": ("scheduler", str), "vpmap": ("_vpmap", str),
    "thread_multi": ("thread_multi", None),
    "ht": ("_ht", _int),
    "abft": ("abft", None), "inject": ("inject", str),
    "dagcheck": ("dagcheck", None),
    "spmdcheck": ("spmdcheck", None),
    "hlocheck": ("hlocheck", None),
    "memcheck": ("memcheck", None),
    "phase-profile": ("phase_profile", None),
    "devprof": ("devprof", None),
    "peaks-file": ("peaks_file", str),
    "max-retries": ("max_retries", _int),
    "run-timeout": ("run_timeout", float),
}

_SHORT = {
    "p": "grid-rows", "P": "grid-rows", "q": "grid-cols", "Q": "grid-cols",
    "N": "N", "M": "M", "K": "NRHS",
    "A": "LDA", "B": "LDB", "C": "LDC",
    "i": "IB", "t": "MB", "T": "NB", "s": "SMB", "S": "SNB",
    "z": "HNB",
    "a": "alpha", "y": "butlvl", "c": "cores", "g": "gpus",
    "o": "scheduler", "V": "vpmap", "d": "domino", "r": "tsrr",
}
_SHORT_FLAGS = {"x": "check", "X": "check_inv", "b": "sync",
                "m": "thread_multi"}


def parse_arguments(argv: list[str], ip: Optional[IParam] = None) -> IParam:
    ip = ip or IParam()
    args = list(argv)
    try:
        return _parse_arguments(args, ip)
    except IndexError:
        sys.stderr.write(f"missing value for option {args[-1]}\n{_USAGE}")
        raise SystemExit(2)


def _parse_arguments(args: list[str], ip: IParam) -> IParam:
    i = 0
    positional = []
    while i < len(args):
        a = args[i]
        if a == "--":
            ip.extra = args[i + 1:]
            break
        if a in ("-h", "--help"):
            sys.stderr.write(_USAGE)
            raise SystemExit(0)
        if a.startswith("--"):
            body = a[2:]
            name, eq, val = body.partition("=")
            if name in ("verbose",):
                ip.loud = _int(val) if eq else 2
            elif name == "nowarmup":
                ip.warmup = False
            elif name == "dot":
                ip.dot = val if eq else "dag.dot"
            elif name == "profile":
                ip.profile = val if eq else "run.prof"
            elif name == "report":
                ip.report = val if eq else "report.json"
            elif name == "jaxtrace":
                ip.jaxtrace = val if eq else "jax_trace"
            elif name == "telemetry":
                ip.telemetry = val if eq else "telemetry.prom"
            elif name in _LONG:
                field_, conv = _LONG[name]
                if conv is None:
                    setattr(ip, field_, True)
                else:
                    if not eq:
                        i += 1
                        val = args[i]
                    if not field_.startswith("_"):
                        setattr(ip, field_, conv(val))
            else:
                sys.stderr.write(f"unknown option {a}\n{_USAGE}")
                raise SystemExit(2)
        elif a.startswith("-") and len(a) >= 2 and not a[1].isdigit():
            c, rest = a[1], a[2:]
            if c == "v":
                ip.loud = _int(rest.lstrip("=")) if rest else 2
            elif c in _SHORT_FLAGS:
                # clustered boolean flags: -xX, -xb
                for cc in a[1:]:
                    if cc not in _SHORT_FLAGS:
                        sys.stderr.write(f"unknown flag -{cc} in {a}\n")
                        raise SystemExit(2)
                    setattr(ip, _SHORT_FLAGS[cc], True)
            elif c in _SHORT:
                field_, conv = _LONG[_SHORT[c]]
                val = rest.lstrip("=")
                if not val:
                    i += 1
                    val = args[i]
                if not field_.startswith("_"):
                    setattr(ip, field_, conv(val))
            else:
                sys.stderr.write(f"unknown option {a}\n{_USAGE}")
                raise SystemExit(2)
        else:
            positional.append(a)
        i += 1
    if positional and ip.N == 0:
        ip.N = _int(positional[0])
    # defaults cascade (iparam_default_* in tests/common.c:586-638).
    # Whether the CLI pinned the tile shape is remembered BEFORE the
    # cascade fills it: --autotune may only apply a DB tile size over
    # the cascade's default, never over an explicit -t/-T.
    ip.nb_explicit = ip.MB != 0 or ip.NB != 0
    if ip.M == 0:
        ip.M = ip.N
    if ip.MB == 0:
        ip.MB = default_tile(ip.N)
    if ip.NB == 0:
        ip.NB = ip.MB
    if ip.HNB == 0:
        ip.HNB = ip.NB
    if ip.HMB == 0:
        ip.HMB = ip.MB
    if ip.LDA == 0:
        ip.LDA = max(ip.M, ip.N)
    return ip


def _pct(frac) -> str:
    """Format an achieved fraction as a percent (None -> n/a)."""
    return "n/a" if frac is None else f"{100.0 * frac:.1f}%"


def _algo_of(name: str) -> str:
    """Precision-less algo name of a driver: testing_dpotrf -> potrf."""
    base = name.rsplit("/", 1)[-1]
    if base.startswith("testing_"):
        rest = base[8:]
        if rest[:1] in PRECISIONS and rest[1:]:
            return rest[1:]
        return rest
    return base


#: driver algo -> priced comm-model class, ONLY where the driver's
#: mesh path actually contains the priced cyclic kernel (so its
#: collective floor genuinely bounds the program). OP_CLASS is too
#: coarse here: it lumps solve-only drivers (potrs, potri, ...),
#: kernel variants with different schedules (geqrf_hqr, getrf_incpiv,
#: ...), and the BLAS3 ops (trsm, syrk, ...) into the same roofline
#: classes — pricing the factorization table against those would
#: falsely abort correct runs.
_HLOCHECK_MODEL_ALGOS = {
    "potrf": "potrf", "posv": "potrf",
    "getrf_ptgpanel": "getrf",
    "geqrf": "geqrf", "gels": "geqrf",
    "gemm": "gemm",
}


def _model_op_kt(algo: str, ip) -> tuple:
    """(op class, KT) for hlocheck's comm-model leg, or (None, 0).

    The SUMMA gemm kernel prices its collectives per CONTRACTION step
    (``ceil(K / NB)``); the factorization classes step over
    ``ceil(min(M,N)/NB)`` panels. Only the ``_HLOCHECK_MODEL_ALGOS``
    drivers qualify — everything else skips the model leg (the
    jaxpr-schedule reconciliation still runs)."""
    cls = _HLOCHECK_MODEL_ALGOS.get(algo)
    nb = max(ip.NB, 1)
    if cls == "gemm":
        return "gemm", max(-(-max(ip.K, 1) // nb), 1)
    if cls is not None:
        return cls, max(-(-min(ip.M, ip.N) // nb), 1)
    return None, 0


@contextlib.contextmanager
def _jaxtrace_guard(logdir: str):
    """--jaxtrace wrapper around the timed loop: profiler start/stop
    failures (backend without a profiler plugin) degrade to a warning,
    never a failed run."""
    from dplasma_tpu.utils.profiling import jax_trace
    cm = jax_trace(logdir)
    try:
        cm.__enter__()
    except Exception as exc:
        sys.stderr.write(f"#! jax profiler unavailable: {exc}\n")
        yield
        return
    try:
        yield
    finally:
        try:
            cm.__exit__(None, None, None)
        except Exception as exc:
            sys.stderr.write(f"#! jax profiler stop failed: {exc}\n")


class Driver:
    """Per-run context: devices, mesh, timing, reporting."""

    def __init__(self, ip: IParam, name: str):
        import jax
        from dplasma_tpu.observability.report import RunReport
        from dplasma_tpu.utils.profiling import Profile

        from dplasma_tpu.parallel import mesh as pmesh

        from dplasma_tpu.ops._sweep import sweep_params
        from dplasma_tpu.utils import config as _cfg

        self.ip = ip
        self.name = name
        self.mesh = None
        wants_la = getattr(ip, "lookahead", -1) >= 0
        # --autotune: consult the persistent tuning DB for this run's
        # (op, N, dtype, grid) key BEFORE any global state mutates —
        # a pure read that may rewrite the UN-pinned tile shape
        # (precedence: CLI flag > DPLASMA_MCA_* env > DB > default)
        self.tuning = None
        self._autopilot = None   # last precision-autopilot decision
        tune_applied: dict = {}
        if getattr(ip, "autotune", False):
            self.tuning, tune_applied = self._autotune_consult(wants_la)
        # scoped MCA override frames (utils.config override stack);
        # popped in LIFO order at close() so back-to-back Drivers in
        # one process never leak a knob
        self._mca_frames: list = []
        # resilience bookkeeping: which fn produced the last progress()
        # output (primary name or a ladder fallback label), and how many
        # -x verifications failed (run_driver turns that into exit 1)
        self.winner = name
        self.check_failures = 0
        self.inputs = self.output = self.compiled = None
        # roofline peaks (resolved lazily: --peaks-file / defaults)
        self._peaks_cache = None
        # observability: one profile + one run-report per driver run
        # (written at close() when --profile/--report asked for them)
        self.prof = Profile(rank=ip.rank)
        self.prof.save_info("driver", name)
        self.prof.save_info("prec", getattr(ip, "prec", "d"))
        self.report = RunReport(name, ip)
        from dplasma_tpu.ops import lu as _lu
        self._getrs_routes0 = dict(_lu.GETRS_ROUTES)
        # --telemetry: the live instruments — streaming Prometheus
        # exporter over the run's metrics registry + a flight recorder
        # of structured run events (v13 "telemetry" report section)
        self.telemetry = None
        if getattr(ip, "telemetry", None):
            from dplasma_tpu.observability.telemetry import Telemetry
            self.telemetry = Telemetry(rank=ip.rank)
            self.telemetry.start_exporter(self.report.metrics,
                                          ip.telemetry)
            self.telemetry.flight.record(
                "run_start", driver=name,
                prec=getattr(ip, "prec", "d"), N=ip.N, NB=ip.NB,
                grid=[ip.P, ip.Q])
        ndev = len(jax.devices())
        if ip.P * ip.Q > 1:
            if ip.P * ip.Q > ndev:
                raise SystemExit(
                    f"grid {ip.P}x{ip.Q} needs {ip.P*ip.Q} devices, "
                    f"have {ndev}")
            self.mesh = pmesh.make_mesh(ip.P, ip.Q,
                                        jax.devices()[:ip.P * ip.Q])
        self._cm = pmesh.use_grid(self.mesh) if self.mesh else None
        if self._cm:
            self._cm.__enter__()
        # the scoped overrides are applied LAST (everything above is
        # raise-prone construction that must not leak process-global
        # knobs) and NEST: --lookahead's frame first, the tuner's
        # frame innermost — close() pops them in LIFO order
        try:
            if wants_la:
                self._mca_frames.append(_cfg.push_overrides(
                    {"sweep.lookahead": ip.lookahead},
                    label="--lookahead"))
            if tune_applied:
                self._mca_frames.append(_cfg.push_overrides(
                    tune_applied, label="--autotune"))
            # resolve the pipeline shape (the FULL knob vector, schema
            # v11) from the now-active configuration — the same source
            # every sweep/panel callback reads
            la, agg = sweep_params()
            from dplasma_tpu.kernels import panels as _panels
            self.pipeline = {
                "sweep.lookahead": la, "qr.agg_depth": agg,
                "lu.agg_depth": _cfg.mca_get_int("lu.agg_depth", 4),
                "panel.kernel": _panels.panel_kernel_config(),
                "panel.qr": _panels.panel_kernel("qr"),
                "panel.lu": _panels.panel_kernel("lu"),
                "panel.tree_leaf": _cfg.mca_get_int(
                    "panel.tree_leaf", 2),
                "panel.rec_base": _cfg.mca_get_int(
                    "panel.rec_base", 8),
                "ring.enable": (_cfg.mca_get("ring.enable")
                                or "auto")}
            if self.tuning is not None:
                self.pipeline["tuning.source"] = self.tuning["source"]
                self.report.add_tuning(self.tuning)
                reg = self.report.metrics
                reg.counter("tuning_consults_total",
                            source=self.tuning["source"],
                            op=self.tuning["op"]).inc()
                reg.counter("tuning_overrides_total",
                            op=self.tuning["op"]).inc(
                    len(tune_applied)
                    + (1 if self.tuning.get("nb") else 0))
                if ip.rank == 0 and ip.loud >= 2:
                    print("#+ tuning: source=%s key=%s nb=%s "
                          "applied=%s"
                          % (self.tuning["source"], self.tuning["key"],
                             self.tuning.get("nb"),
                             self.tuning.get("applied") or {}))
            self.report.pipeline = dict(self.pipeline)   # schema v4
        except BaseException:
            for frame in reversed(self._mca_frames):
                _cfg.pop_overrides(frame)
            self._mca_frames = []
            raise

    def _autotune_consult(self, wants_la: bool):
        """``--autotune``: resolve this run's knobs from the
        persistent tuning database (:mod:`dplasma_tpu.tuning`) —
        exact key, or the nearest measured neighbor. Returns the v11
        ``"tuning"`` summary plus the MCA overrides to apply (the DB
        knob vector filtered by precedence: keys an explicit override
        or env var already pins are dropped, ``sweep.lookahead`` is
        dropped under an explicit ``--lookahead``). The DB tile size
        applies only when the CLI did not pin ``-t/-T``."""
        from dplasma_tpu.observability.comm import OP_CLASS
        from dplasma_tpu.tuning import db as _tdb
        ip = self.ip
        algo = _algo_of(self.name)
        op = OP_CLASS.get(algo, algo)
        entry, source, key, path = _tdb.consult(
            op, ip.N, PRECISIONS[ip.prec], (ip.P, ip.Q))
        summary = {"op": algo, "key": key, "source": source,
                   "db": path, "knobs": None, "applied": {},
                   "nb": None, "measured_s": None, "entry_key": None}
        applied: dict = {}
        if entry is not None and isinstance(entry.get("knobs"), dict):
            knobs = entry["knobs"]
            summary["knobs"] = dict(knobs)
            summary["measured_s"] = entry.get("measured_s")
            try:
                summary["entry_key"] = _tdb.make_key(
                    entry["op"], entry["n"], entry["dtype"],
                    entry["grid"])
            except (KeyError, TypeError):
                summary["entry_key"] = None
            applied = _tdb.appliable(
                knobs, skip=("sweep.lookahead",) if wants_la else ())
            summary["applied"] = dict(applied)
            nb = knobs.get("nb")
            if isinstance(nb, int) and nb > 0:
                # an interpolated neighbor may have been measured at a
                # much larger n: a tile wider than this problem would
                # pad the whole run (the generators pad to the tile
                # boundary) — clamp, exactly like the serving path
                nb = min(nb, max(min(ip.M or ip.N, ip.N), 1))
            if isinstance(nb, int) and nb > 0 \
                    and not getattr(ip, "nb_explicit", False):
                # apply the DB tile size over the defaults cascade;
                # HNB/HMB followed NB/MB's default — keep them in step
                if ip.HNB == ip.NB:
                    ip.HNB = nb
                if ip.HMB == ip.MB:
                    ip.HMB = nb
                ip.MB = ip.NB = nb
                summary["nb"] = nb
        return summary, applied

    def autopilot(self, op: str, a, spd: bool = False):
        """``--autotune`` precision pre-flight: sketch the concrete
        operand's condition class and resolve the stored
        ``ir.precision`` rung for this ``(op, n, dtype, cond_class)``
        key (:mod:`dplasma_tpu.tuning.autopilot`). A resolved rung
        pins a scoped MCA frame (popped at close(), innermost —
        the concrete-operand decision outranks the shape-keyed
        tuner's knob vector); the decision lands in the v17
        ``"autopilot"`` report section, ``autopilot_consults_total``,
        and the flight recorder. Returns the decision summary, or
        None (no ``--autotune`` / autopilot off / no DB). A later
        escalation reported through :meth:`report_refine` writes the
        negative entry back so the DB bucket converges."""
        import numpy as np
        from dplasma_tpu.utils import config as _cfg
        ip = self.ip
        if not getattr(ip, "autotune", False):
            return None
        from dplasma_tpu.tuning import autopilot as _ap
        try:
            host = np.asarray(a.to_dense()
                              if hasattr(a, "to_dense") else a)
            summary = _ap.consult(op, int(host.shape[-1]),
                                  PRECISIONS[ip.prec], host, spd=spd,
                                  grid=(ip.P, ip.Q))
        except Exception as exc:
            sys.stderr.write(f"#! autopilot consult failed: {exc}\n")
            return None
        if summary is None:
            return None
        if summary.get("precision"):
            self._mca_frames.append(_cfg.push_overrides(
                {"ir.precision": summary["precision"]},
                label="autopilot"))
        self._autopilot = summary
        self.report.add_autopilot(summary)
        reg = self.report.metrics
        reg.counter("autopilot_consults_total", op=op,
                    source=summary["source"],
                    cond_class=summary["cond_class"]).inc()
        if self.telemetry is not None:
            self.telemetry.flight.record(
                "autopilot", op=op,
                precision=summary.get("precision"),
                cond_class=summary["cond_class"],
                source=summary["source"])
        if ip.rank == 0 and ip.loud >= 2:
            print("#+ autopilot[%s]: cond~%.3e class=%s precision=%s "
                  "(%s)" % (op, summary["cond_estimate"],
                            summary["cond_class"],
                            summary.get("precision") or "default",
                            summary["source"]))
            sys.stdout.flush()
        return summary

    def close(self):
        from dplasma_tpu.utils import config as _cfg
        # scoped MCA overrides restore in LIFO order: the tuner's
        # frame pops before the --lookahead frame it nests inside
        # (utils.config.pop_overrides enforces the order)
        for frame in reversed(getattr(self, "_mca_frames", [])):
            _cfg.pop_overrides(frame)
        self._mca_frames = []
        ip = self.ip
        from dplasma_tpu.ops import lu as _lu
        for route, n in sorted(_lu.GETRS_ROUTES.items()):
            ran = n - self._getrs_routes0.get(route, 0)
            if ran:
                self.report.metrics.counter("lu_getrs_route_total",
                                            route=route).inc(ran)
        self._getrs_routes0 = dict(_lu.GETRS_ROUTES)
        if getattr(self, "telemetry", None) is not None:
            # final exporter flush + the v13 section, BEFORE the
            # report writes below so the document carries it
            self.telemetry.close()
            self.report.add_telemetry(self.telemetry.summary())
            if ip.rank == 0 and ip.loud >= 1 and self.telemetry.exporter:
                ex = self.telemetry.exporter
                print(f"#+ telemetry: {ex.flushes} snapshot(s) "
                      f"exported to {ex.path}")
        if getattr(ip, "profile", None):
            try:
                self.prof.write(ip.profile)
                if ip.rank == 0 and ip.loud >= 1:
                    print(f"#+ profile trace written to {ip.profile}")
            except OSError as exc:
                sys.stderr.write(f"#! cannot write profile: {exc}\n")
        if getattr(ip, "report", None):
            try:
                # schema v18 attribution stamp: whose code, whose
                # mesh, whose peaks — collected at close() so the
                # MCA snapshot reflects the knobs the run ended with
                self.report.stamp_provenance(
                    family=self.report.name,
                    mesh_shape=[ip.P, ip.Q],
                    peaks_source=("file"
                                  if getattr(ip, "peaks_file", None)
                                  else "default"))
                self.report.write(ip.report)
                if ip.rank == 0 and ip.loud >= 1:
                    print(f"#+ run-report written to {ip.report}")
            except OSError as exc:
                sys.stderr.write(f"#! cannot write report: {exc}\n")
        if self._cm:
            self._cm.__exit__(None, None, None)
            self._cm = None

    # --- timing & reporting -------------------------------------------
    def _sync(self, out):
        import jax
        jax.block_until_ready(out)

    def _comm_model(self):
        """Analytic comm-volume model for this driver's op class (None
        when the op has no model — the report shows an explicit null)."""
        import numpy as _np

        from dplasma_tpu.descriptors import Dist
        from dplasma_tpu.observability.comm import comm_volume_model
        ip = self.ip
        try:
            itemsize = _np.dtype(PRECISIONS[ip.prec]).itemsize
            return comm_volume_model(
                _algo_of(self.name), ip.M, ip.N, ip.K, ip.MB, ip.NB,
                itemsize, Dist(P=ip.P, Q=ip.Q, kp=ip.kp, kq=ip.kq))
        except Exception:
            return None

    def _dagcheck(self, rec, name):
        """--dagcheck: statically verify the recorded tile DAG
        (analysis.dagcheck) before the timed loop runs — acyclicity,
        def-before-use flow coverage, WAW/WAR races, owner-computes
        rank consistency, and reconciliation of the cross-rank flow
        edges against the analytic comm model. The summary lands in
        the run-report (schema v3 ``"dagcheck"`` section); violations
        raise DagCheckError so a wrong DAG never executes."""
        from dplasma_tpu.analysis import dagcheck as dc
        from dplasma_tpu.descriptors import Dist
        ip = self.ip
        dist = Dist(P=ip.P, Q=ip.Q, kp=ip.kp, kq=ip.kq)
        res = dc.check_dag(rec, rank_of=dc.rank_of_dist(dist))
        dc.check_comm(rec, _algo_of(self.name), ip.M, ip.N, ip.K,
                      ip.MB, ip.NB, dist, res)
        self.report.add_dagcheck(name, res.summary())
        lbl = dict(op=name, prec=ip.prec)
        reg = self.report.metrics
        reg.counter("dagcheck_tasks_total", **lbl).inc(res.tasks)
        reg.counter("dagcheck_diagnostics_total", **lbl).inc(
            len(res.diagnostics))
        if ip.rank == 0 and (ip.loud >= 2 or not res.ok):
            print(res.format(name))
            sys.stdout.flush()
        if not res.ok:
            raise dc.DagCheckError(res)
        return res

    def _memcheck(self, rec, name):
        """--memcheck: statically verify the recorded schedule's HBM
        residency (analysis.memcheck) before the timed loop runs —
        per-tile live intervals over the wavefront linearization the
        runtime executes, per-rank peak resident bytes under the
        block-cyclic distribution with dd limb pricing, and the
        predicted-HBM-peak gate against MCA ``memcheck.hbm_budget``
        (the diagnostic names the peak-driving task, tile, and live
        set; a spill/prefetch streaming plan is derived when the
        budget forces one). The summary lands in the run-report
        (schema v16 ``"memcheck"`` section); violations raise
        MemCheckError so an over-budget schedule never executes.
        When --hlocheck also runs, its measured memory_analysis peak
        cross-validates the prediction (see :meth:`_hlocheck`)."""
        from dplasma_tpu.analysis import memcheck as mc
        from dplasma_tpu.descriptors import Dist
        ip = self.ip
        dist = Dist(P=ip.P, Q=ip.Q, kp=ip.kp, kq=ip.kq)
        item = mc.effective_itemsize(PRECISIONS[ip.prec])
        res = mc.check_schedule(
            rec, mb=max(ip.MB, 1), nb=max(ip.NB, 1), itemsize=item,
            dist=dist, lookahead=self.pipeline["sweep.lookahead"],
            kernel=name)
        entry = self.report.add_memcheck(name, res.summary())
        self._memcheck_last = (res, entry)
        lbl = dict(op=name, prec=ip.prec)
        reg = self.report.metrics
        reg.counter("memcheck_tiles_total", **lbl).inc(res.tiles)
        reg.counter("memcheck_diagnostics_total", **lbl).inc(
            len(res.diagnostics))
        reg.gauge("memcheck_peak_bytes", **lbl).set(
            res.resident_peak_bytes)
        reg.gauge("memcheck_predicted_hbm_peak_bytes", **lbl).set(
            res.predicted_hbm_peak_bytes)
        if ip.rank == 0 and (ip.loud >= 2 or not res.ok):
            print(res.format(name))
            sys.stdout.flush()
        if not res.ok:
            raise mc.MemCheckError(res)
        return res

    def _spmdcheck(self, fn, args, name):
        """--spmdcheck: extract the collective schedule of the program
        about to run (jaxpr-level, no execution) and verify the
        structural SPMD invariants — axis binding, per-rank sequence
        uniformity (no collectives behind rank-divergent cond/while),
        ppermute bijections. The summary (collective counts included)
        lands in the run-report (schema v6 ``"spmdcheck"`` section);
        violations raise SpmdCheckError before the timed loop. The
        exact collective-count contract against the analytic comm
        model is enforced where the kernel identity is known — the
        cyclic kernels, via tools/lint_all.py and tests — because a
        driver body may legitimately wrap them in conversions. A
        GSPMD-partitioned op (no explicit shard_map) reports
        no-collectives: its schedule belongs to XLA, not this gate."""
        from dplasma_tpu.analysis import spmdcheck as sp
        ip = self.ip
        try:
            res = sp.extract_schedule(fn, *args, kernel=name)
        except Exception as exc:
            # verification tracing must never break a run the real
            # compile path accepts (e.g. a fallback-only dtype)
            sys.stderr.write(
                f"#! spmdcheck trace failed for {name}: {exc!r}\n")
            return None
        res.relation = ("no-collectives" if not res.collectives
                        else "structural")
        self.report.add_spmdcheck(name, res.summary())
        lbl = dict(op=name, prec=ip.prec)
        reg = self.report.metrics
        reg.counter("spmdcheck_collectives_total", **lbl).inc(
            sum(c.count for c in res.collectives))
        reg.counter("spmdcheck_diagnostics_total", **lbl).inc(
            len(res.diagnostics))
        if ip.rank == 0 and (ip.loud >= 2 or not res.ok):
            print(res.format(name))
            sys.stdout.flush()
        if not res.ok:
            raise sp.SpmdCheckError(res)
        return res

    def _hlocheck(self, lowered, compiled, fn, args, name,
                  schedule=None):
        """``--hlocheck``: audit the exact compiled executable the
        timed loop is about to run (analysis.hlocheck) — per-kind
        collective counts reconciled against the jaxpr-level schedule
        of the same program and the analytic comm model (a dropped
        collective or an under-implemented model class fails), float
        demotions below the working precision outside the registered
        dd/limb sites, requested-but-dropped buffer donations, peak
        memory vs MCA ``hlocheck.hbm_budget``, and host-callback /
        copy-volume anti-patterns. The summary lands in the
        run-report (schema v10 ``"hlocheck"`` section); violations
        raise HloCheckError so a wrong artifact never executes."""
        from dplasma_tpu.analysis import hlocheck as hc
        from dplasma_tpu.analysis import spmdcheck as sp
        from dplasma_tpu.observability.xla import capture_compiled
        ip = self.ip
        if schedule is None:
            # --spmdcheck hands its already-extracted schedule in;
            # standalone --hlocheck traces the program itself
            try:
                schedule = sp.extract_schedule(fn, *args, kernel=name)
            except Exception as exc:
                # the artifact checks still run; only the jaxpr-vs-HLO
                # reconciliation degrades (a fallback-only dtype may
                # not re-trace the way the compiled path did)
                sys.stderr.write(
                    f"#! hlocheck trace failed for {name}: {exc!r}\n")
        # the comm-model leg applies only where the model's collective
        # structure is actually on the wire: a cyclic shard_map
        # program (schedule has collectives) of a modelled op class
        op, KT = None, 0
        ring = False
        if schedule is not None and schedule.collectives:
            op, KT = _model_op_kt(_algo_of(self.name), ip)
            if op is not None:
                # the model leg must price the schedule the kernels
                # resolved: THE SAME gate the cyclic wrappers consult
                # (cyclic._cyclic_ring — per-axis runtime probe +
                # geometry, need_row for the LU exchange), so the
                # two can never disagree on a mesh where one axis
                # gates differently than the other
                from dplasma_tpu.descriptors import Dist
                from dplasma_tpu.parallel import cyclic as _cyc
                desc = _cyc.CyclicDesc(
                    ip.M, ip.N, max(ip.MB, 1), max(ip.NB, 1),
                    Dist(P=ip.P, Q=ip.Q, kp=ip.kp, kq=ip.kq))
                ring = _cyc._cyclic_ring(
                    desc, PRECISIONS[ip.prec], self.mesh,
                    need_row=(op == "getrf"))
        xla_info = capture_compiled(compiled)
        # --report captures the same analyses after the timed loop:
        # remember this pass so an unchanged executable isn't
        # re-analyzed
        self._hlo_xla_cache = (compiled, xla_info)
        # exact-or-dominating: a driver body may wrap the cyclic
        # kernel in GSPMD-sharded conversions whose collectives the
        # partitioner owns — the kernel's pinned schedule must be
        # fully implemented (dominating); the exact == contract is
        # enforced where the program IS the kernel (tools/lint_all.py
        # hlocheck-smoke and tests)
        res = hc.check_executable(
            lowered, compiled, name, schedule=schedule, exact=False,
            op=op, KT=KT,
            lookahead=self.pipeline["sweep.lookahead"],
            prec=ip.prec, ring=ring, grid=(ip.P, ip.Q),
            xla_info=xla_info)
        self.report.add_hlocheck(name, res.summary())
        lbl = dict(op=name, prec=ip.prec)
        reg = self.report.metrics
        reg.counter("hlocheck_collectives_total", **lbl).inc(
            sum(res.counts.values()))
        reg.counter("hlocheck_diagnostics_total", **lbl).inc(
            len(res.diagnostics))
        if res.hbm_peak_bytes is not None:
            reg.gauge("hlocheck_hbm_peak_bytes", **lbl).set(
                res.hbm_peak_bytes)
            mem_last = getattr(self, "_memcheck_last", None)
            if mem_last is not None:
                # --memcheck ran on this op's recording: reconcile
                # the static prediction against the MEASURED compiled
                # peak. A miss (prediction below measurement) is a
                # named finding — the model let a compiled temp
                # escape — recorded on the report entry and in
                # metrics, never fatal (the gate already passed on
                # the documented model).
                from dplasma_tpu.analysis import memcheck as mc
                mres, mentry = mem_last
                findings = mc.cross_validate(
                    mres.predicted_hbm_peak_bytes,
                    res.hbm_peak_bytes, name)
                mentry["cross"] = {
                    "measured_hbm_peak_bytes": res.hbm_peak_bytes,
                    "findings": [d.as_dict() for d in findings]}
                reg.counter("memcheck_cross_findings_total",
                            **lbl).inc(len(findings))
                for d in findings:
                    sys.stderr.write(
                        f"#! memcheck[{name}]: {d.message}\n")
                self._memcheck_last = None
        if ip.rank == 0 and (ip.loud >= 2 or not res.ok):
            print(res.format(name))
            sys.stdout.flush()
        if not res.ok:
            raise hc.HloCheckError(res)
        return res

    def _peaks(self):
        """Resolve the roofline peaks once per driver run
        (``--peaks-file`` — a bench doc/report or raw peaks dict —
        else the conservative built-ins). An unreadable file degrades
        to the defaults with a warning, never a failed run."""
        if self._peaks_cache is None:
            from dplasma_tpu.observability import roofline as _rl
            try:
                self._peaks_cache = _rl.resolve_peaks(
                    getattr(self.ip, "peaks_file", None),
                    prec=getattr(self.ip, "prec", "d"))
            except (OSError, ValueError) as exc:
                sys.stderr.write(f"#! cannot read peaks file: {exc}\n")
                self._peaks_cache = (dict(_rl.DEFAULT_PEAKS), "default")
        return self._peaks_cache

    def _phase_attribution(self, fn, args, name):
        """``--phase-profile``: one extra EAGER attributed pass after
        the timed loop. Eager dispatch gives the phase spans real
        execution boundaries (per-callback jits on the dd routes, one
        XLA op at a time elsewhere); each span fences at exit and the
        ledger's measured times meet the roofline model's per-phase
        expectations. The timed loop itself never fences — the default
        path's fusion/overlap is untouched — so ``attributed_run_s``
        is a separate, deliberately serialized measurement. Returns
        the schema-v5 ``"phases"`` dict, or None when the pass fails
        (a fn that only compiles under jit, an OOM, ...)."""
        from dplasma_tpu.observability import phases as _phases
        from dplasma_tpu.observability import roofline as _rl
        from dplasma_tpu.observability.comm import OP_CLASS
        ip = self.ip
        t0 = time.perf_counter()
        try:
            with _phases.profiling() as led, \
                    self.prof.span(f"phase:{name}"):
                out = fn(*args)
                self._sync(out)
        except Exception as exc:
            sys.stderr.write(
                f"#! phase attribution failed for {name}: {exc!r}\n")
            return None
        total = time.perf_counter() - t0
        peaks, src = self._peaks()
        itemsize = np.dtype(PRECISIONS[ip.prec]).itemsize
        model = _rl.phase_model(
            OP_CLASS.get(_algo_of(self.name)), ip.M, ip.N, ip.NB,
            itemsize, lookahead=self.pipeline["sweep.lookahead"],
            agg_depth=self.pipeline["qr.agg_depth"], nrhs=ip.K,
            peaks=peaks, grid=(ip.P, ip.Q))
        spans = _rl.attribute_phases(led, model, peaks)
        ssum = led.total()
        return {"attributed_run_s": total, "sum_s": ssum,
                "coverage": (ssum / total) if total > 0 else None,
                "peaks_source": src, "spans": spans}

    def _lower_compile(self, fn, args, name):
        """Trace+compile on the default backend. A compile error
        propagates: no op is quietly re-run on another device.
        Returns (lowered, compiled, args)."""
        import jax
        jfn = fn if isinstance(fn, jax.stages.Wrapped) else jax.jit(fn)
        lowered = jfn.lower(*args)
        return lowered, lowered.compile(), args

    def progress(self, fn: Callable, args: tuple, flops: float,
                 label: Optional[str] = None, dag_fn: Callable = None,
                 verify_fn: Callable = None, fallbacks=()):
        """Compile, run nruns times, print the reference-format perf line.

        ENQ = trace+compile (the taskpool-construction analog),
        PROG = best device execution time, DEST = teardown (~0 here).
        Every phase lands in ``self.prof`` (DTPUPROF1 spans) and an op
        entry in ``self.report`` (per-run stats, XLA cost/memory
        analysis, comm model, DAG analytics). Returns (output, gflops).

        Resilience (``--inject/--abft/--run-timeout``, see
        :mod:`dplasma_tpu.resilience`): the armed fault plan corrupts
        the first attempt's trace; after the timed loop a health scan
        (plus ``verify_fn``, the op's ABFT post-verification, which may
        return a corrected output) gates the result, and on failure the
        remediation ladder walks retry → kernel fallback → the driver
        body's ``fallbacks`` alternates, re-tracing each rung. Stats
        and the perf line come from the final (surviving) attempt;
        ``self.winner`` names the fn that produced the output.
        """
        from dplasma_tpu.observability.xla import capture_compiled
        from dplasma_tpu.resilience import guard
        from dplasma_tpu.resilience import inject as rinject
        from dplasma_tpu.utils import profiling
        ip, name = self.ip, label or self.name
        tel = getattr(self, "telemetry", None)
        if tel is not None:
            tel.flight.record("op_start", op=name, flops=flops)
        resil = guard.enabled(ip)
        ladder = guard.Ladder(ip, name, fallbacks) if resil else None
        plan = None
        if resil and getattr(ip, "inject", None):
            plan = rinject.parse_plan(ip.inject, seed=ip.seed)
        injection = {"plan": plan.spec(), "faults": []} if plan else None

        cur_fn, cur_label = fn, name
        action = guard.ACTION_PRIMARY
        first_compile = True
        spmd_res = None      # --spmdcheck schedule, reused by hlocheck
        out = None
        warm = None
        times: list = []
        enq = 0.0
        dag_info = None
        while True:
            t0 = time.perf_counter()
            armed = plan is not None and action == guard.ACTION_PRIMARY
            if armed:
                rinject.arm(plan)  # faults corrupt the primary trace only
            try:
                with self.prof.span(f"enq:{name}"):
                    lowered, compiled, args = self._lower_compile(
                        cur_fn, args, name)
            except Exception as exc:
                if armed:
                    # the trace died before compiling: its faults never
                    # ran — disarm but do NOT report them as injected
                    rinject.disarm()
                if ladder is None:
                    raise
                ladder.record(action, cur_label, ok=False,
                              classification=guard.CLASS_COMPILE,
                              error=repr(exc),
                              elapsed_s=time.perf_counter() - t0)
                nxt = ladder.next_action(guard.CLASS_COMPILE)
                if nxt is None:
                    self._finish_resilience(ladder, injection)
                    raise
                action, cur_label, nfn = nxt
                if nfn is not None:
                    cur_fn = nfn
                if action == guard.ACTION_KERNEL_FALLBACK:
                    guard.kernel_fallback()
                continue
            if armed:
                # harvest only from a trace that actually compiled:
                # these faults are baked into the executable the timed
                # loop will run
                injection["faults"].extend(rinject.disarm())
            enq = time.perf_counter() - t0
            if first_compile:
                first_compile = False
                if ip.rank == 0 and ip.loud >= 2 and \
                        not getattr(self, "_pipe_printed", False):
                    self._pipe_printed = True
                    print("#+ pipeline: sweep.lookahead=%d "
                          "qr.agg_depth=%d panel.qr=%s panel.lu=%s"
                          % (self.pipeline["sweep.lookahead"],
                             self.pipeline["qr.agg_depth"],
                             self.pipeline["panel.qr"],
                             self.pipeline["panel.lu"]))
                # analytic DAG construction is cubic-ish in tile count;
                # the implicit consumers (--report, -v>=3) cap it, the
                # explicit --dot opt-in always honors the request. K
                # tiles count too: the GEMM DAG is MT*NT*KT tasks.
                tiles = max(-(-ip.M // max(ip.MB, 1)), 1) * \
                    max(-(-ip.N // max(ip.NB, 1)), 1) * \
                    max(-(-ip.K // max(ip.NB, 1)), 1)
                want_dag = dag_fn is not None and (
                    ip.dot or ip.dagcheck
                    or getattr(ip, "memcheck", False)
                    or ((ip.report or ip.loud >= 3)
                        and tiles <= _DAG_TILE_CAP))
                if want_dag:
                    from dplasma_tpu.observability.dag import (
                        dag_stats, format_dag_stats)
                    # scoped recording on the module-global recorder:
                    # cleared per run, restored after (no cross-run
                    # accumulation)
                    with profiling.recording() as rec:
                        dag_fn(rec)
                        if ip.dot:
                            with open(ip.dot, "w") as f:
                                f.write(rec.to_dot(name or "dag"))
                        if ip.dagcheck:
                            # verify before execute: a dataflow
                            # violation aborts the run here, before
                            # the timed loop ever dispatches
                            self._dagcheck(rec, name)
                        if getattr(ip, "memcheck", False):
                            # residency gate on the same recording:
                            # an over-budget schedule aborts here,
                            # before the timed loop ever dispatches
                            self._memcheck(rec, name)
                        dag_info = dag_stats(rec)
                    if ip.rank == 0 and ip.loud >= 3:
                        print(format_dag_stats(dag_info, name))
                elif ip.dagcheck and ip.rank == 0 and ip.loud >= 1:
                    print(f"#+ dagcheck[{name}]: no analytic tile-DAG "
                          f"builder for this op; skipped")
                elif getattr(ip, "memcheck", False) and ip.rank == 0 \
                        and ip.loud >= 1:
                    print(f"#+ memcheck[{name}]: no analytic tile-DAG "
                          f"builder for this op; skipped")
                if getattr(ip, "spmdcheck", False):
                    # verify the traced SPMD program's collective
                    # schedule before the timed loop ever dispatches
                    spmd_res = self._spmdcheck(cur_fn, args, name)
                if not want_dag and ip.dot:
                    # no analytic tile-DAG builder for this op: fall
                    # back to the lowered XLA program text
                    # (tests/common.c:406-431)
                    with open(ip.dot, "w") as f:
                        f.write(lowered.as_text())
                if ip.dot and ip.rank == 0 and ip.loud >= 1:
                    print(f"#+ traced DAG written to {ip.dot}")
            if getattr(ip, "hlocheck", False) and \
                    getattr(self, "_hlo_audited", None) is not compiled:
                # audit the COMPILED artifact (post-GSPMD HLO) before
                # the timed loop ever dispatches — EVERY executable
                # that will run, including remediation-ladder fallback
                # artifacts recompiled after a runtime failure (the
                # contract is "a wrong artifact never executes", not
                # "the first artifact"). The first pass reuses
                # --spmdcheck's schedule; a fallback rung's program
                # differs, so its schedule is re-traced fresh.
                self._hlocheck(lowered, compiled, cur_fn, args,
                               cur_label, schedule=spmd_res)
                self._hlo_audited = compiled
                spmd_res = None
            if getattr(ip, "warmup", True):
                # rank-local warm run EXCLUDED from stats (the
                # reference drivers' warmup pattern, ref
                # tests/testing_zpotrf.c:138-202: a CPU-then-each-
                # device warm pass before timing; here one untimed
                # execution absorbs first-run effects — autotuning,
                # allocator growth — that ENQ's compile split does not
                # cover)
                t0 = time.perf_counter()
                with self.prof.span(f"warmup:{name}"):
                    self._sync(compiled(*args))
                warm = time.perf_counter() - t0
            # --jaxtrace: device-side op/kernel capture around the
            # timed loop only (not compile/warmup)
            trace_cm = _jaxtrace_guard(ip.jaxtrace) if ip.jaxtrace \
                else contextlib.nullcontext()
            # --devprof: hardware-profile capture around the same
            # window; a remediation re-run recreates the capture so
            # the surviving attempt owns the ingested timeline
            dp_cap = None
            if getattr(ip, "devprof", False):
                from dplasma_tpu.observability import devprof as _dp
                dp_cap = _dp.DevprofCapture()
            wd = guard.Watchdog(getattr(ip, "run_timeout", 0.0), name) \
                if resil else None
            times = []
            with trace_cm, (dp_cap or contextlib.nullcontext()), \
                    (wd or contextlib.nullcontext()):
                for i in range(max(ip.nruns, 1)):
                    t0 = time.perf_counter()
                    with self.prof.span(f"run[{i}]:{name}", flops=flops,
                                        track=self.prof.TRACK_RUN):
                        out = compiled(*args)
                        self._sync(out)
                    times.append(time.perf_counter() - t0)
            if not resil:
                break
            # post-run gate: non-finite census + the op's ABFT verify
            # (which may hand back a corrected / de-augmented output)
            health = guard.health_scan(out)
            ok = health["ok"]
            verify_rep = None
            # the ABFT verifier understands the PRIMARY fn's
            # (checksum-augmented) output contract; algo-fallback
            # alternates return their own plain contract
            if verify_fn is not None \
                    and action != guard.ACTION_ALGO_FALLBACK:
                out, verify_rep = verify_fn(out)
                ok = ok and verify_rep.get("ok", True)
            timed_out = wd.timed_out
            ok = ok and not timed_out
            if ok:
                ladder.record(action, cur_label, True, health=health,
                              abft=verify_rep, elapsed_s=sum(times))
                ladder.winner = cur_label
                break
            cls = ladder.classify(health, verify_rep, timed_out)
            ladder.record(action, cur_label, False, classification=cls,
                          health=health, abft=verify_rep,
                          elapsed_s=sum(times))
            nxt = ladder.next_action(cls)
            if nxt is None:
                # ladder exhausted: keep the last output (the -x check
                # and exit code report the failure downstream)
                ladder.winner = cur_label
                break
            action, cur_label, nfn = nxt
            if nfn is not None:
                cur_fn = nfn
            if action == guard.ACTION_KERNEL_FALLBACK:
                guard.kernel_fallback()
        if resil:
            self._finish_resilience(ladder, injection)
        xla_info = None
        if ip.report:
            # reuse the --hlocheck pass's capture when the surviving
            # executable IS the audited one (a remediation rung that
            # re-traced gets a fresh capture)
            cached = getattr(self, "_hlo_xla_cache", None)
            xla_info = cached[1] if cached and cached[0] is compiled \
                else capture_compiled(compiled)
        best = min(times)
        t0 = time.perf_counter()
        dest = time.perf_counter() - t0
        gflops = (flops / 1e9) / best
        total = enq + best + dest
        want_attrib = ip.report or getattr(ip, "phase_profile", False)
        comm = self._comm_model() if want_attrib else None
        # --phase-profile: the attributed eager pass runs AFTER the
        # timed loop (and after any remediation settled on cur_fn), so
        # the stats above are from the fence-free compiled path
        phase_info = None
        if getattr(ip, "phase_profile", False):
            phase_info = self._phase_attribution(cur_fn, args, name)
        entry = self.report.add_op(
            name, prec=ip.prec, flops=flops, enq_s=enq, warmup_s=warm,
            dest_s=dest, runs_s=times, gflops=gflops, xla=xla_info,
            comm=comm, dag=dag_info, phases=phase_info)
        if tel is not None:
            tel.flight.record("op_done", op=name, winner=self.winner,
                              best_s=best, gflops=gflops,
                              nruns=len(times))
        # roofline ledger: expected-vs-measured for the whole op
        # (schema v5 "roofline" section)
        rl_entry = None
        if want_attrib:
            from dplasma_tpu.observability import roofline as _rl
            from dplasma_tpu.observability.comm import OP_CLASS
            peaks, src = self._peaks()
            itemsize = np.dtype(PRECISIONS[ip.prec]).itemsize
            rl_entry = self.report.add_roofline(_rl.op_roofline(
                name, OP_CLASS.get(_algo_of(self.name)), ip.M, ip.N,
                ip.K, itemsize, flops, comm, best, peaks, src))
        # --devprof: ingest the captured hardware timeline (or
        # synthesize one from this run + the spmdcheck schedule + the
        # comm-model pricing) and attribute it — schema v14 "devprof"
        dp_entry = None
        if getattr(ip, "devprof", False):
            from dplasma_tpu.observability import devprof as _dp
            op_cls, op_kt = _model_op_kt(_algo_of(self.name), ip)
            dp_ring = False
            if op_cls is not None and ip.P * ip.Q > 1:
                # the SAME ring gate hlocheck's model leg consults,
                # so the priced schedule matches what the kernels ran
                from dplasma_tpu.descriptors import Dist
                from dplasma_tpu.parallel import cyclic as _cyc
                dp_desc = _cyc.CyclicDesc(
                    ip.M, ip.N, max(ip.MB, 1), max(ip.NB, 1),
                    Dist(P=ip.P, Q=ip.Q, kp=ip.kp, kq=ip.kq))
                dp_ring = _cyc._cyclic_ring(
                    dp_desc, PRECISIONS[ip.prec], self.mesh,
                    need_row=(op_cls == "getrf"))
            dpeaks, _src = self._peaks()
            try:
                dp_entry = _dp.attribute(
                    name, op_cls, best, (ip.P, ip.Q), ip.M, ip.N,
                    max(ip.NB, 1),
                    itemsize=np.dtype(PRECISIONS[ip.prec]).itemsize,
                    kt=op_kt or None, ring=dp_ring,
                    lookahead=self.pipeline["sweep.lookahead"],
                    peaks=dpeaks,
                    timeline=(dp_cap.events or None)
                    if dp_cap is not None else None,
                    backend=dp_cap.used if dp_cap is not None
                    else "synthetic")
            except Exception as exc:  # noqa: BLE001 — attribution is
                # observability, not correctness: a failed ingest must
                # not kill the run it describes. The failure is loud —
                # flight-recorder event + stderr — never silent.
                if tel is not None:
                    tel.flight.record("devprof_error", op=name,
                                      error=repr(exc))
                sys.stderr.write(
                    f"#! devprof attribution failed for {name}: "
                    f"{exc!r}\n")
            if dp_entry is not None:
                if dp_cap is not None and dp_cap.note:
                    dp_entry["note"] = dp_cap.note
                self.report.add_devprof(dp_entry)
                if tel is not None:
                    for d in dp_entry["diagnostics"]:
                        tel.flight.record("devprof_diag", op=name,
                                          diag=d["kind"],
                                          target=d["op"])
                    if not dp_entry["ok"]:
                        tel.flight.record(
                            "devprof_mismatch", op=name,
                            relation=dp_entry["reconciliation"]
                                             ["relation"])
        stats = entry["timings"]
        reg = self.report.metrics
        lbl = dict(op=name, prec=ip.prec)
        reg.counter("runs_total", **lbl).inc(len(times))
        hist = reg.histogram("run_seconds", **lbl)
        for t in times:
            hist.observe(t)
        reg.gauge("gflops_best", **lbl).set(gflops)
        reg.gauge("enq_seconds", **lbl).set(enq)
        reg.gauge("model_flops", **lbl).set(flops)
        if xla_info and xla_info.get("flops") is not None:
            reg.gauge("xla_flops", **lbl).set(xla_info["flops"])
        if xla_info and xla_info.get("peak_bytes") is not None:
            reg.gauge("xla_peak_bytes", **lbl).set(xla_info["peak_bytes"])
        if comm and comm.get("dag_model"):
            reg.gauge("comm_bytes_dag_model", **lbl).set(
                comm["dag_model"]["bytes_total"])
        if rl_entry is not None and rl_entry["achieved_frac"] is not None:
            reg.gauge("roofline_achieved_frac", **lbl).set(
                rl_entry["achieved_frac"])
        if phase_info is not None:
            for s in phase_info["spans"]:
                reg.gauge("phase_seconds", phase=s["phase"],
                          **lbl).set(s["measured_s"])
        if dp_entry is not None:
            dp_fracs = [c["achieved_frac"]
                        for c in dp_entry["collectives"]
                        if c["achieved_frac"] is not None]
            if dp_fracs:
                reg.gauge("devprof_ici_achieved_frac", **lbl).set(
                    min(dp_fracs))
            reg.gauge("devprof_skew", **lbl).set(
                dp_entry["skew"]["value"])
            for c, v in dp_entry["categories"].items():
                reg.gauge("devprof_seconds", category=c, **lbl).set(v)
        self.prof.save_dinfo(f"GFLOPS:{name}", gflops)
        if ip.rank == 0:
            if ip.loud >= 2:
                # per-run lines (the reference prints each run), then
                # the spread: best alone hides variance
                for i, t in enumerate(times):
                    print(f"#+ run {i}: {t:12.5f} s : "
                          f"{(flops / 1e9) / t:14f} gflops")
                if len(times) > 1:
                    print("#+ runs %d : min/median/max %g/%g/%g s "
                          "stddev %g" % (len(times), stats["min_s"],
                                         stats["median_s"],
                                         stats["max_s"],
                                         stats["stddev_s"]))
                if rl_entry is not None:
                    print("#+ roofline[%s]: bound=%s expected %.5g s "
                          "measured %.5g s achieved %s (peaks: %s)"
                          % (name, rl_entry["bound"],
                             rl_entry["expected_s"], best,
                             _pct(rl_entry["achieved_frac"]),
                             rl_entry["peaks_source"]))
                if dp_entry is not None:
                    dps = dp_entry["skew"]
                    print("#+ devprof[%s]: backend=%s coverage %s "
                          "relation=%s skew %.3f (slowest rank %d: "
                          "%s) critical-path %s"
                          % (name, dp_entry["backend"],
                             _pct(dp_entry["coverage"]),
                             dp_entry["reconciliation"]["relation"],
                             dps["value"], dps["slowest_rank"],
                             dps["dominating_category"],
                             _pct(dp_entry["critical_path"]["frac"])))
                    for c in dp_entry["collectives"]:
                        print("#+   %-16s n=%3d measured %10.5f s "
                              "achieved %7s of ICI peak"
                              % (c["cls"], c["count"],
                                 c["measured_s"],
                                 _pct(c["achieved_frac"])))
                if phase_info is not None:
                    print("#+ phases[%s]: attributed run %.5f s, "
                          "spans %.5f s (coverage %s)"
                          % (name, phase_info["attributed_run_s"],
                             phase_info["sum_s"],
                             _pct(phase_info["coverage"])))
                    for s in phase_info["spans"]:
                        print("#+   %-10s n=%3d measured %10.5f s "
                              "expected %10.5g s achieved %7s "
                              "bound=%s"
                              % (s["phase"], s["count"],
                                 s["measured_s"], s["expected_s"],
                                 _pct(s["achieved_frac"]),
                                 s["bound"]))
            if dp_entry is not None and not dp_entry["ok"] \
                    and ip.loud >= 1:
                # a reconciliation failure is worth a line even at
                # the default loudness: a priced collective the
                # ingested timeline lost is a measurement bug
                for d in dp_entry["diagnostics"]:
                    if d["kind"] in ("missing-collective",
                                     "count-mismatch"):
                        print(f"#! devprof[{name}]: {d['message']}")
            print("[****] TIME(s) %12.5f : %s\tPxQxg= %3d %-3d %d NB= %4d "
                  "N= %7d : %14f gflops - ENQ&PROG&DEST %12.5f : %14f gflops"
                  " - ENQ %12.5f - DEST %12.5f"
                  % (best, name, ip.P, ip.Q, ip.gpus, ip.NB, ip.N,
                     gflops, total, (flops / 1e9) / total, enq, dest))
            if ip.loud >= 5:
                print('<DartMeasurement name="performance" '
                      'type="numeric/double"\n'
                      '                 encoding="none" compression="none">\n'
                      f'{gflops:g}\n</DartMeasurement>')
            sys.stdout.flush()
        # the last timed op's operands, result and executable, for
        # run_driver's ``inspect`` callback
        self.inputs, self.output, self.compiled = args, out, compiled
        return out, gflops

    def _finish_resilience(self, ladder, injection):
        """Fold one progress() call's ladder walk into the run-report
        (``"resilience"`` section), metrics, and the -v>=2 prints."""
        from dplasma_tpu.resilience import guard
        summary = ladder.summary(injection)
        self.winner = ladder.winner
        self.report.add_resilience(summary)
        tel = getattr(self, "telemetry", None)
        if tel is not None:
            for f in (injection or {}).get("faults") or []:
                tel.flight.record("inject", op=ladder.name, fault=f)
            for a in summary["attempts"]:
                tel.flight.record(
                    "ladder", op=ladder.name, action=a["action"],
                    label=a["label"], ok=a["ok"],
                    classification=a["classification"])
            tel.flight.record("remediation", op=ladder.name,
                              outcome=summary["outcome"],
                              winner=summary["winner"])
            if summary["outcome"] != "clean":
                # a walked ladder dumps its evidence to disk, exactly
                # like a serving incident (MCA telemetry.flight_path)
                path = tel.flight_dump_path()
                if path:
                    tel.flight.dump(path)
        reg = self.report.metrics
        lbl = dict(op=ladder.name, prec=self.ip.prec)
        reg.counter("resilience_attempts_total", **lbl).inc(
            len(ladder.attempts))
        reg.counter("resilience_faults_total", **lbl).inc(
            summary["faults_detected"])
        if injection:
            reg.counter("resilience_injected_total", **lbl).inc(
                len(injection["faults"]))
        ip = self.ip
        noteworthy = summary["outcome"] != "clean" \
            or summary["faults_detected"] \
            or (injection and injection["faults"])
        if ip.rank == 0 and (ip.loud >= 3
                             or (ip.loud >= 2 and noteworthy)):
            for line in guard.format_lines(summary):
                print(line)
            sys.stdout.flush()

    def report_refine(self, summary: dict) -> dict:
        """Record one mixed-precision IR solve: the run-report
        ``"refine"`` section (schema v7; ops.refine.summarize),
        refine_* metrics, and the ``#+ refine:`` line at -v>=2."""
        entry = self.report.add_refine(summary)
        reg = self.report.metrics
        lbl = dict(op=summary.get("op", self.name), prec=self.ip.prec)
        reg.gauge("refine_iterations", **lbl).set(
            summary.get("iterations", 0))
        if summary.get("krylov_steps") is not None:
            reg.gauge("refine_krylov_steps", **lbl).set(
                summary["krylov_steps"])
        reg.counter("refine_escalations_total", **lbl).inc(
            1 if summary.get("escalated") else 0)
        hist = summary.get("backward_errors") or []
        if hist:
            reg.gauge("refine_backward_error", **lbl).set(hist[-1])
        if summary.get("quant_guard_max") is not None:
            reg.gauge("quant_guard_max", **lbl).set(
                summary["quant_guard_max"])
        # the autopilot's negative write-back: a consulted rung that
        # escalated stores the next-stronger rung under its cond key
        ap = getattr(self, "_autopilot", None)
        if ap is not None and summary.get("escalated")                 and ap.get("precision"):
            from dplasma_tpu.tuning import autopilot as _ap
            try:
                _ap.record_escalation(
                    ap["op"], ap["n"], ap["dtype"], ap["cond_class"],
                    ap["precision"],
                    cond_estimate=ap.get("cond_estimate"),
                    grid=(self.ip.P, self.ip.Q))
                reg.counter("autopilot_escalations_total",
                            op=ap["op"]).inc()
                if self.telemetry is not None:
                    self.telemetry.flight.record(
                        "autopilot_writeback", op=ap["op"],
                        failed=ap["precision"],
                        cond_class=ap["cond_class"])
            except Exception as exc:
                sys.stderr.write(
                    f"#! autopilot write-back failed: {exc}\n")
        ip = self.ip
        if ip.rank == 0 and ip.loud >= 2:
            tail = f" bwd={hist[-1]:.3e}" if hist else ""
            print("#+ refine[%s]: precision=%s iters=%d %s%s"
                  % (summary.get("op", self.name),
                     summary.get("precision", "?"),
                     summary.get("iterations", 0),
                     ("escalated" if summary.get("escalated") else
                      "converged" if summary.get("converged") else
                      "exhausted"), tail))
            sys.stdout.flush()
        return entry

    def report_check(self, what: str, residual, ok) -> int:
        res = float(np.asarray(residual))
        passed = bool(ok)
        status = "SUCCESS" if passed else "FAILED"
        # every -x verification is tracked on the driver AND recorded
        # in the run-report, so a failed check can never exit 0 even if
        # a body forgets to propagate the return value (run_driver
        # enforces it from self.check_failures)
        self.report.add_check(what, res, passed)
        if not passed:
            self.check_failures += 1
        if self.ip.rank == 0:
            print(f"[{status}] {what} residual = {res:e}")
        return 0 if passed else 1


def run_driver(name: str, body: Callable[[Driver], int],
               argv: Optional[list[str]] = None,
               inspect: Optional[Callable[[Driver], None]] = None) -> int:
    """Entry point shared by every testing_* driver.

    ``name`` is e.g. ``testing_dpotrf``; the precision letter after
    ``testing_`` selects the dtype (the reference's precision-generated
    binaries, ref tests/CMakeLists.txt:16-81). ``inspect``, when
    given, sees the driver after its body returned and before it
    closes: its report, ``inputs`` and ``output`` (chip_smoke.py).
    """
    ip = IParam()
    base = name.rsplit("/", 1)[-1]
    if base.startswith("testing_") and base[8] in PRECISIONS:
        ip.prec = base[8]
    ip = parse_arguments(sys.argv[1:] if argv is None else argv, ip)
    if ip.N <= 0:
        sys.stderr.write("missing matrix dimension (-N)\n" + _USAGE)
        return 2
    import os

    import jax

    from dplasma_tpu.utils.config import use_compile_cache
    use_compile_cache()
    if ip.prec in ("d", "z"):
        jax.config.update("jax_enable_x64", True)
    if ip.inject is None:
        # env tier of the fault-injection plan (like the [SDCZ]<FUNC>
        # priority-limit tier: ambient, CLI wins)
        ip.inject = os.environ.get("DPLASMA_INJECT") or None
    if ip.inject:
        from dplasma_tpu.resilience import inject as _rinject
        try:
            _rinject.parse_plan(ip.inject, seed=ip.seed)
        except ValueError as exc:
            sys.stderr.write(f"bad --inject spec: {exc}\n")
            return 2
    drv = Driver(ip, base)
    try:
        ret = body(drv) or 0
        if inspect is not None:
            inspect(drv)
    finally:
        drv.close()
    if ret == 0 and drv.check_failures:
        # structural guarantee: a failed -x/--check verification exits
        # nonzero even when a driver body drops the check's return value
        ret = 1
    return ret
