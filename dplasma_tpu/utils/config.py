"""Runtime configuration tiers.

The reference exposes four tiers (SURVEY §5.6):

1. CLI vocabulary — lives in ``drivers/common.py``;
2. MCA-style params — ``--mca key value`` passthrough / env overrides
   with a help catalog (ref tests/Testings.cmake:146,
   share/help-dplasma.txt:1-8);
3. environment per-precision priority limits ``[SDCZ]<FUNC>``
   (ref src/dplasmaaux.c:58-90, documented at tests/common.c:162-164);
4. ``dplasma_info_t`` — MPI_Info-style string kv store passed to the
   ``_New_ex`` wrapper variants for per-operation tuning
   (ref src/utils/dplasma_info.c, src/zgemm_wrapper.c:290-334).

All four are plain host-side Python consulted at trace time — tunables
shape the compiled program (loop blocking, lookahead, algorithm choice)
exactly as the reference's values shaped its DAGs.
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional

#: the persistent compile cache's home when nothing outside places it
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own
    setting and is left alone; otherwise the cache is
    ``<repo>/.jax_cache``: a fixed path, so a later process finds
    what an earlier one compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.5)
    return path


class Info:
    """MPI_Info-style string key/value store (dplasma_info_t analog:
    create/set/get/delete/dup/free — ref src/utils/dplasma_info.h).

    Keys are case-insensitive strings; values are strings (callers parse
    numbers), mirroring ``dplasma_info_set(info, "DPLASMA:GEMM:GPU:B",
    "64")`` usage.
    """

    def __init__(self, items: Optional[dict] = None):
        self._kv: dict[str, str] = {}
        if items:
            for k, v in items.items():
                self.set(k, v)

    def set(self, key: str, value) -> None:
        self._kv[key.upper()] = str(value)

    def get(self, key: str, default: Optional[str] = None) -> Optional[str]:
        return self._kv.get(key.upper(), default)

    def get_int(self, key: str, default: int) -> int:
        v = self.get(key)
        if v is None:
            return default
        try:
            return int(v)
        except ValueError:
            return default

    def delete(self, key: str) -> None:
        self._kv.pop(key.upper(), None)

    def dup(self) -> "Info":
        return Info(dict(self._kv))

    def nkeys(self) -> int:
        return len(self._kv)

    def keys(self):
        return list(self._kv)

    def __contains__(self, key: str) -> bool:
        return key.upper() in self._kv

    def __repr__(self):
        return f"Info({self._kv!r})"


# -- tier 3: per-precision priority limits ----------------------------

_PREC_OF_DTYPE = {"float32": "S", "float64": "D",
                  "complex64": "C", "complex128": "Z"}


def priority_limit(func: str, dtype=None, prec: Optional[str] = None
                   ) -> Optional[int]:
    """Environment lookup ``[SDCZ]<FUNC>`` → int priority/lookahead cap
    (dplasma_aux_get_priority_limit semantics, dplasmaaux.c:58-90):
    e.g. ``DPOTRF=4`` caps the d-precision POTRF lookahead depth."""
    if prec is None:
        name = None
        if dtype is not None:
            try:
                import jax.numpy as jnp
                name = jnp.dtype(dtype).name
            except TypeError:
                name = str(dtype)
        prec = _PREC_OF_DTYPE.get(name, "S")
    v = os.environ.get(f"{prec.upper()}{func.upper()}")
    if v is None:
        return None
    try:
        return int(v)
    except ValueError:
        return None


# -- tier 2: MCA-style params with a help catalog ----------------------

_MCA_REGISTRY: dict[str, tuple[str, str]] = {}  # name -> (default, help)
_MCA_OVERRIDES: dict[str, str] = {}


def mca_register(name: str, default, help_text: str) -> None:
    """Register a tunable with default + help text (the analog of
    PaRSEC MCA param registration backed by share/help-dplasma.txt)."""
    _MCA_REGISTRY[name] = (str(default), help_text)


def mca_set(name: str, value) -> None:
    """Programmatic/CLI override (``--mca name value`` passthrough)."""
    _MCA_OVERRIDES[name] = str(value)


def mca_unset(name: str) -> None:
    """Drop a programmatic override (the env/default tiers resume)."""
    _MCA_OVERRIDES.pop(name, None)


def mca_snapshot() -> dict:
    """The ACTIVE override set (explicit overrides only — registered
    defaults are code, not run configuration). This is what the
    run-report's v18 ``"provenance"`` section records, so a ledger
    entry measured under ``--mca panel.qr chain`` is attributable."""
    return dict(sorted(_MCA_OVERRIDES.items()))


def mca_get(name: str, default=None) -> Optional[str]:
    """Resolution order: explicit override > env DPLASMA_MCA_<NAME>
    (dots → underscores) > registered default > ``default``."""
    if name in _MCA_OVERRIDES:
        return _MCA_OVERRIDES[name]
    env = os.environ.get(
        "DPLASMA_MCA_" + name.upper().replace(".", "_").replace(":", "_"))
    if env is not None:
        return env
    if name in _MCA_REGISTRY:
        return _MCA_REGISTRY[name][0]
    return None if default is None else str(default)


def mca_get_int(name: str, default: int) -> int:
    v = mca_get(name)
    if v is None:
        return default
    try:
        return int(v)
    except ValueError:
        return default


def mca_get_float(name: str, default: float) -> float:
    v = mca_get(name)
    if v is None:
        return default
    try:
        return float(v)
    except ValueError:
        return default


# -- scoped override stack ---------------------------------------------
#
# Several layers apply *temporary* MCA overrides around a region of
# work — a driver's --lookahead, the autotuner's per-trial knob
# vectors, the tuning-DB consultation a driver/serving dispatch makes.
# These scopes NEST (a tuner trial runs inside a driver that already
# holds --lookahead), so ad-hoc save/restore pairs per call site are a
# leak waiting to happen: restoring out of order resurrects a stale
# value. The stack below makes LIFO restoration structural — each
# frame records the prior state of exactly the keys it touched, and
# popping out of order is an error, not a silent corruption.
#
# Thread contract: the stack itself is lock-free — it is trace-time
# host code, single-threaded in every driver path. The ONE caller
# that reaches it from concurrent threads is the serving layer's
# dispatch (caller + timer), which must serialize the whole push..pop
# under its _TUNE_LOCK (the r11-i race class: two interleaved scopes
# pop each other into RuntimeErrors). analysis.threadcheck enforces
# that call-site contract statically (CALL_UNDER) and
# analysis.racefuzz replays it (the override_stack probe's LIFO
# integrity invariant).

_UNSET = object()          # "key had no override before this frame"
_OVERRIDE_STACK: list = []  # [_OverrideFrame, ...] — top is last


class _OverrideFrame:
    """One pushed override scope: the applied values plus the exact
    prior state of every touched key (value, or _UNSET)."""

    __slots__ = ("applied", "saved", "label")

    def __init__(self, applied: dict, saved: dict, label: str):
        self.applied = applied
        self.saved = saved
        self.label = label


def push_overrides(kv: dict, label: str = "") -> _OverrideFrame:
    """Apply ``kv`` as MCA overrides and push a restore frame.

    Returns the frame token; hand it back to :func:`pop_overrides` in
    LIFO order. Keys are applied through :func:`mca_set` (stringified);
    a ``None`` value means "unset the override for this key in this
    scope" (the env/default tiers resume underneath)."""
    saved = {}
    applied = {}
    for name, value in kv.items():
        saved[name] = _MCA_OVERRIDES.get(name, _UNSET)
        if value is None:
            mca_unset(name)
            applied[name] = None
        else:
            mca_set(name, value)
            applied[name] = str(value)
    frame = _OverrideFrame(applied, saved, label)
    _OVERRIDE_STACK.append(frame)
    return frame


def pop_overrides(frame: _OverrideFrame) -> None:
    """Restore the prior override state of ``frame``'s keys.

    LIFO is enforced: ``frame`` must be the top of the stack (popping
    an inner scope's parent first would restore stale values over the
    inner scope's save). A non-top pop raises RuntimeError and leaves
    the stack untouched."""
    if not _OVERRIDE_STACK or _OVERRIDE_STACK[-1] is not frame:
        raise RuntimeError(
            "MCA override scopes must pop in LIFO order: "
            f"frame {frame.label or id(frame)} is not the innermost "
            "active scope")
    _OVERRIDE_STACK.pop()
    for name, prev in frame.saved.items():
        if prev is _UNSET:
            _MCA_OVERRIDES.pop(name, None)
        else:
            _MCA_OVERRIDES[name] = prev


@contextlib.contextmanager
def override_scope(kv: dict, label: str = ""):
    """``with override_scope({...}):`` — scoped MCA overrides with
    structural LIFO restore (the context-manager face of
    :func:`push_overrides`/:func:`pop_overrides`)."""
    frame = push_overrides(kv, label=label)
    try:
        yield frame
    finally:
        pop_overrides(frame)


def override_depth() -> int:
    """Number of active override scopes (diagnostics/tests)."""
    return len(_OVERRIDE_STACK)


def mca_help() -> str:
    """Render the registered-param catalog (help-dplasma.txt analog)."""
    lines = []
    for name, (default, text) in sorted(_MCA_REGISTRY.items()):
        lines.append(f"{name} (default: {default})\n    {text}")
    return "\n".join(lines)


# Core registrations (mirroring tunables the reference exposes)
mca_register("device.hbm_fraction", "0.95",
             "Fraction of accelerator memory the streaming GEMM footprint "
             "model may plan for (analog of "
             "device_cuda_memory_use/number_of_blocks).")
mca_register("gemm.lookahead", "2",
             "Pipeline lookahead depth for paced GEMM variants (analog of "
             "dplasma_aux_getGEMMLookahead, dplasmaaux.c:92-111).")
mca_register("runtime.scheduler", "wavefront",
             "Trace-time tile ordering policy (analog of the 8 PaRSEC "
             "scheduler modules, tests/common.c:35-45).")
mca_register("gemm.summa_steps", "2",
             "SUMMA broadcast panels per owner block (pipelined "
             "lookahead; >1 overlaps a step's matmul with the next "
             "panel's broadcast)")
mca_register("lu.pallas_panel", "off",
             "on = factor f32 LU panels with the blocked Pallas "
             "register-tile kernel instead of the vendor custom call")
mca_register("lu.panel_ib", "0",
             "Sub-panel width for a nested in-panel LU sweep "
             "(0 = disabled; the LU custom call's cost is ~linear in "
             "rows x cols, so column-splitting buys nothing on "
             "current hardware — kept for chips where it is not).")
mca_register("lu.panel_chunk", "8192",
             "Row-chunk height for the CALU tournament-pivoting LU "
             "panel; panels taller than this elect pivot candidates "
             "per chunk (XLA's LU custom call overflows scoped VMEM "
             "past 8192 rows x 128 cols on current hardware).")
mca_register("trsm_inv", "auto",
             "Run triangular solves as explicit triangle inverse + "
             "matmul (cuBLAS-style): auto/never (native XLA solve — "
             "measured faster on current hardware), always (force the "
             "inverse form; any dtype). Tuning knob per algorithm.")
mca_register("qr_panel", "auto",
             "Panel QR algorithm for the flat geqrf sweep: auto/lapack "
             "(vendor QR — measured faster on current MXU hardware), "
             "cholqr (CholeskyQR2 + Householder reconstruction, all "
             "matmul-shaped work; requires numerically full-rank "
             "panels). Applies only to ops.qr.geqrf, whose edge tiles "
             "are identity-padded to keep panels full rank.")
mca_register("sweep.lookahead", "1",
             "Lookahead depth of the pipelined factorization sweeps "
             "(potrf/getrf/geqrf, single-chip and cyclic): how many "
             "upcoming panel columns are updated by narrow applies "
             "ahead of the wide trailing update, keeping the "
             "serialized chain panel -> column-update -> panel "
             "(Kurzak/Dongarra tiled-LU/QR lookahead; the reference "
             "gets it from PaRSEC's dataflow scheduler). 0 = the "
             "serialized baseline, bit-identical op order. CLI "
             "--lookahead overrides.")
mca_register("lu.agg_depth", "4",
             "Fused far-flush depth of the EAGER dd LU sweep: the "
             "wide trailing updates of this many consecutive panels "
             "dispatch as ONE executable (same op order; XLA may "
             "contract across the fused steps, so results agree to "
             "rounding, not bit for bit; the traced sweep is already "
             "a single executable and ignores this).")
mca_register("qr.agg_depth", "4",
             "Update aggregation depth of the pipelined QR sweep: "
             "the far trailing matrix is left untouched for this "
             "many consecutive panels and then updated by ONE "
             "compact-WY rank-(d*nb) apply (block-T accumulation), "
             "streaming the far block through HBM once instead of d "
             "times. 1 = per-panel far updates (baseline op order).")
mca_register("dd_gemm", "auto",
             "FP64-equivalent limb GEMM for f64/c128 matmuls: auto "
             "(MXU backends only), always, never. The d/z-precision "
             "CORE_*gemm substrate on hardware without native f64 "
             "matmul units.")
