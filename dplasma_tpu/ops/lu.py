"""LU factorization family — the reference's five variants plus solvers.

Reference surface (SURVEY §2.2 "LU variants (5)"):
- ``dplasma_zgetrf_nopiv``  (zgetrf_nopiv.jdf) — no pivoting;
- ``dplasma_zgetrf_1d``     (zgetrf_1d.jdf + wrapper) — partial
  pivoting over the whole column, 1-D panel distribution, IPIV as a
  tiled vector (zgetrf_1d_wrapper.c:55-97), pivots applied by
  ``dplasma_zlaswp`` (zlaswp.jdf);
- ``dplasma_zgetrf_incpiv`` (zgetrf_incpiv.jdf + ztrsmpl_incpiv.jdf)
  — tile-incremental pivoting: couples [U_kk; A_mk] factored with
  pivoting confined to the couple;
- ``dplasma_zgetrf_ptgpanel`` (zgetrf_ptgpanel.jdf, 1076 lines) —
  distributed parallel panel with partial pivoting;
- ``dplasma_zgetrf_qrf``    (zgetrf_qrf.jdf, 1368 lines) — hybrid
  LU/QR: per-panel choice between an unpivoted LU panel and a QR
  panel by numerical criteria (Higham sum/max/moy, MUMPS, random,
  alternating — zgetrf_qrf_wrapper.c:115-201), recorded in ``lu_tab``.

TPU-native design:
- the multithreaded recursive CPU panel (CORE_zgetrf_rectil) becomes
  one ``lax.linalg.lu`` on the whole (Mp-s)×nb panel — XLA's blocked
  LU is the MXU-friendly panel kernel, and under a mesh GSPMD
  distributes it (which is exactly what ptgpanel hand-built over MPI);
- pivoting is kept as a *global row permutation vector* (semantics
  ``A[perm] = L U``) instead of LAPACK swap-format IPIV: on TPU a
  permutation is one gather, while sequential swaps serialize;
  :func:`laswp` applies it, :func:`perm_to_ipiv`/:func:`ipiv_to_perm`
  convert to/from the reference's format;
- the qrf hybrid's data-dependent panel choice is a branchless
  ``lax.cond`` over both panel kernels (both traced once), per
  SURVEY §7 "hard parts" #3; data-independent criteria (random,
  alternating) resolve at trace time instead.
"""
from __future__ import annotations

import threading

import jax.numpy as jnp
from jax import lax

from dplasma_tpu import utils
from dplasma_tpu.descriptors import TileMatrix
from dplasma_tpu.kernels import blas as k
from dplasma_tpu.kernels import householder as hh
from dplasma_tpu.kernels import quant as _quant
from dplasma_tpu.ops import blas3
from dplasma_tpu.ops._sweep import assemble_sweep
from dplasma_tpu.parallel import mesh as pmesh


# -- pivot bookkeeping -------------------------------------------------

def perm_to_ipiv(perm):
    """Convert a permutation vector (A[perm] = LU) to LAPACK-style
    sequential swap indices (0-based): swapping rows i and ipiv[i] for
    i = 0..n-1 reproduces the permutation."""
    import numpy as np
    target = np.asarray(perm)
    n = target.shape[0]
    cur = np.arange(n)            # cur[i] = original row now at slot i
    where = np.arange(n)          # where[r] = slot currently holding r
    ipiv = np.zeros(n, dtype=np.int32)
    for i in range(n):
        j = int(where[target[i]])
        ipiv[i] = j
        ri, rj = cur[i], cur[j]
        cur[i], cur[j] = rj, ri
        where[ri], where[rj] = j, i
    return jnp.asarray(ipiv)


def ipiv_to_perm(ipiv):
    """Inverse of :func:`perm_to_ipiv`."""
    import numpy as np
    iv = np.asarray(ipiv)
    n = iv.shape[0]
    perm = np.arange(n)
    for i in range(n):
        j = int(iv[i])
        if j != i:
            perm[i], perm[j] = perm[j], perm[i]
    return jnp.asarray(perm)


def laswp(A: TileMatrix, perm, inverse: bool = False) -> TileMatrix:
    """Apply a global row permutation (dplasma_zlaswp analog): one
    gather instead of the reference's sequential row swaps."""
    from dplasma_tpu.observability import phases
    with phases.span("laswp", timed=False):
        if inverse:
            inv = jnp.zeros_like(perm).at[perm].set(
                jnp.arange(perm.shape[0], dtype=perm.dtype))
            perm = inv
        return A.like(A.data[perm, :])


# -- no-pivoting LU ----------------------------------------------------

def _lu_apply_block(pan, blk, bw: int, perm=None, update_dtype=None):
    """Apply one factored LU panel to a column block: optional pivot
    gather, U solve of the top bw rows, rank-bw Schur update below.
    The shared narrow/wide update of the pipelined sweep; the Schur
    product routes through the block-scaled int8 GEMM under the
    ir.precision=int8 rung (kernels.quant.update_scope), or takes
    ``update_dtype`` operands (:func:`~dplasma_tpu.kernels.blas.dot_in`)
    — the U solve stays f32, it writes factor output."""
    if perm is not None:
        blk = blk[perm]
    u = k.trsm(pan[:bw], blk[:bw], side="L", lower=True, unit=True)
    below = blk[bw:]
    if below.shape[0]:
        below = below - (_quant.update_dot(pan[bw:], u)
                         if update_dtype is None
                         else k.dot_in(pan[bw:], u, update_dtype))
    return u, below


def getrf_nopiv(A: TileMatrix, lookahead=None,
                update_dtype=None) -> TileMatrix:
    """Blocked right-looking LU without pivoting
    (dplasma_zgetrf_nopiv). Returns packed L\\U (unit L implicit).
    ``update_dtype`` (``jnp.bfloat16``) rounds the trailing updates'
    operands to it, accumulating in ``A``'s dtype.

    Under an active mesh with a nontrivial process grid the factor is
    the block-cyclic sweep without pivoting
    (:func:`dplasma_tpu.parallel.cyclic.getrf_cyclic`); the returned
    LU then carries the cyclic factor with the identity ``perm``
    (``LU.cyclic_factor``, as :func:`getrf_ptgpanel`'s does), so that
    :func:`getrs` with :func:`nopiv_perm` solves on the slabs.

    Lookahead-pipelined shrinking-window sweep
    (:func:`dplasma_tpu.ops._sweep.pipelined_sweep`): the next panel's
    block-column is updated first by a narrow solve+rank-nb product,
    so the serialized chain is panel -> column-update -> panel while
    the full-width MXU Schur update of the remainder stays dataflow-
    independent of the next panel. ``lookahead=0`` (or MCA
    ``sweep.lookahead 0``) is the serialized baseline, bit-identical
    op order."""
    from dplasma_tpu.kernels import panels as _panels
    from dplasma_tpu.observability import phases
    from dplasma_tpu.ops import _sweep
    assert A.desc.mb == A.desc.nb, "getrf needs square tiles"
    if _grid_of(A) is not None:
        with phases.span("getrf", timed=False):
            LU, _ = _getrf_grid(A, pivot=False, update_dtype=update_dtype)
            return LU
    la, _ = _sweep.sweep_params(lookahead)
    nb = A.desc.nb
    KT = A.desc.KT
    NT = A.desc.NT
    rest = A.pad_diag().data
    # panel engine: rec factors the whole (m, nb) slab as one
    # blocked-recursive fused panel; chain keeps the diagonal
    # getrf_nopiv + trsm pair (bit-identical pre-engine route)
    pkind = _panels.panel_kernel("nopiv")

    def panel(col):
        if pkind == "rec":
            return (_panels.lu_panel_rec_nopiv(col),) * 2
        d = k.getrf_nopiv(col[:nb])
        if col.shape[0] > nb:
            pan = jnp.concatenate(
                [d, k.trsm(d, col[nb:], side="R", lower=False)], axis=0)
        else:
            pan = d
        return pan, pan

    packs, urows = _sweep.pipelined_sweep(
        rest, nb, KT, NT, panel,
        lambda pan, blk: _lu_apply_block(pan, blk, nb,
                                         update_dtype=update_dtype),
        lookahead=la)
    full = assemble_sweep(packs, urows, KT, NT, nb)
    return TileMatrix(pmesh.constrain2d(full), A.desc)


def nopiv_perm(LU: TileMatrix):
    """The row order of a :func:`getrf_nopiv` factor, the identity: the
    very ``perm`` its cyclic factor carries (so that :func:`getrs` solves
    on the slabs), else a new one over the padded rows."""
    twin = getattr(LU, "cyclic_factor", None)
    if twin is not None:
        return twin[1]
    return jnp.arange(LU.desc.MT * LU.desc.mb, dtype=jnp.int32)


# -- partial pivoting (1d / ptgpanel) ----------------------------------

# VMEM row limit for XLA's LuDecompositionBlock custom call (full panel
# height x 128-column blocks must fit scoped VMEM; 16384x128 f32
# overflows the 16 MB budget on current hardware).
_LU_CHUNK = 8192
# Sub-panel width for the nested in-panel sweep (0 = disabled). The LU
# custom call's cost is ~linear in rows x cols, so column-splitting the
# panel saves no slow-call time (measured: a 128-wide nested sweep was
# net slower from its own gather/update overheads); kept as an MCA
# tuning knob for hardware with superlinear panel cost.
_LU_IB = 0


def _base_lu(panel, chunk: int | None = None, kind: str | None = None):
    """Pivoted LU of one narrow tall sub-panel: direct XLA LU when the
    panel fits the custom call's VMEM row budget, else CALU tournament
    pivoting (Grigori/Demmel CALU — also the shape of the reference's
    distributed panel, src/zgetrf_ptgpanel.jdf): row chunks elect ib
    candidate pivot rows each via independent chunk LUs (one batched
    call), a second-level LU of the stacked candidates picks the
    winners, and the remaining rows are solved against the winners' U.
    Returns (packed m x ib L\\U with unit L, perm) with
    ``panel[perm] = L U``.

    Singular/near-singular panels are undefined behavior (as with
    getrf_nopiv): when a pivot column is zero across every real row,
    zero pad rows from the last chunk can be elected and silently
    dropped, so the factorization degrades to a singular U / NaNs
    rather than a diagnostic (ADVICE r2; the reference's nopiv path
    has the same contract)."""
    m, ib = panel.shape
    from dplasma_tpu.utils import config as _cfg
    from dplasma_tpu.kernels import panels as _panels
    # panel engine (kernels.panels, MCA panel.kernel): rec replaces
    # the vendor custom call + CALU chunking with the blocked-
    # recursive slab; pallas selects the fused VMEM kernel where the
    # shape fits, degrading to rec. chain falls through to the
    # pre-engine body below, bit-identical.
    if kind is None:
        kind = _panels.panel_kernel("lu")
    if kind == "pallas":
        from dplasma_tpu.kernels import pallas_lu
        if pallas_lu.eligible(panel):
            return pallas_lu.lu_panel(panel)
        kind = "rec"
    if kind == "rec":
        return _panels.lu_panel_rec(panel)
    if (_cfg.mca_get("lu.pallas_panel") or "off").lower() == "on":
        # blocked register-tile Pallas panel (kernels/pallas_lu.py;
        # VMEM-resident, JB-wide column blocks, rank-JB MXU updates) —
        # opt-in while the vendor custom call holds the measured edge
        from dplasma_tpu.kernels import pallas_lu
        if pallas_lu.eligible(panel):
            return pallas_lu.lu_panel(panel)
    if chunk is None:
        chunk = _cfg.mca_get_int("lu.panel_chunk", _LU_CHUNK)
    # A chunk narrower than the panel cannot elect ib candidates, and a
    # chunk in [ib, 2*ib) leaves C*ib >= m so the candidate recursion
    # never shrinks (ADVICE r2): clamp to 2*ib so every level at least
    # halves the row count.
    chunk = max(chunk, 2 * ib)
    if m <= chunk:
        lu, _, perm = lax.linalg.lu(panel)
        return lu, perm
    C = -(-m // chunk)
    pad = C * chunk - m
    ap = jnp.pad(panel, ((0, pad), (0, 0)))
    chunks = ap.reshape(C, chunk, ib)
    # lax.map, not vmap: the batched LU custom call co-resides every
    # batch member's panel in scoped VMEM and overflows for C*chunk
    # beyond ~16k rows; sequential chunk LUs keep the footprint flat.
    _, _, cperm = lax.map(lambda c: lax.linalg.lu(c), chunks)
    cand_pos = cperm[:, :ib]                                # (C, ib)
    cands = jnp.take_along_axis(chunks, cand_pos[:, :, None], axis=1)
    cand_glob = cand_pos + (jnp.arange(C) * chunk)[:, None]
    # recurse for the second level: C*ib candidate rows can themselves
    # exceed the custom call's VMEM row budget for very tall panels
    # (kind is pinned: a caller's chain pin must not re-resolve MCA)
    lu2, perm2 = _base_lu(cands.reshape(C * ib, ib), chunk, kind)
    win_rows = cand_glob.reshape(-1)[perm2[:ib]]            # (ib,)
    # window permutation: winners first in elimination order, the rest
    # below in stable original order
    rank = jnp.zeros((m + pad,), jnp.int32).at[win_rows].set(
        jnp.arange(ib, dtype=jnp.int32))
    is_w = jnp.zeros((m + pad,), bool).at[win_rows].set(True)
    key = jnp.where(is_w, rank,
                    ib + jnp.arange(m + pad, dtype=jnp.int32))[:m]
    perm = jnp.argsort(key)
    top = lu2[:ib]                     # packed L11\U11 of winner rows
    rest = panel[perm[ib:]]
    l21 = k.trsm(jnp.triu(top), rest, side="R", lower=False)
    return jnp.concatenate([top, l21], axis=0), perm


def _lu_finish(packs, urows, step_ids, ids, Mp, KT, NT, bw):
    """Deferred-pivot stitching shared by the traced and eager sweeps:
    final row order, per-step reorder closure, assembly. The pivot
    bookkeeping is attributed to the ``assemble`` phase (sibling of
    the span inside :func:`~dplasma_tpu.ops._sweep.assemble_sweep`)."""
    from dplasma_tpu.observability import phases
    with phases.span("assemble") as _f:
        final_ids = _f(jnp.concatenate(
            [si[:bw] for si in step_ids] + [ids]))

    def reorder(kk):
        sids = step_ids[kk]
        wpos = jnp.zeros((Mp,), jnp.int32).at[sids].set(
            jnp.arange(sids.shape[0], dtype=jnp.int32))
        return wpos[final_ids[(kk + 1) * bw:]]

    full = assemble_sweep(packs, urows, KT, NT, bw, reorder=reorder)
    return full, final_ids


def _lu_sweep(X, bw: int, panel_fn, lookahead=None,
              jit_steps: bool = False):
    """Generic pivoted shrinking-window LU sweep at block width ``bw``:
    right-looking, with *deferred* pivot bookkeeping — each block's
    permutation is applied to the shrinking trailing window only (one
    gather), never to already-factored left columns; the packed factor
    is stitched at the end from traced row ids. Returns
    (packed L\\U, perm) with ``X[perm] = L U``. Used at two levels:
    the nb-wide matrix sweep and the ib-wide in-panel sweep.

    Lookahead-pipelined via :func:`~dplasma_tpu.ops._sweep.
    pipelined_sweep`: the next panel's column is permuted+updated
    first (narrow), the wide Schur remainder stays off the panel
    chain. ``jit_steps=True`` routes the panel and block updates
    through per-shape jitted executables (the eager dd route, which
    keeps each compile to one step's shape); there the far flushes of
    MCA ``lu.agg_depth`` consecutive panels fuse into one executable
    (same op order, unlike QR's reassociating compact-WY aggregation,
    so the recorded DAG keeps per-step far tasks; XLA may contract
    across the fused steps, so results agree to rounding)."""
    from dplasma_tpu.ops import _sweep
    from dplasma_tpu.utils import config as _cfg
    # the jitted route dispatches through module-level executables
    # that hardcode _panel_lu (a lambda panel_fn would retrace per
    # call); refuse a mismatched panel_fn rather than silently
    # factoring with the wrong kernel
    assert not jit_steps or panel_fn is _panel_lu, \
        "jit_steps supports only the _panel_lu panel kernel"
    la, _ = _sweep.sweep_params(lookahead)
    agg = max(_cfg.mca_get_int("lu.agg_depth", 1), 1) if jit_steps \
        else 1
    # resolve the panel-engine kernel ONCE and thread it statically
    # into the jitted panel executable (an MCA flip between calls
    # must re-trace, not replay a stale cached kernel choice)
    if jit_steps:
        from dplasma_tpu.kernels import panels as _panels
        pkind = _panels.panel_kernel("lu")
    Mp, Np = X.shape
    KT = min(Mp, Np) // bw
    NT = -(-Np // bw)
    ids_cell = [jnp.arange(Mp)]
    step_ids = []

    def panel(col):
        pan, perm = _jit_lu_panel(col, pkind) if jit_steps \
            else panel_fn(col)
        idsp = ids_cell[0][perm]
        step_ids.append(idsp)
        ids_cell[0] = idsp[bw:]
        return pan, (pan, perm)

    def apply_block(st, blk):
        if jit_steps:
            return _jit_lu_apply(st[0], st[1], blk)
        return _lu_apply_block(st[0], blk, bw, perm=st[1])

    def agg_apply(sts, far):
        return _jit_lu_flush(far, *[x for st in sts for x in st])

    packs, urows = _sweep.pipelined_sweep(
        X, bw, KT, NT, panel, apply_block, lookahead=la,
        agg_depth=agg, agg_apply=agg_apply if agg > 1 else None)
    return _lu_finish(packs, urows, step_ids, ids_cell[0], Mp, KT, NT,
                      bw)


def _panel_lu_dd(panel, ib: int | None = None,
                 kind: str | None = None):
    """d-precision panel LU: seed with the f32 pivoted panel machinery
    (including its CALU/VMEM fallbacks), then refine L and U to
    f64-equivalent accuracy for the FIXED permutation with limb-exact
    residuals (kernels.dd.lu_ir) — the TPU replacement for the
    reference's d-precision CORE_zgetrf_rectil."""
    from dplasma_tpu.kernels import dd as _dd
    nb = panel.shape[1]
    # Power-of-two COLUMN prescale before the f32 cast: f64 magnitudes
    # outside f32 range would otherwise overflow/flush and poison the
    # seed (review r3). Column scaling leaves the partial-pivot choice
    # and L itself invariant (each column's entry ratios are unchanged,
    # |L| <= 1 as with unscaled pivoting); only U unscales, exactly:
    # panel*D = L*(U*D)  =>  U = U_scaled / d.
    m_ = jnp.max(jnp.abs(panel), axis=0, keepdims=True)
    d = 4.0 / _dd._pow2_scale_bits(m_)   # 2^-floor(log2 colmax)
    pan32, perm = _panel_lu((panel * d).astype(jnp.float32), ib, kind)
    # refine in the scaled coordinates (everything O(growth) there, so
    # the IR's own f32 seeds stay in range), unscale U exactly after
    L = k.tri(pan32.astype(panel.dtype), lower=True, unit=True)
    Us = jnp.triu(pan32[:nb]).astype(panel.dtype)
    L, Us = _dd.lu_ir(panel[perm] * d, L, Us)
    U = Us / d
    packed = jnp.concatenate(
        [jnp.triu(U) + jnp.tril(L[:nb], -1)] +
        ([L[nb:]] if L.shape[0] > nb else []), axis=0)
    return packed, perm


def _panel_lu(panel, ib: int | None = None, kind: str | None = None):
    """Pivoted LU of one nb-wide tall panel: a nested ib-wide
    shrinking-window sweep (full-height pivot search per sub-panel —
    LAPACK-blocked-getrf pivot quality) whose base case is
    :func:`_base_lu`. Keeps the slow LU custom call to O(M*ib*nb) flops
    and turns the rest of the panel into matmuls. f64 panels on the
    dd route get an f32 seed + limb-IR (:func:`_panel_lu_dd`).
    ``kind`` pins the panel-engine kernel (None = live MCA
    ``panel.kernel`` — jitted callers thread it statically so a
    config flip never hits a stale cache)."""
    if panel.dtype == jnp.float64 and k._dd_active(panel.dtype):
        return _panel_lu_dd(panel, ib, kind)
    m, nb = panel.shape
    if ib is None:
        from dplasma_tpu.utils import config as _cfg
        ib = _cfg.mca_get_int("lu.panel_ib", _LU_IB)
    if ib <= 0 or nb <= ib or nb % ib or m % ib:
        return _base_lu(panel, kind=kind)
    # the in-panel sweep stays serialized (lookahead=0): inside the
    # latency-bound panel a column split only adds narrow ops — the
    # matrix-level sweep owns the pipeline. The kind pin threads into
    # the sub-panel base cases (a chain pin must stay chain).
    return _lu_sweep(panel, ib,
                     lambda sub: _base_lu(sub, kind=kind),
                     lookahead=0)


# -- shape-cached dd LU sweep callbacks (eager) ------------------------
# Eager callers drive the pipelined sweep engine over per-callback
# executables, compiled per shrinking-window shape and persistent-
# cached (one step's shape per compile, not the whole unrolled sweep).
# Panels factor at the TRUE window height (r5: ~half the
# panel time of the fixed-height form factored zero pad rows).
# Zero-padded panel rows remain PIVOT-SAFE: partial pivoting never
# selects a zero row over a nonzero one, and an unselected zero row
# stays zero and in place — so perm[:m] permutes only real rows.

import functools as _functools

import jax as _jax


@_functools.partial(_jax.jit, static_argnums=(1,))
def _jit_lu_panel(col, kind: str | None = None):
    return _panel_lu(col, kind=kind)


@_jax.jit
def _jit_lu_apply(pan, perm, blk):
    return _lu_apply_block(pan, blk, pan.shape[1], perm=perm)


@_jax.jit
def _jit_lu_flush(far, *pan_perm):
    """Fused far flush: the wide updates of several consecutive panels
    in ONE executable — IDENTICAL op order to the per-step applies
    (dispatch fusion, not reassociation: fewer executables per sweep).
    ``pan_perm`` is pan0, perm0, pan1, perm1, ..."""
    tops = []
    for i in range(0, len(pan_perm), 2):
        pan = pan_perm[i]
        top, far = _lu_apply_block(pan, far, pan.shape[1],
                                   perm=pan_perm[i + 1])
        tops.append(top)
    return tops, far


def getrf_1d(A: TileMatrix):
    """Partial-pivoting blocked LU (dplasma_zgetrf_1d). Returns
    (packed L\\U, perm) with semantics ``A[perm] = L U``.

    Two nested shrinking-window right-looking sweeps (:func:`_lu_sweep`
    over nb-wide panels; each panel an ib-wide inner sweep) with
    deferred pivot bookkeeping — the reference instead chains zlaswp
    row swaps through finished tiles (zgetrf_1d_wrapper.c:55-97) and
    hand-distributes the panel (CORE_zgetrf_rectil / the ptgpanel JDF).
    Eager f64 callers on the dd route ride shape-cached executables
    (one step's shape per compile, not the whole unrolled sweep)."""
    assert A.desc.mb == A.desc.nb, "getrf needs square tiles"
    X = A.pad_diag().data
    use_dd = (A.dtype == jnp.float64 and k._dd_active(A.dtype))
    # eager only where the traced monolith cannot compile (> 8 panels:
    # N > 4096 at nb=512); below that the traced executable is ~3x
    # faster than the per-step dispatch chain (427 vs 136 GF/s at
    # 4096, measured r4)
    if (use_dd and utils.is_concrete(X)
            and min(X.shape) // A.desc.nb > 8):
        full, final_ids = _lu_sweep(X, A.desc.nb, _panel_lu,
                                    jit_steps=True)
    else:
        full, final_ids = _lu_sweep(X, A.desc.nb, _panel_lu)
    return TileMatrix(pmesh.constrain2d(full), A.desc), final_ids


def getrf_rec(A: TileMatrix, hnb: int = 0):
    """Recursive-panel LU (the -z/--HNB variant; ref the reference's
    recursive CORE_zgetrf_rectil panels + -z drivers): each nb-wide
    panel factors as an hnb-wide nested shrinking-window sweep —
    the machinery :func:`_panel_lu` already owns via its ``ib``
    parameter, here surfaced with the same driver semantics as
    ops.potrf.potrf_rec / ops.qr.geqrf_rec."""
    if hnb <= 0 or hnb >= A.desc.nb:
        return getrf_1d(A)
    assert A.desc.mb == A.desc.nb, "getrf needs square tiles"
    full, final_ids = _lu_sweep(
        A.pad_diag().data, A.desc.nb,
        lambda panel: _panel_lu(panel, ib=hnb))
    return TileMatrix(pmesh.constrain2d(full), A.desc), final_ids


def getrf_ptgpanel(A: TileMatrix):
    """Distributed-parallel-panel LU (dplasma_zgetrf_ptgpanel,
    src/zgetrf_ptgpanel.jdf). Under an active mesh with a nontrivial
    process grid this runs the realized distributed panel
    (:func:`dplasma_tpu.parallel.cyclic.getrf_cyclic`): per-row-rank
    candidate election, an ICI all_gather playoff, masked-psum pivot
    row exchange — the shard_map re-design of the reference's 1,076
    JDF lines. Single-process grids fall back to :func:`getrf_1d`
    (same (LU, perm) contract either way). The layout changes around
    the distributed factorization (to cyclic storage and back, and the
    row gather by ``perm``) carry the ``redistribute`` scope. On the
    distributed branch the returned LU also carries the cyclic factor
    and ``perm`` (``LU.cyclic_factor``), which :func:`getrs` solves on;
    a program that never reads the dense factor then drops its
    conversion back."""
    from dplasma_tpu.observability import phases
    with phases.span("getrf", timed=False):
        if A.desc.mb == A.desc.nb and _grid_of(A) is not None:
            return _getrf_grid(A)
        return getrf_1d(A)


def _grid_of(A: TileMatrix):
    """The Dist of the active mesh's nontrivial process grid (keeping
    ``A``'s kp/kq where its grid is the mesh's), or None."""
    m = pmesh.active()
    if m is None:
        return None
    P = m.shape[pmesh.ROW_AXIS]
    Q = m.shape[pmesh.COL_AXIS]
    if P * Q == 1:
        return None
    from dplasma_tpu.descriptors import Dist
    d = A.desc.dist
    return d if (d.P, d.Q) == (P, Q) else Dist(P=P, Q=Q)


def _getrf_grid(A: TileMatrix, pivot: bool = True, update_dtype=None):
    """The block-cyclic LU of ``A`` on the active grid, as ``(LU,
    perm)`` in natural order with the cyclic factor attached."""
    from dplasma_tpu.observability import phases
    from dplasma_tpu.parallel import cyclic
    with phases.span("redistribute", timed=False):
        C = cyclic.CyclicMatrix.from_tile(A, _grid_of(A))
    F, perm = cyclic.getrf_cyclic(C, pivot=pivot, update_dtype=update_dtype)
    with phases.span("redistribute", timed=False):
        full = F.to_tile().data
        if pivot:
            full = full[perm]
        full = pmesh.constrain2d(full)
    LU = TileMatrix(full, A.desc)
    # a plain attribute, not a pytree field: it does not cross a jit
    # boundary, so only a getrs traced with the factor (and with this
    # perm) solves on the slabs
    LU.cyclic_factor = (F, perm)
    return LU, perm


def trsmpl_ptgpanel(LU: TileMatrix, perm, B: TileMatrix) -> TileMatrix:
    """Apply pivots + L^{-1} to B (dplasma_ztrsmpl_ptgpanel)."""
    Bp = laswp(B.zero_pad(), perm)
    return blas3.trsm(1.0, LU, Bp, side="L", uplo="L", trans="N", diag="U")


#: times :func:`getrs` was traced (or run eagerly) on each route:
#: ``cyclic`` on the block-cyclic factor, ``dense`` on the natural-order
#: one. The drivers export it as ``lu_getrs_route_total{route}``.
GETRS_ROUTES = {"cyclic": 0, "dense": 0}
_GETRS_ROUTES_LOCK = threading.Lock()


def _count_route(route: str) -> None:
    with _GETRS_ROUTES_LOCK:
        GETRS_ROUTES[route] += 1


def _cyclic_factor(trans: str, LU: TileMatrix, perm, B: TileMatrix):
    """The block-cyclic factor to solve on, or None for the dense route:
    only for trans N, a factor :func:`getrf_ptgpanel` returned in this
    trace together with this very ``perm``, a right-hand side of its
    dtype and row tiling, and an active mesh of the factor's grid."""
    twin = getattr(LU, "cyclic_factor", None)
    if trans != "N" or twin is None or twin[1] is not perm:
        return None
    F = twin[0]
    m = pmesh.active()
    d = F.desc.dist
    if (m is None or B.dtype != F.dtype
            or B.data.shape[0] != F.desc.MT * F.desc.mb
            or (m.shape[pmesh.ROW_AXIS], m.shape[pmesh.COL_AXIS])
            != (d.P, d.Q)):
        return None
    return F


def getrs(trans: str, LU: TileMatrix, perm, B: TileMatrix) -> TileMatrix:
    """Solve op(A) X = B from a pivoted factorization
    (dplasma_zgetrs). A factor fresh from :func:`getrf_ptgpanel`'s
    distributed branch is solved on its block-cyclic slabs
    (:func:`~dplasma_tpu.parallel.cyclic.getrs_cyclic`, trans N, the
    right-hand sides replicated); every other factor on the dense one."""
    from dplasma_tpu.observability import phases
    trans = trans.upper()
    with phases.span("solve", timed=False):
        F = _cyclic_factor(trans, LU, perm, B)
        _count_route("dense" if F is None else "cyclic")
        if F is not None:
            from dplasma_tpu.parallel import cyclic
            Bz = B.zero_pad()
            return Bz.like(cyclic.getrs_cyclic(F, perm, Bz.data)).zero_pad()
        if trans == "N":
            Y = trsmpl_ptgpanel(LU, perm, B)
            return blas3.trsm(1.0, LU, Y, side="L", uplo="U", trans="N")
        # op(A) = A^T/A^H: U^x L^x P x = b
        Y = blas3.trsm(1.0, LU, B, side="L", uplo="U", trans=trans)
        Z = blas3.trsm(1.0, LU, Y, side="L", uplo="L", trans=trans,
                       diag="U")
        return laswp(Z, perm, inverse=True)


def gesv_1d(A: TileMatrix, B: TileMatrix):
    """Factor + solve (dplasma_zgesv_1d). Returns (LU, perm, X)."""
    LU, perm = getrf_1d(A)
    return LU, perm, getrs("N", LU, perm, B)


# -- incremental pivoting ----------------------------------------------

def getrf_incpiv(A: TileMatrix):
    """Tile-incremental-pivoting LU (dplasma_zgetrf_incpiv):
    pivoting is confined to [U_kk; A_mk] couples, trading numerical
    strength for tile-local data movement (the reference's original
    out-of-cache motivation; on TPU it demonstrates the couple-kernel
    schedule — partial pivoting via getrf_1d is the stronger default).

    Returns (factored, Lc, piv): ``factored`` holds U above the
    diagonal and couple L21 blocks below; ``Lc`` holds the couples'
    L11 blocks at tile (m, k) (the reference's separate L descriptor,
    tests/testing_zgetrf_incpiv.c); ``piv[k, m]`` is the couple's
    2nb-row permutation (row k of piv holds the diagonal tile's).
    """
    assert A.desc.mb == A.desc.nb
    nb = A.desc.nb
    MT, KT = A.desc.MT, A.desc.KT
    X = A.pad_diag().data
    Np = A.desc.Np
    Lc = jnp.zeros_like(X)
    piv = jnp.tile(jnp.arange(2 * nb, dtype=jnp.int32), (KT, MT, 1))

    def rows(m):
        return slice(m * nb, (m + 1) * nb)

    for kk in range(KT):
        s, e = kk * nb, (kk + 1) * nb
        lu, _, perm = lax.linalg.lu(X[s:e, s:e])
        X = X.at[s:e, s:e].set(lu)
        piv = piv.at[kk, kk, :nb].set(perm.astype(jnp.int32))
        if e < Np:
            rk = X[s:e, e:][perm, :]
            X = X.at[s:e, e:].set(
                k.trsm(lu, rk, side="L", lower=True, unit=True))
        for m in range(kk + 1, MT):
            stack = jnp.concatenate(
                [jnp.triu(X[s:e, s:e]), X[rows(m), s:e]], axis=0)
            lu2, _, perm2 = lax.linalg.lu(stack)
            u_new = jnp.triu(lu2[:nb, :])
            l11c = jnp.tril(lu2[:nb, :], -1)
            l21c = lu2[nb:, :]
            X = X.at[s:e, s:e].set(jnp.tril(X[s:e, s:e], -1) + u_new)
            X = X.at[rows(m), s:e].set(l21c)
            Lc = Lc.at[rows(m), s:e].set(l11c)
            piv = piv.at[kk, m, :].set(perm2.astype(jnp.int32))
            if e < Np:
                top, bot = _ssssm(l11c, l21c, perm2,
                                  X[s:e, e:], X[rows(m), e:])
                X = X.at[s:e, e:].set(top)
                X = X.at[rows(m), e:].set(bot)
        X = pmesh.constrain2d(X)
    return TileMatrix(X, A.desc), TileMatrix(Lc, A.desc), piv


def _ssssm(l11c, l21c, perm, c_top, c_bot):
    """Apply a couple's L^{-1} P to the vertical pair (CORE_zssssm):
    y1 = L11c^{-1} (P c)[:nb]; y2 = (P c)[nb:] - L21c y1."""
    nb = l11c.shape[0]
    cstack = jnp.concatenate([c_top, c_bot], axis=0)[perm, :]
    y1 = k.trsm(l11c, cstack[:nb, :], side="L", lower=True, unit=True)
    y2 = cstack[nb:, :] - k.dot(l21c, y1)
    return y1, y2


def trsmpl_incpiv(LU: TileMatrix, Lc: TileMatrix, piv,
                  B: TileMatrix) -> TileMatrix:
    """Replay the incpiv panel transformations on B
    (dplasma_ztrsmpl_incpiv)."""
    nb = LU.desc.nb
    MT, KT = LU.desc.MT, LU.desc.KT
    Y = B.zero_pad().data

    def rows(m):
        return slice(m * nb, (m + 1) * nb)

    for kk in range(KT):
        s, e = kk * nb, (kk + 1) * nb
        perm = piv[kk, kk, :nb]
        d = LU.data[s:e, s:e]
        Y = Y.at[s:e, :].set(
            k.trsm(d, Y[s:e, :][perm, :], side="L", lower=True, unit=True))
        for m in range(kk + 1, MT):
            top, bot = _ssssm(Lc.data[rows(m), s:e],
                              LU.data[rows(m), s:e],
                              piv[kk, m, :], Y[s:e, :], Y[rows(m), :])
            Y = Y.at[s:e, :].set(top)
            Y = Y.at[rows(m), :].set(bot)
        Y = pmesh.constrain2d(Y)
    return TileMatrix(Y, B.desc)


def getrs_incpiv(LU: TileMatrix, Lc: TileMatrix, piv,
                 B: TileMatrix) -> TileMatrix:
    """Solve from an incpiv factorization (dplasma_zgetrs_incpiv)."""
    Y = trsmpl_incpiv(LU, Lc, piv, B)
    return blas3.trsm(1.0, LU, Y, side="L", uplo="U", trans="N")


def gesv_incpiv(A: TileMatrix, B: TileMatrix):
    """dplasma_zgesv_incpiv. Returns (LU, Lc, piv, X)."""
    LU, Lc, piv = getrf_incpiv(A)
    return LU, Lc, piv, getrs_incpiv(LU, Lc, piv, B)


# -- hybrid LU/QR ------------------------------------------------------

CRITERIA = ("higham_sum", "higham_max", "higham_moy", "mumps",
            "random", "alternating")


def _panel_criterion(criterion: str, panel, nb: int, alpha: float):
    """Data-dependent LU-acceptability test for one panel (the
    reference's Higham/MUMPS criteria, zgetrf_qrf_wrapper.c:115-201,
    src/include/dplasma/lu_qr.h). Returns a traced bool: True → the
    unpivoted LU panel is numerically acceptable."""
    d = jnp.abs(jnp.diagonal(panel[:nb, :]))
    col = jnp.abs(panel)
    if criterion == "higham_sum":
        growth = jnp.sum(col, axis=0)
    elif criterion == "higham_max":
        growth = jnp.max(col, axis=0)
    elif criterion == "higham_moy":
        growth = jnp.mean(col, axis=0) * panel.shape[0]
    elif criterion == "mumps":
        # diagonal dominance within the diagonal block
        off = jnp.sum(jnp.abs(panel[:nb, :]), axis=0) - d
        return jnp.all(d >= alpha * off)
    else:
        raise ValueError(criterion)
    safe = jnp.where(d > 0, d, jnp.finfo(col.dtype).tiny)
    return jnp.all(growth <= alpha * safe)


def getrf_qrf(A: TileMatrix, criterion: str = "higham_sum",
              alpha: float | None = None, seed: int = 3872):
    """Hybrid LU/QR factorization (dplasma_zgetrf_qrf): per panel,
    factor with an unpivoted LU panel when the criterion accepts it,
    else with a QR panel (pivot-free stability via orthogonality).

    Returns (factored, T, lu_tab): lu_tab[k] ∈ {1 (LU), 0 (QR)} — the
    reference's ``lu_tab``; T holds compact-WY triangles for QR
    panels. Solve with :func:`trsmpl_qrf` + upper trsm (the final
    factor is upper triangular either way).
    """
    assert A.desc.mb == A.desc.nb
    assert criterion in CRITERIA, criterion
    nb = A.desc.nb
    KT = A.desc.KT
    X = A.pad_diag().data
    Mp, Np = X.shape
    if alpha is None:
        # Higham-style criteria accept LU when growth <= alpha*|diag|
        # (larger alpha = more LU); mumps accepts when the diagonal
        # dominates alpha*|offdiag| (larger alpha = less LU) — the
        # defaults reflect the opposite polarity.
        alpha = 0.5 if criterion == "mumps" else float(Mp)
    Tm = jnp.zeros_like(X)
    lu_tab = jnp.zeros((KT,), jnp.int32)

    for kk in range(KT):
        s, e = kk * nb, (kk + 1) * nb
        panel = X[s:, s:e]

        def lu_branch(Xk):
            pan = Xk[s:, s:e]
            d = k.getrf_nopiv(pan[:nb, :])
            l21 = k.trsm(d, pan[nb:, :], side="R", lower=False)
            Xk = Xk.at[s:e, s:e].set(d)
            Xk = Xk.at[e:, s:e].set(l21)
            if e < Np:
                u12 = k.trsm(d, Xk[s:e, e:], side="L", lower=True,
                             unit=True)
                Xk = Xk.at[s:e, e:].set(u12)
                Xk = Xk.at[e:, e:].add(-k.dot(l21, u12))
            return Xk, jnp.zeros((Mp - s, nb), Xk.dtype)

        def qr_branch(Xk):
            packed, v, T = hh.geqrt(Xk[s:, s:e])
            Xk = Xk.at[s:, s:e].set(packed)
            if e < Np:
                Xk = Xk.at[s:, e:].set(
                    hh.apply_q(v, T, Xk[s:, e:], trans="C"))
            Tfull = jnp.zeros((Mp - s, nb), Xk.dtype).at[:nb, :].set(T)
            return Xk, Tfull

        if criterion == "random":
            use_lu = (hash((seed, kk)) % 2) == 0
        elif criterion == "alternating":
            use_lu = (kk % 2) == 0
        else:
            use_lu = _panel_criterion(criterion, panel, nb, alpha)

        if isinstance(use_lu, bool):  # trace-time choice
            X, Tpan = (lu_branch if use_lu else qr_branch)(X)
            flag = jnp.int32(1 if use_lu else 0)
        else:  # data-dependent: branchless lax.cond over both kernels
            X, Tpan = lax.cond(use_lu, lu_branch, qr_branch, X)
            flag = use_lu.astype(jnp.int32)
        Tm = Tm.at[s:, s:e].set(Tpan)
        lu_tab = lu_tab.at[kk].set(flag)
        X = pmesh.constrain2d(X)
    return TileMatrix(X, A.desc), TileMatrix(Tm, A.desc), lu_tab


def trsmpl_qrf(LU: TileMatrix, Tm: TileMatrix, lu_tab,
               B: TileMatrix) -> TileMatrix:
    """Apply the qrf panel transformations to B (dplasma_ztrsmpl_qrf):
    L^{-1} for LU panels, Q^H for QR panels, selected by lu_tab."""
    nb = LU.desc.nb
    KT = LU.desc.KT
    Y = B.zero_pad().data
    for kk in range(KT):
        s, e = kk * nb, (kk + 1) * nb
        pan = LU.data[s:, s:e]

        def lu_apply(y):
            d = pan[:nb, :]
            y1 = k.trsm(d, y[:nb, :], side="L", lower=True, unit=True)
            y2 = y[nb:, :] - k.dot(pan[nb:, :], y1)
            return jnp.concatenate([y1, y2], axis=0)

        def qr_apply(y):
            v = k.tri(pan, lower=True, unit=True)
            T = Tm.data[s:s + nb, s:e]
            return hh.apply_q(v, T, y, trans="C")

        Y = Y.at[s:, :].set(
            lax.cond(lu_tab[kk] == 1, lu_apply, qr_apply, Y[s:, :]))
        Y = pmesh.constrain2d(Y)
    return TileMatrix(Y, B.desc)


def getrs_qrf(LU: TileMatrix, Tm: TileMatrix, lu_tab,
              B: TileMatrix) -> TileMatrix:
    """Solve from a qrf factorization."""
    Y = trsmpl_qrf(LU, Tm, lu_tab, B)
    return blas3.trsm(1.0, LU, Y, side="L", uplo="U", trans="N")


def gerfs(A: TileMatrix, LU: TileMatrix, perm, B: TileMatrix,
          X: TileMatrix, iters: int = 1) -> TileMatrix:
    """Iterative refinement of a getrf_1d solve (dplasma_zgerfs):
    r = B - A X; X += A^{-1} r, repeated ``iters`` times."""
    for _ in range(iters):
        R = B.like(B.zero_pad().data
                   - k.dot(A.zero_pad().data, X.zero_pad().data))
        D = getrs("N", LU, perm, R)
        X = X.like(X.data + D.data)
    return X


# -- out-of-HBM tier ---------------------------------------------------

@_functools.partial(_jax.jit, static_argnums=(2,))
def _lowmem_lu_apply(col, W, j0_rows: int):
    """One streamed finished-block application inside the left-looking
    update: U rows of the panel solve against W's unit-lower diagonal
    block, then the rows below take the rank-cw product. ``W`` holds
    only rows j0_rows and below (the rows above are never read —
    streaming them would be ~33% avoidable transfer, review r5)."""
    cw = W.shape[1]
    blk = lax.dynamic_slice_in_dim(col, j0_rows, cw, axis=0)
    u = k.trsm(W[:cw], blk, side="L", lower=True, unit=True)
    col = lax.dynamic_update_slice_in_dim(col, u, j0_rows, axis=0)
    below = col.shape[0] - j0_rows - cw
    if below > 0:
        col = lax.dynamic_update_slice_in_dim(
            col, lax.dynamic_slice_in_dim(col, j0_rows + cw, below,
                                          axis=0) - k.dot(W[cw:], u),
            j0_rows + cw, axis=0)
    return col


def getrf_lowmem(A, nb: int = 512, budget_bytes: int | None = None):
    """Out-of-HBM partial-pivoting LU (the lowmem tier beyond
    POTRF/GEMM — VERDICT r4 missing #5; ref tests/Testings.cmake:147
    memory-starved runs, src/zgemm_NN_gpu.jdf:243-330 paced
    streaming).

    The matrix lives HOST-side; a left-looking sweep streams finished
    packed column blocks through a device working set of
    O(N*(nb+cw)) bytes: per panel the streamed blocks drive the U
    solve + rank-cw updates on device, the shrinking tail factors
    with the standard pivoted panel machinery, and the new pivots
    swap HOST rows (LAPACK-style physical swaps, so streamed factor
    columns are always in final row order).  Returns (packed L\\U
    host array, perm) with ``A[perm] = L U`` — the getrf_1d
    contract."""
    import numpy as np

    from dplasma_tpu.ops import gemm as gemm_mod
    from dplasma_tpu.utils import config as _cfg

    Ah = np.array(A, copy=True)
    N = Ah.shape[0]
    assert Ah.shape[1] == N, "getrf_lowmem: square only"
    if budget_bytes is None:
        try:
            frac = float(_cfg.mca_get("device.hbm_fraction", "0.95"))
        except ValueError:
            frac = 0.95
        budget_bytes = int(frac * gemm_mod.device_memory_bytes())
    from dplasma_tpu.analysis import memcheck as _mc
    item = np.dtype(Ah.dtype).itemsize
    # chunk width from the analyzer's working-set inequality — the
    # same accounting memcheck.lowmem_plan simulates feasible
    cw = _mc.lowmem_blocking("getrf", N, item, budget_bytes,
                             nb=nb)["cw"]
    perm = np.arange(N)
    for s in range(0, N, nb):
        w = min(nb, N - s)
        col = jnp.asarray(Ah[:, s:s + w])
        for j0 in range(0, s, cw):
            j1 = min(j0 + cw, s)
            W = jnp.asarray(Ah[j0:, j0:j1])
            col = _lowmem_lu_apply(col, W, j0)
        pan, p_loc = _panel_lu(jnp.asarray(col)[s:])
        p_loc = np.asarray(p_loc)
        Ah[:, s:s + w] = np.asarray(col)
        Ah[s:, s:s + w] = np.asarray(pan)
        # physical host row swaps on all OTHER columns + bookkeeping
        Ah[s:, :s] = Ah[s:, :s][p_loc]
        if s + w < N:
            Ah[s:, s + w:] = Ah[s:, s + w:][p_loc]
        perm[s:] = perm[s:][p_loc]
    return Ah, jnp.asarray(perm)


def dag(A: TileMatrix, recorder=None, *, lookahead=None,
        panel_kernel=None):
    """Record the tile-level right-looking LU DAG (task classes
    getrf/trsm_l/trsm_u/gemm with block-cyclic owner ranks) into
    ``recorder`` for ``--dot`` dumps and DAG analytics.

    Like :func:`dplasma_tpu.ops.potrf.dag` this is pure index algebra
    (data-independent), so it is emitted analytically. Priorities reuse
    the cubic critical-path family (getrf on the potrf formula, panel
    solves on trsm, updates on gemm — the zgetrf JDF uses the same
    shape). With an active pipeline (MCA ``sweep.lookahead`` > 0 or
    the explicit kwarg) the recorded DAG is instead the engine's
    split-column structure (:func:`dplasma_tpu.ops._sweep.
    dag_pipelined`) — what the compiled sweep actually emits.
    """
    from dplasma_tpu import native
    from dplasma_tpu.ops import _sweep
    from dplasma_tpu.utils import profiling
    la, _ = _sweep.sweep_params(lookahead)
    if la > 0:
        return _sweep.dag_pipelined(A, "getrf", recorder, la,
                                    panel_kernel=panel_kernel)
    rec = recorder if recorder is not None else profiling.recorder
    MT, NT = A.desc.MT, A.desc.NT
    KT = min(MT, NT)
    nt = max(MT, NT)
    ranks = native.rank_grid(A.desc.dist, MT, NT)
    pri = native.potrf_priority

    def getrf_t(k):
        return rec.task("getrf", k, priority=pri("potrf", nt, k),
                        rank=int(ranks[k, k]),
                        reads=[(k, k)], writes=[(k, k)])

    def trsm_l_t(m, k):
        return rec.task("trsm_l", m, k, priority=pri("trsm", nt, k, m),
                        rank=int(ranks[m, k]),
                        reads=[(k, k), (m, k)], writes=[(m, k)])

    def trsm_u_t(k, n):
        return rec.task("trsm_u", k, n, priority=pri("trsm", nt, k, n),
                        rank=int(ranks[k, n]),
                        reads=[(k, k), (k, n)], writes=[(k, n)])

    def gemm_t(m, n, k):
        return rec.task("gemm", m, n, k,
                        priority=pri("gemm", nt, k, m, n),
                        rank=int(ranks[m, n]),
                        reads=[(m, k), (k, n), (m, n)],
                        writes=[(m, n)])

    for k in range(KT):
        gk = getrf_t(k)
        if k > 0:
            rec.edge(gemm_t(k, k, k - 1), gk, "Akk")
        for m in range(k + 1, MT):
            tl = trsm_l_t(m, k)
            rec.edge(gk, tl, "Ukk")
            if k > 0:
                rec.edge(gemm_t(m, k, k - 1), tl, "Amk")
        for n in range(k + 1, NT):
            tu = trsm_u_t(k, n)
            rec.edge(gk, tu, "Lkk")
            if k > 0:
                rec.edge(gemm_t(k, n, k - 1), tu, "Akn")
            for m in range(k + 1, MT):
                gm = gemm_t(m, n, k)
                rec.edge(trsm_l_t(m, k), gm, "L")
                rec.edge(tu, gm, "U")
                if k > 0:
                    rec.edge(gemm_t(m, n, k - 1), gm, "C")
    return rec
