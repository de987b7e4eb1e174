"""QR / LQ factorization family (flat tile algorithm).

Reference surface: ``dplasma_zgeqrf`` / ``zgelqf`` / ``zungqr`` /
``zunglq`` / ``zunmqr`` (4 side×trans cases) / ``zunmlq`` /
``zgeqrs`` / ``zgelqs`` / ``zgels`` — src/zgeqrf.jdf (609 lines of
geqrt/tsqrt/unmqr/tsmqr task DAG), src/zgeqrf_wrapper.c,
src/zgels_wrapper.c (SURVEY §2.2 "QR/LQ flat").

TPU-native design: a trace-time blocked Householder sweep. Where the
reference decomposes each panel into MT tile tasks chained by TS
kernels (cache-sized work units for CPU cores), the TPU wants the
whole panel in one MXU-friendly geqrf and the whole trailing update
as three large matmuls (compact-WY): per panel k we emit O(1) big XLA
ops on shrinking static shapes. The T factors live in a (nb × KT·nb)
tile matrix — the analog of the reference's TS matrix
(tests/testing_zgeqrf.c T descriptor).

Storage convention (LAPACK/PLASMA compatible): the returned factor
stores R on/above the diagonal and the Householder vectors V below
it; LQ stores L on/below and V above.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from dplasma_tpu import utils
from dplasma_tpu.descriptors import TileMatrix
from dplasma_tpu.kernels import blas as k
from dplasma_tpu.kernels import householder as hh
from dplasma_tpu.kernels import quant as _quant
from dplasma_tpu.ops import blas3
from dplasma_tpu.ops._sweep import assemble_sweep
from dplasma_tpu.parallel import mesh as pmesh


def _quant_apply_q(v, T, c):
    """Compact-WY trailing apply (Q^H C) with the heavy wide outer
    product ``V @ (T^H (V^H C))`` routed through the block-scaled int8
    GEMM under the ir.precision=int8 rung; the two narrow inner
    products stay f32 — they are rank-nb and set the small coefficient
    matrix the wide product merely applies. Falls through to
    hh.apply_q verbatim when the quant route is inactive."""
    if not _quant.updates_active(v.dtype, c.dtype):
        return hh.apply_q(v, T, c, trans="C")
    w = k.dot(T.conj().T, k.dot(v, c, ta=True, conj_a=True))
    return c - _quant.update_dot(v, w)


# -- shape-cached dd QR sweep callbacks (eager) ------------------------
# The monolithic traced dd sweep inlines the full geqrt_f64 limb graph
# in every panel (~30-40 exact-product subgraphs each). Eager callers instead drive the
# pipelined sweep engine over per-callback executables, compiled per
# shrinking-window shape and persistent-cached; the aggregated far
# apply keeps the executable count near the r5 fused form (one panel
# + one narrow column apply per step, one wide apply per agg_depth
# steps) while streaming the far trailing matrix once per flush.

@partial(jax.jit, static_argnums=(1,))
def _jit_dd_qr_panel(col, kind: str = "chain"):
    from dplasma_tpu.kernels import dd as _dd
    if kind == "tree":
        return _dd.geqrt_f64_tree(col)
    return _dd.geqrt_f64(col)


@jax.jit
def _jit_qr_apply(v, T, blk):
    out = hh.apply_q(v, T, blk, trans="C")
    nb = v.shape[1]
    return out[:nb], out[nb:]


@jax.jit
def _jit_qr_agg_apply(far, *vts):
    panels = [(vts[i], vts[i + 1]) for i in range(0, len(vts), 2)]
    V, T = hh.wy_stack(panels)
    return hh.apply_q(V, T, far, trans="C")


def _check_square_tiles(A: TileMatrix, who: str):
    assert A.desc.mb == A.desc.nb, f"{who} needs square tiles"


def t_desc(A: TileMatrix) -> TileMatrix:
    """Allocate the T-factor matrix for A: one nb×nb triangle per panel
    (the reference's TS/TT descriptor, tests/testing_zgeqrf.c)."""
    nb = A.desc.nb
    return TileMatrix.zeros(nb, A.desc.KT * nb, nb, nb, dtype=A.dtype,
                            dist=A.desc.dist)


# -- QR ----------------------------------------------------------------

def geqrt_rec(a, hnb: int):
    """Panel QR as an hnb-wide nested sweep (the recursive-QR panel
    kernels, ref src/zgeqrfr_geqrt.jdf / zgeqrfr_tsqrt.jdf exposed to
    drivers as -z/--HNB): sub-panels factor and apply within the
    panel; T triangles merge into the full compact-WY factor by the
    standard block formula T12 = -T1 (V1^H V2) T2.  Same (packed, V,
    T) contract as hh.geqrt."""
    m, nb = a.shape
    if hnb <= 0 or hnb >= nb:
        return hh.geqrt(a, rankfull=True)
    V = T = None
    packs, rrows, offs = [], [], []
    rest = a
    for j in range(0, nb, hnb):
        wj = min(hnb, nb - j)
        pk, vj, tj = hh.geqrt(rest[:, :wj], rankfull=True)
        trail = rest[:, wj:]
        if trail.shape[1]:
            trail = hh.apply_q(vj, tj, trail, trans="C")
        rrows.append(trail[:wj])      # R12 rows for later columns
        packs.append(pk)
        offs.append(j)
        vfull = jnp.concatenate(
            [jnp.zeros((j, wj), a.dtype), vj], axis=0) if j else vj
        if V is None:
            V, T = vfull, tj
        else:
            V, T = hh.wy_merge(V, T, vfull, tj)
        rest = trail[wj:]
    # stitch the packed panel: column block i carries the R12 slices of
    # every earlier sub-step above its own (R diag + V below) pack
    cols = []
    for i, (pk, j) in enumerate(zip(packs, offs)):
        wi = pk.shape[1]
        tops = [rrows[t][:, j - offs[t] - rrows[t].shape[0]:
                         j - offs[t] - rrows[t].shape[0] + wi]
                for t in range(i)]
        cols.append(jnp.concatenate(tops + [pk], axis=0))
    packed = jnp.concatenate(cols, axis=1)
    return packed, V, T


def geqrf(A: TileMatrix, *, panel_kernel=None, lookahead=None,
          agg_depth=None) -> tuple[TileMatrix, TileMatrix]:
    """A = Q R (dplasma_zgeqrf). Returns (packed factor, T factors).

    Lookahead-pipelined right-looking sweep on a *shrinking* trailing
    window (:func:`dplasma_tpu.ops._sweep.pipelined_sweep`): panel k's
    reflector block first hits the next panel's block-column with a
    narrow compact-WY apply — so the latency-bound panel chain
    ``panel_k -> column_update -> panel_{k+1}`` never waits for the
    wide trailing update — and the remainder gets the MXU-bound wide
    apply off that chain. MCA ``qr.agg_depth`` > 1 additionally holds
    the far update back for d panels and applies them as ONE rank-d·nb
    compact-WY product (:func:`~dplasma_tpu.kernels.householder.
    wy_stack`), streaming the far trailing matrix once instead of d
    times. ``lookahead=0, agg_depth=1`` is the serialized baseline
    (bit-identical op order); defaults come from MCA
    ``sweep.lookahead`` / ``qr.agg_depth`` (CLI ``--lookahead``).

    The panel itself factors by the panel ENGINE (kernels.panels,
    MCA ``panel.kernel``): ``chain`` = the vendor geqrt (or the dd
    limb CholeskyQR2 on the d route) exactly as before; ``tree`` =
    the TSQR/CAQR binary-reduction panel (batched leaf geqrfs,
    O(log mt) R-tree, TSQR-HR reconstruction back to compact-WY, so
    every downstream apply is untouched); ``pallas`` = the fused
    VMEM panel kernel where eligible. The explicit ``panel_kernel``
    CALLABLE argument (geqrf_rec) bypasses the engine.

    The window is a fresh value each step — no dynamic-update-slice
    re-materialization of the full matrix (the pathology that forced
    ops.potrf left-looking)."""
    from dplasma_tpu.ops import _sweep
    _check_square_tiles(A, "geqrf")
    la, agg = _sweep.sweep_params(lookahead, agg_depth)
    nb = A.desc.nb
    KT = A.desc.KT
    NT = A.desc.NT
    rest = A.zero_pad().data
    if KT == NT and rest.shape[1] > A.desc.N:
        # Tall/square: the right-edge pad columns DO get factored.
        # Identity-pad them (e_i) instead of zero: the pad reflectors
        # are then exact no-ops on the valid region (v_p vanishes above
        # row p >= N, and T's triangularity keeps pad coefficients from
        # leaking into real columns), while keeping every panel full
        # rank — the CholeskyQR2 panel breaks down on zero columns.
        idx = jnp.arange(A.desc.N, rest.shape[1])
        rest = rest.at[idx, idx].set(jnp.ones((), rest.dtype))
    Ts = []       # T triangle per finished panel (V blocks are NOT
    #               retained: only the engine's in-flight states hold
    #               them — the eager dd route exists because of memory
    #               pressure, so nothing keeps KT limb-carrying V
    #               blocks alive until assembly)

    # d-precision route: CholQR2+reconstruction panels with every heavy
    # product an exact limb GEMM (kernels.dd.geqrt_f64). Envelope: the
    # Gram matrix squares the panel condition, so panels must be
    # numerically full rank with cond below ~1e7 — MCA qr_panel=lapack
    # keeps the (slow, emulated-f64, rank-safe) vendor panel instead.
    # The trailing applies need no dd twin: hh.apply_q's products ride
    # k.dot, which already routes f64 through the limb GEMM.
    from dplasma_tpu.utils import config as _cfg
    use_dd = (A.dtype == jnp.float64 and k._dd_active(A.dtype)
              and (_cfg.mca_get("qr_panel") or "auto").lower() != "lapack")
    if use_dd:
        from dplasma_tpu.kernels import dd as _dd

    eager = (use_dd and panel_kernel is None and KT > 1
             and utils.is_concrete(rest))
    # eager dd callers ride per-callback executables, persistent-
    # cached per window shape — the monolithic trace OOM-kills the
    # compile helper > 2048

    # panel-engine kernel for this sweep (kernels.panels MCA
    # panel.kernel; chain = the pre-engine route, bit-identical). The
    # dd route has only the tree/chain pair (the fused pallas panel
    # is f32; pallas resolves to its tree fallback there). Resolved
    # ONCE here and threaded as a static arg into the eager
    # executables so a config flip never hits a stale jit cache.
    from dplasma_tpu.kernels import panels as _panels
    pk = _panels.panel_kernel("qr")
    dd_kind = "tree" if pk in ("tree", "pallas") else "chain"

    def panel(col):
        if eager:
            packed, v, T = _jit_dd_qr_panel(col, dd_kind)
        elif panel_kernel is not None:
            packed, v, T = panel_kernel(col)
        elif use_dd:
            packed, v, T = (_dd.geqrt_f64_tree(col)
                            if dd_kind == "tree"
                            else _dd.geqrt_f64(col))
        else:
            packed, v, T = _panels.qr_panel(col, pk)
        Ts.append(T)
        return packed, (v, T)

    def apply_block(st, blk):
        if eager:
            return _jit_qr_apply(st[0], st[1], blk)
        out = _quant_apply_q(st[0], st[1], blk)
        return out[:nb], out[nb:]

    def agg_apply(sts, far):
        if eager:
            new = _jit_qr_agg_apply(far, *[x for vt in sts for x in vt])
        else:
            new = _quant_apply_q(*hh.wy_stack(sts), far)
        d = len(sts)
        return ([new[i * nb:(i + 1) * nb] for i in range(d)],
                new[d * nb:])

    packs, rrows = _sweep.pipelined_sweep(
        rest, nb, KT, NT, panel, apply_block, lookahead=la,
        agg_depth=agg, agg_apply=agg_apply if agg > 1 else None)

    full = assemble_sweep(packs, rrows, KT, NT, nb)
    Tm = t_desc(A)
    # T-factor stitching rides the assemble phase (sibling span of the
    # one inside assemble_sweep — no nesting, no double counting)
    from dplasma_tpu.observability import phases
    with phases.span("assemble") as _f:
        Td = jnp.concatenate(Ts, axis=1)
        if Td.shape[1] < Tm.desc.Np:
            Td = jnp.pad(Td, ((0, 0), (0, Tm.desc.Np - Td.shape[1])))
        _f(Td)
    return (TileMatrix(pmesh.constrain2d(full), A.desc),
            TileMatrix(Td, Tm.desc))


def geqrf_rec(A: TileMatrix, hnb: int = 0):
    """Recursive-panel QR (dplasma_zgeqrf_rec, the -z/--HNB variant,
    ref src/zgeqrfr_*.jdf nested taskpools): each nb-wide panel is
    itself an hnb-wide nested sweep (:func:`geqrt_rec`), mirroring
    ops.potrf.potrf_rec's diagonal-kernel pattern."""
    if hnb <= 0 or hnb >= A.desc.nb:
        return geqrf(A)
    return geqrf(A, panel_kernel=lambda a: geqrt_rec(a, hnb))


def _qr_panels(Af: TileMatrix, Tf: TileMatrix):
    """Yield (row_start, V, T) per panel from a geqrf result.

    The split is cached on ``Af`` per exact (Af.data, Tf.data) pair:
    repeated applies against one factor object (the geqrs solve path,
    the RBT replay, unmqr both-sides) re-use the V gathers instead of
    re-emitting KT tril/diag-set ops per call. Identity-checked
    against the live arrays, so a factor with replaced data never
    serves a stale split; inside a jit the cache naturally scopes to
    the trace that built the TileMatrix."""
    cache = getattr(Af, "_qr_panels_cache", None)
    if cache is not None and cache[0] is Af.data \
            and cache[1] is Tf.data:
        return cache[2]
    nb = Af.desc.nb
    out = []
    for kk in range(Af.desc.KT):
        s, e = kk * nb, (kk + 1) * nb
        v, _ = hh.split_qr(Af.data[s:, s:e])
        out.append((s, v, Tf.data[:, s:e]))
    try:
        Af._qr_panels_cache = (Af.data, Tf.data, out)
    except (AttributeError, TypeError):
        pass
    return out


def unmqr(side: str, trans: str, Af: TileMatrix, Tf: TileMatrix,
          C: TileMatrix) -> TileMatrix:
    """C ← op(Q) C or C op(Q) (dplasma_zunmqr, zunmqr_{LN,LC,RN,RC}.jdf).

    Q is the factor implicit in (Af, Tf) from :func:`geqrf`.
    """
    side = side.upper()
    trans = trans.upper()
    assert side in ("L", "R") and trans in ("N", "C", "T")
    if trans == "T":  # real-case alias of ConjTrans
        trans = "C"
    panels = _qr_panels(Af, Tf)
    # Q = Q_0 Q_1 … Q_{K-1}; applying Q left ⇒ reverse panel order,
    # Q^H left ⇒ forward; right side mirrors.
    forward = (side == "L") == (trans != "N")
    if not forward:
        panels = panels[::-1]
    Y = C.zero_pad().data
    for s, v, T in panels:
        if side == "L":
            Y = Y.at[s:, :].set(hh.apply_q(v, T, Y[s:, :], trans=trans))
        else:
            Y = Y.at[:, s:].set(
                hh.apply_q_right(v, T, Y[:, s:], trans=trans))
        Y = pmesh.constrain2d(Y)
    return TileMatrix(Y, C.desc)


def ungqr(Af: TileMatrix, Tf: TileMatrix, K: int | None = None) -> TileMatrix:
    """Form the first K (default N) columns of Q explicitly
    (dplasma_zungqr, zungqr.jdf)."""
    M = Af.desc.M
    K = min(M, Af.desc.N) if K is None else K
    nb = Af.desc.nb
    E = TileMatrix.from_dense(jnp.eye(M, K, dtype=Af.dtype), nb, nb,
                              Af.desc.dist)
    return unmqr("L", "N", Af, Tf, E)


def geqrs(Af: TileMatrix, Tf: TileMatrix, B: TileMatrix) -> TileMatrix:
    """Least-squares solve from a QR factorization (dplasma_zgeqrs):
    X = R^{-1} (Q^H B)[:N]."""
    N = Af.desc.N
    nb = Af.desc.nb
    Y = unmqr("L", "C", Af, Tf, B)
    R = TileMatrix.from_dense(Af.to_dense()[:N, :N], nb, nb, Af.desc.dist)
    Yt = TileMatrix.from_dense(Y.to_dense()[:N, :], nb, nb, B.desc.dist)
    return blas3.trsm(1.0, R, Yt, side="L", uplo="U", trans="N")


# -- LQ ----------------------------------------------------------------

def gelqf(A: TileMatrix) -> tuple[TileMatrix, TileMatrix]:
    """A = L Q (dplasma_zgelqf): the QR dual, factored as row panels.

    Returns (packed factor, T factors): L on/below the diagonal, V^H
    above it (LAPACK gelqf storage).
    """
    _check_square_tiles(A, "gelqf")
    At = A.zero_pad().data.conj().T
    desc_t = A.desc.transposed()
    Bf, Tf = geqrf(TileMatrix(At, desc_t))
    return TileMatrix(Bf.data.conj().T, A.desc), Tf


def unmlq(side: str, trans: str, Af: TileMatrix, Tf: TileMatrix,
          C: TileMatrix) -> TileMatrix:
    """C ← op(Q) C or C op(Q) for the LQ factor (dplasma_zunmlq).

    With A = L Q and A^H = Q' R (our gelqf internals), Q = Q'^H, so
    e.g. (Q C)^H = C^H Q': conjugate-transpose C, flip the side, keep
    trans, and conjugate-transpose back.
    """
    side = side.upper()
    trans = trans.upper()
    assert side in ("L", "R") and trans in ("N", "C", "T")
    if trans == "T":
        trans = "C"
    AfT = TileMatrix(Af.data.conj().T, Af.desc.transposed())
    CT = TileMatrix(C.zero_pad().data.conj().T, C.desc.transposed())
    out = unmqr("R" if side == "L" else "L", trans, AfT, Tf, CT)
    return TileMatrix(out.data.conj().T, C.desc)


def unglq(Af: TileMatrix, Tf: TileMatrix, K: int | None = None) -> TileMatrix:
    """Form the first K (default M) rows of Q from an LQ factorization
    (dplasma_zunglq)."""
    N = Af.desc.N
    K = min(N, Af.desc.M) if K is None else K
    nb = Af.desc.nb
    E = TileMatrix.from_dense(jnp.eye(K, N, dtype=Af.dtype), nb, nb,
                              Af.desc.dist)
    return unmlq("R", "N", Af, Tf, E)


def gelqs(Af: TileMatrix, Tf: TileMatrix, B: TileMatrix) -> TileMatrix:
    """Minimum-norm solve from an LQ factorization (dplasma_zgelqs):
    X = Q^H L^{-1} B."""
    M, N = Af.desc.M, Af.desc.N
    nb = Af.desc.nb
    L = TileMatrix.from_dense(Af.to_dense()[:M, :M], nb, nb, Af.desc.dist)
    Y = blas3.trsm(1.0, L, B, side="L", uplo="L", trans="N")
    Z = TileMatrix.from_dense(
        jnp.zeros((N, B.desc.N), B.dtype).at[:M, :].set(Y.to_dense()),
        nb, nb, B.desc.dist)
    return unmlq("L", "C", Af, Tf, Z)


def gels(A: TileMatrix, B: TileMatrix) -> TileMatrix:
    """Least-squares / minimum-norm driver (dplasma_zgels,
    src/zgels_wrapper.c): QR path for M >= N, LQ path for M < N."""
    if A.desc.M >= A.desc.N:
        Af, Tf = geqrf(A)
        return geqrs(Af, Tf, B)
    Af, Tf = gelqf(A)
    return gelqs(Af, Tf, B)


# -- out-of-HBM tier ---------------------------------------------------

@partial(jax.jit, static_argnums=(3,), donate_argnums=(0,))
def _lowmem_qr_apply(col, V, T, s0: int):
    """Apply one streamed finished panel's compact-WY reflectors
    (rows s0 and below) to the device-resident column block. ``col``
    is donated: the caller rebinds it every apply, and the lowmem
    tier exists precisely to not carry a second N x nb buffer."""
    tail = col[s0:]
    tail = hh.apply_q(V, T, tail, trans="C")
    return col.at[s0:].set(tail)


def geqrf_lowmem(A, nb: int = 512, budget_bytes: int | None = None):
    """Out-of-HBM blocked QR (the lowmem tier beyond POTRF/GEMM —
    VERDICT r4 missing #5; ref tests/Testings.cmake:147 memory-starved
    runs paced by streaming, src/zgemm_NN_gpu.jdf:243-330).

    The matrix lives HOST-side; a LEFT-looking sweep holds one column
    block on device and streams each finished panel's (V, T) through
    to apply its compact-WY update, then factors the shrinking tail
    with the standard panel kernel — device-live bytes stay
    O(N*3nb) regardless of N; ``budget_bytes`` bounds that working
    set by shrinking the panel width when needed (as
    plan_potrf_lowmem sizes its blocking).  Returns (packed host
    factor, T host stack (nb, KT*nb)) in the ops.qr layout."""
    import numpy as np

    from dplasma_tpu.kernels import householder as _hh

    Ah = np.array(A, copy=True)
    N = Ah.shape[0]
    assert Ah.shape[1] == N, "geqrf_lowmem: square only"
    if budget_bytes is not None:
        from dplasma_tpu.analysis import memcheck as _mc
        item = np.dtype(Ah.dtype).itemsize
        # panel width from the analyzer's working-set inequality —
        # the same accounting memcheck.lowmem_plan simulates feasible
        nb = _mc.lowmem_blocking("geqrf", N, item, budget_bytes,
                                 nb=nb)["nb"]
    KT = -(-N // nb)
    Ts = np.zeros((nb, KT * nb), Ah.dtype)
    for kk in range(KT):
        s = kk * nb
        w = min(nb, N - s)
        col = jnp.asarray(Ah[:, s:s + w])
        for j in range(kk):
            s0 = j * nb
            Vj = jnp.asarray(Ah[s0:, s0:s0 + nb])
            Vj = jnp.tril(Vj, -1).at[
                jnp.arange(min(nb, Vj.shape[0])),
                jnp.arange(min(nb, Vj.shape[1]))].set(1.0)
            Tj = jnp.asarray(Ts[:, s0:s0 + nb])
            col = _lowmem_qr_apply(col, Vj, Tj, s0)
        packed, v, T = _hh.geqrt(jnp.asarray(col)[s:], rankfull=True)
        Ah[:, s:s + w] = np.asarray(col)
        Ah[s:, s:s + w] = np.asarray(packed)
        Ts[:T.shape[0], s:s + T.shape[1]] = np.asarray(T)
    return Ah, Ts


def dag(A: TileMatrix, recorder=None, *, lookahead=None,
        agg_depth=None, panel_kernel=None):
    """Record the tile-level blocked QR DAG (task classes geqrt/unmqr/
    tsqrt/tsmqr — the zgeqrf JDF's flat-tree dependence structure) into
    ``recorder`` for ``--dot`` dumps and DAG analytics.

    With an active pipeline (MCA ``sweep.lookahead`` > 0 or
    ``qr.agg_depth`` > 1, or the explicit kwargs) the recorded DAG is
    the pipelined engine's split-column task structure instead
    (:func:`dplasma_tpu.ops._sweep.dag_pipelined`) — what the compiled
    sweep actually emits.

    Pure index algebra like :func:`dplasma_tpu.ops.potrf.dag`.
    Priorities grow with the panel index (later panels sit deeper on
    the critical path).

    Tile declarations split the panel-k diagonal tile into its ``V``
    (reflectors, below the diagonal) and ``R`` regions: tsqrt(m,k)
    updates only R while unmqr(k,n) reads only V — at whole-tile
    granularity that pair would be a false write-after-read race, but
    the regions are disjoint (the JDF expresses the same split through
    per-region flows).
    """
    from dplasma_tpu import native
    from dplasma_tpu.ops import _sweep
    from dplasma_tpu.utils import profiling
    la, agg = _sweep.sweep_params(lookahead, agg_depth)
    if la > 0 or agg > 1:
        return _sweep.dag_pipelined(A, "geqrf", recorder, la, agg,
                                    panel_kernel=panel_kernel)
    rec = recorder if recorder is not None else profiling.recorder
    MT, NT = A.desc.MT, A.desc.NT
    KT = min(MT, NT)
    ranks = native.rank_grid(A.desc.dist, MT, NT)

    def t(cls, *ix, tile):
        if cls == "geqrt":
            (k,) = ix
            rd, wr = [(k, k)], [(k, k, "V"), (k, k, "R")]
        elif cls == "unmqr":
            k, n = ix
            rd, wr = [(k, k, "V"), (k, n)], [(k, n)]
        elif cls == "tsqrt":
            m, k = ix
            rd, wr = [(k, k, "R"), (m, k)], [(m, k), (k, k, "R")]
        else:  # tsmqr(m, n, k) updates the [A(k,n); A(m,n)] couple
            m, n, k = ix
            rd, wr = [(m, k), (k, n), (m, n)], [(m, n), (k, n)]
        return rec.task(cls, *ix, priority=ix[-1],
                        rank=int(ranks[tile[0], tile[1]]),
                        reads=rd, writes=wr)

    for k in range(KT):
        ge = t("geqrt", k, tile=(k, k))
        for n in range(k + 1, NT):
            un = t("unmqr", k, n, tile=(k, n))
            rec.edge(ge, un, "V1")
        prev_panel = ge
        for m in range(k + 1, MT):
            ts = t("tsqrt", m, k, tile=(m, k))
            rec.edge(prev_panel, ts, "R")     # panel reduction chain
            prev_panel = ts
            for n in range(k + 1, NT):
                tm = t("tsmqr", m, n, k, tile=(m, n))
                rec.edge(ts, tm, "V2")
                # top row slab rides down the column through tsmqr
                top = t("unmqr", k, n, tile=(k, n)) if m == k + 1 \
                    else t("tsmqr", m - 1, n, k, tile=(m - 1, n))
                rec.edge(top, tm, "A_kn")
        if k + 1 < KT:
            # next panel consumes the updated tiles of step k
            rec.edge(t("tsmqr", k + 1, k + 1, k, tile=(k + 1, k + 1)),
                     t("geqrt", k + 1, tile=(k + 1, k + 1)), "Akk")
            for m in range(k + 2, MT):
                rec.edge(t("tsmqr", m, k + 1, k, tile=(m, k + 1)),
                         t("tsqrt", m, k + 1, tile=(m, k + 1)), "Amk")
            for n in range(k + 2, NT):
                rec.edge(t("tsmqr", k + 1, n, k, tile=(k + 1, n)),
                         t("unmqr", k + 1, n, tile=(k + 1, n)), "Akn")
                # trailing tiles accumulate across panels: step k+1's
                # update of A(m,n) reads step k's
                for m in range(k + 2, MT):
                    rec.edge(t("tsmqr", m, n, k, tile=(m, n)),
                             t("tsmqr", m, n, k + 1, tile=(m, n)), "C")
    return rec
