"""FP64-equivalent GEMM on the MXU via exact int8 limb splitting.

SURVEY §7 ranks "FP64-equivalent throughput on TPU" the #1 hard part:
the MXU multiplies bf16/int8 natively and f64 only by slow scalar
emulation. This module implements the Ozaki-style splitting scheme on
the *int8 systolic path*: each f64 operand is scaled (per A-row /
per B-column) and split EXACTLY into ``nl`` limbs of ``w = 7``
significant bits stored as int8 digits (|d| <= 127). A limb-pair
matmul then accumulates natively in int32 — every digit dot product is
EXACT with no f32-accumulator width juggling (measured: the int8 path
runs at 2x the bf16 matmul rate on current hardware, 400 TOPS vs
197 TF, so the same accuracy costs 36 products at double speed
instead of 45 — ~5x the round-2 bf16 engine's bound). Same-scale
products (same i+j) are summed exactly in int32 (chunk bound
``nl*kc*127^2 < 2^31``); only the ``nl`` level sums touch (emulated,
slow) f64.

Cost model: pairs with i+j < nl limb matmuls (nl = ceil(54/w)); at
w = 7, nl = 8 -> 36 int8 matmuls ~ 1/36 of int8 peak (and the knob:
callers needing only ~f32x2 accuracy can pass ``bits=32`` for
nl = 5 -> 15 products).

Ref: the role of the reference's d-precision CORE_dgemm
(src/cores/*.c precision-generated from CORE_zgemm) on hardware whose
matmul unit is int8/bf16-native.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from dplasma_tpu import utils

# Digit width for int8 limbs: |d| <= 2^7 - 1 = 127.
W8 = 7


def _plan(K: int, bits: int):
    """Limb width/count and chunk depth for a K-deep dot.

    w is W8 (int8 digits); nl covers the requested mantissa; kc bounds
    the per-chunk reduction depth so the worst LEVEL sum — up to nl
    pair products, each a kc-deep dot of w-bit digits — stays exact in
    the MXU's native int32 accumulator: nl * kc * (2^w-1)^2 < 2^31.
    Cross-chunk accumulation rides f64 (exact: each summand is an
    integer < 2^31), so any K is supported with no precision cliff
    (round-1 ADVICE: no silent clamp).
    """
    w = W8
    nl = math.ceil((bits + 1) / w)
    kc = (2 ** 31 - 1) // (nl * (2 ** w - 1) ** 2)
    return w, nl, min(K, kc)


#: columns per row scale of :class:`LimbMatrix`'s limbs
LIMB_COLS = 512

# Back-compat alias for the chunk-depth constant (tests poke it to
# build deep-K cases); the real value is now plan-dependent.
KC = _plan(2 ** 20, 53)[2]


def _split_int(x, w: int, nl: int, axis: int):
    """Exact row/col-scaled integer limb decomposition.

    Returns (limbs, scale, m): x == scale * sum_l limbs[l] *
    2^{-w(l+1)} exactly up to the dropped tail; each limbs[l] is an
    int8 digit array with |d| < 2^w, and ``m`` is the row/col max the
    scale derives from (callers use it for NaN/Inf detection without
    an extra pass).
    """
    from dplasma_tpu.observability import phases
    with phases.span("split", timed=False):
        ax = 1 - axis  # reduce over the opposite axis
        m = jnp.max(jnp.abs(x), axis=ax, keepdims=True)
        # strictly-greater power-of-two scale: |u| < 1 keeps every digit
        # <= 2^w - 1 = 127 (u = +-1 would emit +-128, wrapping int8)
        scale = _pow2_scale_bits(m)
        return _split_fixed(x, scale, w, nl), scale, m


def _level_recombine(levels, w: int):
    """sum_l levels[l] * 2^{-w(l+2)} in f64 — the only emulated-f64
    arithmetic in the scheme (nl converts + fmas)."""
    acc = None
    for l, lvl in enumerate(levels):
        term = lvl.astype(jnp.float64) * (2.0 ** (-w * (l + 2)))
        acc = term if acc is None else acc + term
    return acc


def _pin_cat_axis(p):
    """Keep the limb-concat (last) axis of a level dot output
    UNSHARDED under an active device mesh.

    With a sharded consumer (e.g. a 2-D-distributed residual), GSPMD
    back-propagates the output's column sharding through the per-limb
    prefix slices into the concatenated dot — partitioning the concat
    axis at limb-interior boundaries, which XLA's halo-exchange
    lowering miscompiles (observed on the 2x2 CPU grid: jit+sharded
    results are garbage while eager is exact). Pinning the concat axis
    (rows stay 'p'-distributed when they divide) forces the reshard to
    happen AFTER the slices instead, restoring exactness. No-op
    without an active grid, and skipped on concrete (eager) values —
    the bug is a partitioner miscompile, eager execution is exact and
    must not pay placement traffic per limb product."""
    from dplasma_tpu.parallel import mesh as pmesh
    m = pmesh._ACTIVE
    if m is None or utils.is_concrete(p):
        return p
    from jax.sharding import NamedSharding, PartitionSpec as P
    rows_ax = p.ndim - 2   # lhs-free axis (batched when chunked)
    rows = (pmesh.ROW_AXIS
            if p.shape[rows_ax] % m.shape[pmesh.ROW_AXIS] == 0
            else None)
    spec = [None] * p.ndim
    spec[rows_ax] = rows
    return jax.lax.with_sharding_constraint(
        p, NamedSharding(m, P(*spec)))


def _limb_levels(al, bl, K: int, w: int, nl: int, kc: int,
                 lhs_t: bool = False):
    """Exact level sums of the limb-pair products.

    ``al``: nl int8 arrays (M, K) — or (K, M) when ``lhs_t`` (the
    natural slice layout of the transposed factor-limb cache);
    ``bl``: nl int8 arrays (K, N).  Contraction always runs on the
    K-MAJOR layout of the rhs: the MXU pays 2.2x for an rhs contracted
    on its minor axis at K=8192 and 9x at K=1024 (measured r5 — the
    r4 cache_layout form, (N, K) rhs, was exactly that), while an
    lhs-transposed operand is nearly free (387 vs 333 TOPS).
    Returns the nl level arrays: int32 when unchunked (K <= kc), f64
    otherwise (per-chunk int32 sums are exact by the _plan bound;
    cross-chunk adds are exact integer-valued f64).
    """
    nchunks = math.ceil(K / kc)
    if nchunks > 1:
        pad = nchunks * kc - K
        if lhs_t:
            al = [jnp.pad(x, ((0, pad), (0, 0))) for x in al]
            al = [x.reshape(nchunks, kc, x.shape[1]) for x in al]
            dn_l = (1,)
        else:
            al = [jnp.pad(x, ((0, 0), (0, pad))) for x in al]
            al = [x.reshape(x.shape[0], nchunks, kc).transpose(1, 0, 2)
                  for x in al]
            dn_l = (2,)
        bl = [jnp.pad(x, ((0, pad), (0, 0))) for x in bl]
        bl = [x.reshape(nchunks, kc, x.shape[1]) for x in bl]
        dn = ((dn_l, (1,)), ((0,), (0,)))
        cat_ax, P = 2, bl[0].shape[2]
    else:
        dn = ((((0,) if lhs_t else (1,)), (0,)), ((), ()))
        cat_ax = 1
        P = bl[0].shape[1]

    # One dot per LEFT limb against the concatenation of every right
    # limb it pairs with (j < nl - i): same flops as the 36 pair
    # products, ~4.5x fewer matmul HLOs — the unrolled blocked sweeps
    # were OOM-killing the AOT compile helper at 16 block columns.
    # The concatenation is built ONCE; per-i operands are prefix
    # slices of it (per-i concats cost ~28 dynamic-update-slice ops
    # per product — profiled r4 as a top op-count line).
    bfull = bl[0] if nl == 1 else jnp.concatenate(bl, axis=cat_ax)
    levels = [None] * nl
    for i in range(nl):
        nj = nl - i
        bcat = jax.lax.slice_in_dim(bfull, 0, nj * P, axis=cat_ax)
        p = _pin_cat_axis(jax.lax.dot_general(
            al[i], bcat, dn, preferred_element_type=jnp.int32))
        for j in range(nj):
            # output = batch + lhs-free + rhs-free: the concatenated
            # right limbs always land on the LAST axis
            pj = p[..., j * P:(j + 1) * P]
            lvl = levels[i + j]
            levels[i + j] = pj if lvl is None else lvl + pj
    if nchunks > 1:                 # (nc, M, N) int32 -> exact f64 sum
        levels = [jnp.sum(x.astype(jnp.float64), axis=0)
                  for x in levels]
    return levels


def _pallas_epilogue_ok(levels, N: int) -> bool:
    """Route the recombine through the Pallas double-single kernel?
    Only on float-float backends (where DS width == the platform's
    own f64), unchunked int32 levels, lane-aligned widths, and not
    disabled via MCA ``dd_epilogue=off`` (which a multi-device
    ``parallel.mesh.use_grid`` sets)."""
    if not _ff_backend() or levels[0].dtype != jnp.int32:
        return False
    if N % 128 or levels[0].shape[0] % 8:
        return False
    from dplasma_tpu.utils import config as _cfg
    return (_cfg.mca_get("dd_epilogue") or "auto").lower() != "off"


def _recombine_scale_base(levels, base, sa, sb, w: int):
    """``base - (sa*sb) * sum_l levels[l] * 2^(-w(l+2))`` — the
    epilogue that closes every exact limb product.  On the TPU
    float-float backend this is ONE fused Pallas double-single pass
    (kernels/pallas_dd.py; profiled r5 at ~60% of the blocked-dd
    panel IR and half the trailing-update time when left to the x64
    rewriter's emulated chain); elsewhere the exact emulated
    recombine. Either route runs under the ``recombine`` scope."""
    from dplasma_tpu.observability import phases
    with phases.span("recombine", timed=False):
        if base is None and not isinstance(sa, jax.Array):
            sa = jnp.asarray(sa)
        N = levels[0].shape[1]
        if _pallas_epilogue_ok(levels, N):
            from dplasma_tpu.kernels import pallas_dd
            return pallas_dd.recombine_base(levels, base, sa, sb, w)
        U = _level_recombine(levels, w)
        prod = U * (sa * sb)
        return -prod if base is None else base - prod


def gemm_residual(base, a, b, bits: int = 53):
    """``base - a @ b`` at f64-equivalent accuracy with the limb
    recombine and the subtraction fused into one epilogue pass — the
    residual form every dd iterative-refinement step consumes
    (_potrf_tile_ir / _panel_trsm_ir / lu_ir). Real f64 only."""
    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            "gemm_residual requires jax_enable_x64 (inputs would "
            "silently truncate to f32, breaking the FP64 contract)")
    a = jnp.asarray(a, jnp.float64)
    b = jnp.asarray(b, jnp.float64)
    K = a.shape[1]
    w, nl, kc = _plan(K, bits)
    al, sa, _ = _split_int(a, w, nl, axis=0)
    bl, sb, _ = _split_int(b, w, nl, axis=1)
    levels = _limb_levels(al, bl, K, w, nl, kc)
    return _recombine_scale_base(levels, base, sa, sb, w)


def gemm_f64(a, b, bits: int = 53, _nonfinite_mask: bool = True):
    """C = A @ B with f64-equivalent accuracy from int8 MXU matmuls.

    ``a``, ``b`` are f64 (M, K) and (K, N). ``bits`` selects target
    mantissa (53 = full f64; 32 ~ f32x2 double-single at ~2.4x speed).
    Requires x64 mode: without it the f64 contract is silently broken.

    Non-finite semantics: any NaN OR Inf operand entry poisons its
    whole result row/column with NaN. This is coarser than native f64
    GEMM (which would propagate signed Inf where no cancellation
    occurs): the digit cast cannot represent Inf, and the row/col max
    the mask derives from cannot distinguish which products overflow.
    Callers that test for Inf specifically must pre-screen inputs
    (ADVICE r3).
    """
    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            "gemm_f64 requires jax_enable_x64 (inputs would silently "
            "truncate to f32, breaking the FP64-equivalent contract)")
    a = jnp.asarray(a, jnp.float64)
    b = jnp.asarray(b, jnp.float64)
    K = a.shape[1]
    w, nl, kc = _plan(K, bits)
    al, sa, ma = _split_int(a, w, nl, axis=0)   # row-scaled
    bl, sb, mb = _split_int(b, w, nl, axis=1)   # col-scaled
    levels = _limb_levels(al, bl, K, w, nl, kc)
    out = _recombine_scale_base(levels, None, -sa, sb, w)
    # NaN/Inf propagation: the digit cast would silently turn
    # non-finite entries into garbage integers (review r3); a bad
    # entry must poison its result row/column as a real matmul would
    # (downstream INFO detection relies on NaNs surviving products).
    # The masks reuse the split's own row/col maxes — no extra pass.
    # Internal IR callers (blocked potrf) skip the mask: their f32
    # seeds/residuals already propagate NaNs, and the two where-passes
    # per product are measurable on (N, nb) panels (profiled r4).
    if not _nonfinite_mask:
        return out
    return jnp.where(~jnp.isfinite(ma) | ~jnp.isfinite(mb),
                     jnp.nan, out)


def gemm_dd(alpha, a, b, beta, c, bits: int = 53):
    """alpha*A@B + beta*C in f64-equivalent precision (CORE_zgemm shape
    for the d-precision path on MXU hardware)."""
    out = gemm_f64(a, b, bits=bits)
    return alpha * out + beta * jnp.asarray(c, jnp.float64)


def mm(a, b, bits: int = 53):
    """Complex-aware exact matmul: f64 via :func:`gemm_f64`; c128 as two
    2K-deep real limb GEMMs (same flops as the 4-matmul form)."""
    if jnp.iscomplexobj(a) or jnp.iscomplexobj(b):
        a = jnp.asarray(a, jnp.complex128)
        b = jnp.asarray(b, jnp.complex128)
        lhs = jnp.concatenate([jnp.real(a), jnp.imag(a)], axis=1)
        re = gemm_f64(lhs, jnp.concatenate(
            [jnp.real(b), -jnp.imag(b)], axis=0), bits=bits)
        im = gemm_f64(lhs, jnp.concatenate(
            [jnp.imag(b), jnp.real(b)], axis=0), bits=bits)
        return (re + 1j * im).astype(jnp.complex128)
    return gemm_f64(a, b, bits=bits)


class LimbMatrix:
    """A matrix split ONCE into int8 limbs, for many f64-equivalent
    products ``a @ x`` with narrow ``x`` (the matvecs of a Krylov
    refinement): each product splits only ``x``.

    The limbs are scaled per row in blocks of :data:`LIMB_COLS`
    columns, not per whole row: in a diagonally dominant row the
    diagonal sets a whole-row scale some log2(N) bits above its other
    entries, whose limbs would then drop those bits. Every limb pair is
    multiplied (not only the ``i + j < nl`` of :func:`gemm_f64`), so
    the product is as exact as the split. Each block's level sums are
    exact in int32 and recombined in f64 on their own scales, then
    summed.

    Under an active mesh whose grid divides ``a`` (rows sharded on
    'p', columns on 'q'), the split and every product run per device
    in a ``shard_map`` on its own block of ``a`` and of the replicated
    ``x``; the partial products, carried across the chips as three
    f32 words that add up to them exactly, are summed along 'q' and
    gathered along 'p'. The answer comes back replicated. Real f64
    only."""

    def __init__(self, a):
        from dplasma_tpu.parallel import mesh as pmesh
        if not jax.config.jax_enable_x64:
            raise RuntimeError("LimbMatrix requires jax_enable_x64")
        a = jnp.asarray(a, jnp.float64)
        m = pmesh.active()
        M, K = a.shape
        grid = (1, 1) if m is None else (m.shape[pmesh.ROW_AXIS],
                                         m.shape[pmesh.COL_AXIS])
        self.mesh = m if (grid != (1, 1) and M % grid[0] == 0
                          and K % grid[1] == 0) else None
        if self.mesh is None:
            grid = (1, 1)
        self.shape = (M, K)
        self.kl = K // grid[1]                   # columns per device
        self.w, self.nl, _ = _plan(LIMB_COLS, 53)
        self.cw = min(LIMB_COLS, self.kl)
        self.nch = -(-self.kl // self.cw)
        if self.mesh is None:
            self.limbs, self.scale, self.bad = self._split(a)
            return
        from jax.sharding import PartitionSpec as P
        row, col = pmesh.ROW_AXIS, pmesh.COL_AXIS

        def split(x):
            limbs, scale, bad = self._split(x)
            return limbs, scale, jax.lax.pmax(bad.astype(jnp.int32),
                                              col) > 0

        self.limbs, self.scale, self.bad = jax.shard_map(
            split, mesh=self.mesh, in_specs=P(row, col),
            out_specs=(P(col, row), P(col, row), P(row)),
            check_vma=False)(a)

    def _blocks(self, x):
        """(rows, K) -> (nch, rows, cw), the last block zero-padded."""
        pad = self.nch * self.cw - x.shape[1]
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)))
        return x.reshape(x.shape[0], self.nch, self.cw).transpose(1, 0, 2)

    def _split(self, a):
        """(nl int8 limbs (nch, rows, cw), their scales (nch, rows, 1),
        non-finite rows (rows, 1)) of one block of rows."""
        from dplasma_tpu.observability import phases
        with phases.span("split", timed=False):
            a3 = self._blocks(a)
            mx = jnp.max(jnp.abs(a3), axis=2, keepdims=True)
            scale = _pow2_scale_bits(mx)
            bad = jnp.any(~jnp.isfinite(mx), axis=0)
            return _split_fixed(a3, scale, self.w, self.nl), scale, bad

    def _partial(self, limbs, scale, x):
        """This block's ``a @ x`` in f64, ``x`` its (kl, c) rows."""
        from dplasma_tpu.observability import phases
        w, nl = self.w, self.nl
        with phases.span("split", timed=False):
            x3 = self._blocks(x.T).transpose(0, 2, 1)     # (nch, cw, c)
            xscale = _pow2_scale_bits(jnp.max(jnp.abs(x3), axis=1,
                                              keepdims=True))
            bl = _split_fixed(x3, xscale, w, nl)
        c = x.shape[1]
        bfull = jnp.concatenate(bl, axis=2)            # (nch, cw, nl*c)
        dn = (((2,), (1,)), ((0,), (0,)))
        # every limb pair, not only i + j < nl: the product is then as
        # exact as the split, and a matvec costs the read of the limbs
        levels = [None] * (2 * nl - 1)
        for i in range(nl):
            p = jax.lax.dot_general(limbs[i], bfull, dn,
                                    preferred_element_type=jnp.int32)
            for j in range(nl):
                pj = p[..., j * c:(j + 1) * c]
                levels[i + j] = pj if levels[i + j] is None \
                    else levels[i + j] + pj
        with phases.span("recombine", timed=False):
            u = _level_recombine(levels, w) * (scale * xscale)
            return jnp.sum(u, axis=0)

    def matmul(self, x):
        """``a @ x`` at f64-equivalent accuracy for a replicated ``x``
        of shape (K, c); replicated (M, c) out. A non-finite entry of
        ``a`` or ``x`` makes its row or column NaN."""
        from dplasma_tpu.parallel import mesh as pmesh
        x = jnp.asarray(x, jnp.float64)
        xbad = ~jnp.isfinite(jnp.max(jnp.abs(x), axis=0, keepdims=True))
        if self.mesh is None:
            out = self._partial(self.limbs, self.scale, x)
        else:
            from jax.sharding import PartitionSpec as P
            row, col = pmesh.ROW_AXIS, pmesh.COL_AXIS
            kl = self.kl

            def body(limbs, scale, xr):
                q = jax.lax.axis_index(col)
                xl = jax.lax.dynamic_slice_in_dim(xr, q * kl, kl, axis=0)
                part = self._partial(limbs, scale, xl)
                # three f32 words that add up to the f64 partial exactly
                w1 = part.astype(jnp.float32)
                r1 = part - w1.astype(jnp.float64)
                w2 = r1.astype(jnp.float32)
                w3 = (r1 - w2.astype(jnp.float64)).astype(jnp.float32)
                words = jnp.stack([w1, w2, w3])
                words = jax.lax.all_gather(words, col)
                return jax.lax.all_gather(words, row, axis=2, tiled=True)

            words = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(P(col, row), P(col, row), P()),
                out_specs=P(), check_vma=False)(self.limbs, self.scale,
                                                x)
            parts = jnp.sum(words.astype(jnp.float64), axis=1)
            out = parts[0]
            for k in range(1, parts.shape[0]):
                out = out + parts[k]
        return jnp.where(self.bad | xbad, jnp.nan, out)

    def residual(self, base, x):
        """``base - a @ x`` at f64-equivalent accuracy (see
        :meth:`matmul`)."""
        return jnp.asarray(base, jnp.float64) - self.matmul(x)


# ---------------------------------------------------------------------
# Tile factorizations at f64-equivalent accuracy.
#
# The MXU has no f64 unit, and XLA's scalar-emulated f64 lax.linalg is
# ~100x off MXU speed (measured: 69 ms for one 1024-tile cholesky vs
# ~6 ms of limb matmuls). The TPU-native design: factor the tile in
# f32 (fast, MXU-blocked), then restore f64 accuracy with Newton /
# iterative-refinement steps whose ONLY heavy ops are exact limb
# matmuls. Mixed-precision IR in the Carson–Higham sense, applied at
# tile granularity — this is what replaces the reference's d-precision
# CORE_zpotrf/ztrtri tile kernels (src/cores/, @precisions ... d).
# ---------------------------------------------------------------------


def _wdtype(x):
    return jnp.complex128 if jnp.iscomplexobj(x) else jnp.float64


def _ct(x):
    return x.conj().T if jnp.iscomplexobj(x) else x.T


def _take_triangle(T, lower: bool, unit: bool):
    """Mask to the named triangle (optionally forcing a unit diagonal):
    the stored-triangle contract — the opposite triangle may hold
    scratch (e.g. the U part of a packed L\\U tile) and must NOT leak
    into the Newton products."""
    t = jnp.tril(T) if lower else jnp.triu(T)
    if unit:
        r = jnp.arange(T.shape[0])
        t = t.at[r, r].set(jnp.ones((), T.dtype))
    return t


def trtri_f64(T, lower: bool = True, unit: bool = False, iters: int = 2):
    """Inverse of a triangular tile at f64-equivalent accuracy.

    f32 triangular solve seeds X0; Newton iterations
    X <- X (2I - T X) square the error each step (error_k ~
    (eps32*kappa)^{2^k}; 2 steps reach f64 for kappa up to ~1e7), with
    every product an exact limb matmul. Reads only the named triangle.
    """
    T = jnp.asarray(T, _wdtype(T))
    T = _take_triangle(T, lower, unit)
    n = T.shape[0]
    if not unit:
        # power-of-two row prescale: f64 magnitudes outside f32 range
        # would overflow/flush in the seed solve (review r3);
        # inv(S T') = inv(T') S^{-1} unscales exactly
        m_ = jnp.max(jnp.abs(T), axis=1, keepdims=True)
        s = 0.25 * _pow2_scale_bits(m_)   # 2^floor(log2 m)
        T = T / s
    eye32 = jnp.eye(n, dtype=jnp.complex64 if jnp.iscomplexobj(T)
                    else jnp.float32)
    X = jax.lax.linalg.triangular_solve(
        T.astype(eye32.dtype), eye32, left_side=True, lower=lower)
    X = X.astype(T.dtype)
    eye2 = 2.0 * jnp.eye(n, dtype=T.dtype)
    tri = jnp.tril if lower else jnp.triu
    for _ in range(iters):
        R = mm(T, X)                   # ~ I
        X = tri(mm(X, eye2 - R))
    if not unit:
        X = X / s[:, 0][None, :]
    return X


def trsm_f64(T, B, *, side="L", lower=True, trans="N", unit=False,
             alpha=1.0, iters=2):
    """Triangular solve at f64-equivalent accuracy: f32-inverse seed +
    exact-residual iterative refinement.

    Each refinement step costs ONE exact limb product (the residual;
    first step rides the cheap bits=32 ladder rung) plus f32 MXU
    applies of the seed inverse — the r4 Newton-trtri composition paid
    ~4x that in exact nb^3 products and its emulated-f64 Newton chains
    dominated the dd LU/QR sweeps' per-step time (profiled r5).  Error
    contracts ~eps32*kappa(T) per step: 2 steps reach the kappa*eps64
    floor for condition to ~1e5 (the Newton path's ~1e7 envelope is
    kept for complex inputs, which stay on it).  Reads only the named
    triangle of T."""
    T = jnp.asarray(T, _wdtype(T))
    if jnp.iscomplexobj(T) or jnp.iscomplexobj(B):
        X = trtri_f64(T, lower=lower, unit=unit)
        if trans == "T":
            X = X.T
        elif trans == "C":
            X = X.conj().T
        out = mm(X, B) if side == "L" else mm(B, X)
        return alpha * out
    B = jnp.asarray(B, jnp.float64)
    f32 = jnp.float32
    Tm = _take_triangle(T, lower, unit)
    if trans in ("T", "C"):
        Tm = Tm.T
    n = Tm.shape[0]
    # power-of-two row prescale: keeps the f32 seed solve in range for
    # f64 magnitudes outside f32's span (as trtri_f64)
    m_ = jnp.max(jnp.abs(Tm), axis=1, keepdims=True)
    s = 0.25 * _pow2_scale_bits(jnp.where(m_ > 0, m_, 1.0))
    Ts = Tm / s                           # exact pow2 row scale
    lo_eff = lower != (trans in ("T", "C"))
    Xi = jax.lax.linalg.triangular_solve(
        Ts.astype(f32), jnp.eye(n, dtype=f32), left_side=True,
        lower=lo_eff)

    if side == "L":
        Bs = B / s                        # (S T') X = B  ->  T' X = S^-1 B
        # per-COLUMN power-of-two prescale of the rhs: each column
        # solves independently and X is linear in it, so B magnitudes
        # outside f32's range would otherwise Inf/flush the f32 seed
        # and every f32-cast correction (the _panel_lu_dd bug class,
        # review r3/r5)
        mB = jnp.max(jnp.abs(Bs), axis=0, keepdims=True)
        c = _pow2_scale_bits(jnp.where(mB > 0, mB, 1.0))
        Bs = Bs / c
        X = mm_f32(Xi, Bs.astype(f32)).astype(jnp.float64)
        for it in range(iters):
            bits = 32 if it == 0 and iters > 1 else 53
            E = gemm_residual(Bs, Ts, X, bits=bits)
            X = X + mm_f32(Xi, E.astype(f32)).astype(jnp.float64)
        X = X * c
    else:
        # X (S T') = B: solve Y T' = B for Y = X S, unscale exactly;
        # per-ROW rhs prescale for f32 range safety (independent rows)
        mB = jnp.max(jnp.abs(B), axis=1, keepdims=True)
        c = _pow2_scale_bits(jnp.where(mB > 0, mB, 1.0))
        Bc = B / c
        X = mm_f32(Bc.astype(f32), Xi).astype(jnp.float64)
        for it in range(iters):
            bits = 32 if it == 0 and iters > 1 else 53
            E = gemm_residual(Bc, X, Ts, bits=bits)
            X = X + mm_f32(E.astype(f32), Xi).astype(jnp.float64)
        X = (X * c) / s[:, 0][None, :]
    return alpha * X


# ---------------------------------------------------------------------
# Blocked FP64-equivalent Cholesky with limb-cached panels.
#
# The round-2 per-tile scheme (potrf_f64/trsm_f64 composed by the ops
# sweep) paid ~17 exact limb products per diagonal tile, re-ran the
# Newton inverse for every panel solve, and re-split finished panels on
# every consumption (VERDICT r2 weak #1).  This is the restructured
# design: the N^3/3 bulk rides limbs that are split ONCE per finished
# block column and cached, diagonal work is f32-seeded iterative
# refinement whose only exact products are residuals, and each column's
# panel solve multiplies by a single Newton-refined inverse.
# ---------------------------------------------------------------------


def _row_norm_scales(diag):
    """A-priori per-row power-of-two scales for the Cholesky factor:
    row i of L has 2-norm exactly sqrt(A_ii) (sum_j L_ij^2 = A_ii), so
    2^(ceil(log2 sqrt(A_ii)) + 1) bounds every entry of the row with a
    bit of headroom for rounding.  Sharing one scale per row across all
    block columns is what lets finished limbs concatenate into a single
    cache; norm-wise accuracy matches gemm_f64's row-max scaling (the
    error bound is ~K*eps64*||a_i||*||b_j|| either way, Cauchy-Schwarz).
    """
    v = jnp.sqrt(jnp.maximum(diag, jnp.finfo(jnp.float64).tiny))
    return _pow2_scale_bits(v)


def mm_f32(a, b):
    """f32 matmul at full f32 accuracy: the IR seeds and corrections
    contract by this product's error per step, and the TPU's DEFAULT
    precision is a single bf16 pass (~2^-8)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _ff_backend() -> bool:
    """Is f64 emulated as an f32 pair (the TPU x64 rewriter), limiting
    its range to f32's and forbidding f64 bitcasts?"""
    return jax.default_backend() == "tpu"


def _pow2_scale_bits(m):
    """floor(log2 m) + 2 power-of-two scale read from the exponent
    field (so |x| <= scale/2 for |x| <= m — the headroom both split
    implementations need; exponent clamped inside the normal range).
    The transcendental route (log2+exp2) costs ~1s of AOT compile per
    call site in f64 emulation (measured r3); this is a handful of
    bitcast integer ops.  True-f64 backends read the f64 exponent
    (full range); float-float backends read the f32 exponent — which
    IS their f64's range."""
    if not _ff_backend():
        p = jax.lax.bitcast_convert_type(
            jnp.asarray(m, jnp.float64), jnp.uint32)
        e = jnp.clip((p[..., 1] >> 20) & 0x7FF, 1, 0x7FC) + 2
        pair = jnp.stack([jnp.zeros_like(e), e << 20],
                         axis=-1).astype(jnp.uint32)
        return jax.lax.bitcast_convert_type(pair, jnp.float64)
    m32 = jnp.asarray(m).astype(jnp.float32)
    b = jax.lax.bitcast_convert_type(m32, jnp.uint32)
    # f32(m) may round up across a power-of-two boundary: that only
    # grows the scale by one more factor of 2 (safe, budgeted)
    e = jnp.clip((b >> 23) & 0xFF, 1, 0xFC) + 2
    s32 = jax.lax.bitcast_convert_type(
        (e << 23).astype(jnp.uint32), jnp.float32)
    return s32.astype(jnp.float64)


def _split_fixed(x, scale, w: int, nl: int):
    """Exact limb split with a caller-supplied per-row power-of-two
    scale (requires |x| <= scale/2 elementwise): x == scale *
    sum_l limbs[l] * 2^{-w(l+1)} up to the dropped tail
    < 2^{-w*nl+1}.

    Two implementations, both integer/f32-shaped — the f64-arithmetic
    trunc recurrence costs ~0.07s of AOT compile per emulated op and
    dominated the dd graphs' compile time (measured r3):

    * true-f64 backends: digits read straight from the f64 bit
      pattern (shifted mantissa windows);
    * MXU backends, where the x64 rewriter emulates f64 as an f32
      pair and cannot bitcast it: two exact f32 trunc chains on the
      hi/lo parts + one integer carry normalization
      (:func:`_split_fixed_ff`).
    """
    if _ff_backend():
        return _split_fixed_ff(x, scale, w, nl)
    p = jax.lax.bitcast_convert_type(x, jnp.uint32)   # [..., lo, hi]
    lo = p[..., 0].astype(jnp.int64)
    hi = p[..., 1].astype(jnp.int64)
    e_x = (hi >> 20) & 0x7FF
    mant = jnp.where(e_x > 0,
                     ((hi & 0xFFFFF) << 32) | lo | (1 << 52),
                     0)
    sgn = 1 - 2 * (hi >> 31)
    ps = jax.lax.bitcast_convert_type(jnp.asarray(scale, jnp.float64),
                                      jnp.uint32)
    e_s = (ps[..., 1].astype(jnp.int64) >> 20) & 0x7FF
    sh = e_x - e_s                    # <= -1 given |x| < scale; the
    # scale's broadcast shape rides the integer arithmetic
    mask = jnp.int64(2 ** w - 1)
    limbs = []
    for l in range(nl):
        t = 52 - sh - w * (l + 1)     # bit offset of the window LSB
        tpos = jnp.clip(t, 0, 63)
        tneg = jnp.clip(-t, 0, 63)
        d = ((mant >> tpos) << tneg) & mask
        limbs.append((sgn * d).astype(jnp.int8))
    return limbs


def _split_fixed_ff(x, scale, w: int, nl: int):
    """Digit split for float-float f64 backends: u = x/scale splits
    exactly into its native f32 hi/lo parts; each part is captured
    EXACTLY in two int32 fixed-point words (i1 = trunc(v*2^28),
    i2 = trunc((v*2^28 - i1)*2^28) — the pow2 products and the Dekker
    remainder are exact f32 operations for |v| < 1, and a 24-bit f32
    mantissa fits entirely in the 56 captured bits), then digits read
    off by integer shifts.  The previous f32 trunc recurrence compiled
    to ~2*nl unfusable select chains per split and dominated the
    blocked-dd op budget (profiled r4); this form is a handful of
    integer ops.  The two digit streams add with one integer carry
    pass into [-64, 63] (level 0 keeps its <= 66 headroom — carrying
    out of it would drop value).  On a true-f64 backend the lo part
    rounds to 24 bits, so this path is only selected where f64 IS an
    f32 pair (precision there equals the platform's own f64)."""
    assert 56 % w == 0 and (28 // w) * w == 28, w
    u = x / scale                    # exact: power-of-two divide
    uh = u.astype(jnp.float32)
    ul = (u - uh.astype(jnp.float64)).astype(jnp.float32)
    two28 = jnp.float32(2.0 ** 28)

    def digits(v):
        # sign-magnitude: window shifts on the magnitude words match
        # the trunc recurrence's toward-zero semantics (an arithmetic
        # shift on a negative word would floor, breaking exactness)
        i1f = jnp.trunc(v * two28)
        i2 = jnp.abs(((v * two28 - i1f) * two28)).astype(jnp.int32)
        i1 = jnp.abs(i1f).astype(jnp.int32)
        sgn = jnp.where(v < 0, jnp.int32(-1), jnp.int32(1))
        ds = []
        for l in range(nl):
            word, off = (i1, 28) if l < 28 // w else (i2, 56)
            sh = off - w * (l + 1)
            ds.append(sgn * ((word >> sh) & ((1 << w) - 1)))
        return ds

    d = [a + b for a, b in zip(digits(uh), digits(ul))]
    half = 1 << (w - 1)
    out = [None] * nl
    for l in range(nl - 1, 0, -1):
        k = (d[l] + half) >> w
        out[l] = d[l] - (k << w)
        d[l - 1] = d[l - 1] + k
    out[0] = d[0]
    return [o.astype(jnp.int8) for o in out]


def _pair_dot_base(al, bl, base, sa, sb, K: int, w: int, nl: int,
                   kc: int):
    """``base - (sa*sb) * pair-dot`` with the epilogue fused (the
    trailing-update form of the blocked sweeps)."""
    levels = _limb_levels(al, bl, K, w, nl, kc, lhs_t=True)
    return _recombine_scale_base(levels, base, sa, sb, w)


def _pair_dot(al, bl, K: int, w: int, nl: int, kc: int):
    """Unscaled limb product sum_l 2^{-w(l+2)} sum_{i+j=l}
    al[i]^T @ bl[j]: ``al`` (K, M) and ``bl`` (K, N) — both K-major,
    the slice layout of the TRANSPOSED factor-limb cache Wt[l, col,
    row] (one cache serves both operands; measured r5: the MXU runs
    this at 333-387 TOPS where the r4 row-major cache's minor-axis rhs
    contraction got 29-175)."""
    return _level_recombine(
        _limb_levels(al, bl, K, w, nl, kc, lhs_t=True), w)


def _potrf_tile_ir(Akk, refine: int = 3, newton: int = 2,
                   need_inverse: bool = True,
                   refine_bits=(32, 53, 53)):
    """Diagonal-tile Cholesky + inverse at f64 accuracy, limb-lean.

    f32 Cholesky seeds; each refinement step's only exact product is
    the residual E = A - L L^T (corrections ride f32 triangular solves
    and matmuls — their error is second order).  IR contracts the
    factor error by ~eps32*kappa per step, so the FIRST residual may
    ride the cheap bits=32 product (its 2^-32 noise floor is below the
    seed error it corrects); later steps must be bits=53 or the
    refinement stalls at kappa*2^-32 (``refine_bits`` ladder).  The
    Newton inverse keeps BOTH its residual and its apply exact, so the
    eps32*kappa seed error squares per iteration ((eps32*kappa)^4 <
    eps64 for tile condition up to ~2e3; library callers needing more
    headroom use trtri_f64).  Returns (L, X ~= L^{-1}), lower, real
    f64.
    """
    n = Akk.shape[0]
    Af = jnp.tril(Akk) + jnp.tril(Akk, -1).T
    # symmetric power-of-two prescale (exact): keeps the f32 seeds in
    # range for diagonals outside f32's span (review r3); A = D A' D
    # with D = 2^round(log2 sqrt(a_ii)), so L = D L', X = X' D^{-1}
    dg = jnp.diagonal(Af)
    d = 0.25 * _pow2_scale_bits(
        jnp.sqrt(jnp.where(dg > 0, dg, 1.0)))
    Af = Af / (d[:, None] * d[None, :])
    L = jax.lax.linalg.cholesky(
        Af.astype(jnp.float32), symmetrize_input=False)
    # ONE f32 inverse up front; the IR rounds then run on MXU matmuls
    # only (triangular_solve custom calls measured ~1.5 TF/s on wide
    # rhs, a top line of the blocked-dd budget — profiled r4). X's
    # eps32*kappa error perturbs the correction at second order only.
    X32 = jax.lax.linalg.triangular_solve(
        jnp.tril(L), jnp.eye(n, dtype=jnp.float32), left_side=True,
        lower=True)
    L = jnp.tril(L).astype(jnp.float64)
    f32 = jnp.float32
    for r in range(refine):
        bits = refine_bits[min(r, len(refine_bits) - 1)]
        E = gemm_residual(Af, L, L.T, bits=bits)
        L32 = jnp.tril(L).astype(f32)
        Y = mm_f32(X32, E.astype(f32))
        M = mm_f32(Y, X32.T)
        phi = jnp.tril(M, -1) + 0.5 * jnp.diag(jnp.diag(M))
        corr = mm_f32(L32, phi)
        L = jnp.tril(L + corr.astype(jnp.float64))
    if not need_inverse:   # panel rides the trsm-IR path instead
        return L * d[:, None], None
    eye = jnp.eye(n, dtype=jnp.float64)
    X = jax.lax.linalg.triangular_solve(
        L.astype(jnp.float32), jnp.eye(n, dtype=jnp.float32),
        left_side=True, lower=True).astype(jnp.float64)
    for _ in range(newton):
        R = eye - gemm_f64(L, X)
        X = jnp.tril(X + gemm_f64(X, R))
    return L * d[:, None], X / d[None, :]


def _panel_trsm_ir(Lkk, slab, iters: int = 2):
    """Panel solve pan @ Lkk^T = slab at f64-equivalent accuracy via
    multiply-by-f32-inverse + exact-residual iterative refinement.

    Each IR step costs ONE exact (m, nb, nb) limb product and one f32
    MXU matmul by the tile inverse (a wide-rhs triangular_solve custom
    call measured ~1.5 TF/s vs ~25 TF/s for the matmul — profiled r4;
    the inverse's own eps32*kappa error perturbs corrections at second
    order only).  The factor error contracts by ~eps32*kappa(Lkk) per
    step, so 2 steps from the f32 seed reach the kappa*eps64 floor for
    tile condition to ~1e7.
    """
    f32 = jnp.float32
    L32 = jnp.tril(Lkk).astype(f32)
    Xt = jax.lax.linalg.triangular_solve(
        L32, jnp.eye(L32.shape[0], dtype=f32), left_side=True,
        lower=True).T                     # L^{-T}, f32

    def rsolve(b):
        return mm_f32(b, Xt)

    pan = rsolve(slab.astype(f32)).astype(jnp.float64)
    for it in range(iters):
        # first residual rides the cheap bits=32 product: its 2^-32
        # noise floor sits below the eps32 seed error it corrects
        # (the same ladder argument as _potrf_tile_ir's refine_bits)
        bits = 32 if it == 0 and iters > 1 else 53
        E = gemm_residual(slab, pan, Lkk.T, bits=bits)
        pan = pan + rsolve(E.astype(f32)).astype(jnp.float64)
    return pan


@partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _cache_write(W, limbs, s: int):
    """In-place (donated) limb-cache column write. ``W`` is the
    TRANSPOSED cache Wt[l, col, row] (nl, N-nb, N); ``limbs`` arrive
    ALREADY transposed as (nl, nb, N) — _jit_panel splits colL.T so
    the transpose fuses into the split's elementwise chain (an
    explicit post-split int8 transpose measured ~95 ms/step) — and
    land at Wt[:, s:s+nb, s:], so trail slices contract K-major on
    the MXU (measured r5: 9x on early skinny-K steps). Row extent is
    clipped inside the executable, so no eager slice of a big array
    is dispatched on its own."""
    from dplasma_tpu.observability import phases
    with phases.span("split", timed=False):
        N = W.shape[2]
        lim = jax.lax.slice_in_dim(limbs, 0, N - s, axis=2)
        return jax.lax.dynamic_update_slice(W, lim, (0, s, s))


@partial(jax.jit, static_argnums=(3, 4))
def _jit_panel(slab, scale, s, nb: int, refine: int):
    """One blocked-Cholesky panel at FIXED (N, nb) shape (rows below
    the real N-s are zero): diagonal tile IR + trsm-IR panel solve +
    the column's limb split. ``s`` is a DYNAMIC offset — the per-row
    scales are rolled so row i sees scale[s+i] (the wrap rows land on
    zero pad content). Compiles ONCE per (N, nb) and is reused by
    every panel of every sweep at that size — the r3 unrolled graphs
    recompiled this shape-identical subgraph nt times and the AOT
    helper was OOM-killed at N=8192 (VERDICT r4 item 2)."""
    from dplasma_tpu.observability import phases
    w, nl, _ = _plan(slab.shape[0], 53)
    sc = jnp.roll(scale, -s, axis=0)
    with phases.span("panel", timed=False):
        Lkk, _ = _potrf_tile_ir(slab[:nb], refine=refine,
                                need_inverse=False)
        pan = _panel_trsm_ir(Lkk, slab[nb:])
        colL = jnp.concatenate([Lkk, pan], axis=0)
    # split the TRANSPOSE: the cache stores Wt[l, col, row], and an
    # explicit post-split int8 transpose measured ~95 ms/step at
    # N=16384 (byte-granularity shuffles); transposing the f64 operand
    # fuses into the split's elementwise chain instead
    with phases.span("split", timed=False):
        limbs = jnp.stack(_split_fixed(colL.T, sc[:, 0][None, :], w, nl))
    return colL, limbs


@partial(jax.jit, static_argnums=(1,))
def _jit_slab0(A, nb: int):
    return jax.lax.slice(A, (0, 0), (A.shape[0], nb))


@partial(jax.jit, static_argnums=(2, 3), donate_argnums=(0,))
def _jit_colwrite(out, colL, s: int, nb: int):
    """Write finished column block (rows clipped) into the result."""
    from dplasma_tpu.observability import phases
    with phases.span("assemble", timed=False):
        N = out.shape[0]
        c = jax.lax.slice_in_dim(colL, 0, N - s, axis=0)
        return jax.lax.dynamic_update_slice(out, c, (s, s))


@partial(jax.jit, static_argnums=(1,))
def _jit_tile(slab, refine: int):
    from dplasma_tpu.observability import phases
    nb = slab.shape[1]
    with phases.span("panel", timed=False):
        return _potrf_tile_ir(slab[:nb], refine=refine,
                              need_inverse=False)[0]


@partial(jax.jit, static_argnums=(3, 4))
def _jit_trail(A, W, scale, s: int, nb: int):
    """A[s:, s:s+nb] - (pair-dot of cached limbs) * outer(scales):
    the N^3/3 bulk. ``W`` is the transposed cache Wt[l, col, row] —
    lhs (K, M) and rhs (K, nb) slices come K-major off the same
    column band Wt[:, :s, s:]. Full arrays in, slicing INSIDE the
    executable (no eager big-array slice dispatched on its own); one
    executable per s."""
    from dplasma_tpu.observability import phases
    N = A.shape[0]
    K = s
    w, nl, kc = _plan(K, 53)
    with phases.span("update", timed=False):
        band = jax.lax.slice(W, (0, 0, s), (nl, K, N))   # (nl, K, N-s)
        slabA = jax.lax.slice(A, (s, s), (N, s + nb))
        out = _pair_dot_base([band[i] for i in range(nl)],
                             [jax.lax.slice_in_dim(band[i], 0, nb, axis=1)
                              for i in range(nl)], slabA, scale[s:],
                             scale[s:s + nb].T, K=K, w=w, nl=nl, kc=kc)
        # fixed (N, nb) for _jit_panel
        return jnp.pad(out, ((0, s), (0, 0)))


def _potrf_f64_blocked_cached(A, nb: int, refine: int):
    """Python-orchestrated blocked dd Cholesky over shape-cached
    executables (the eager-mode twin of the traced path below; exact
    same math). One ~(N,nb) panel compile + nt cheap int8 trail
    compiles replace the monolithic unrolled graph. Dispatch is async:
    the ~50 enqueues per factorization pipeline behind the device."""
    N = A.shape[0]
    nt = N // nb
    w, nl, _ = _plan(N, 53)
    scale = _row_norm_scales(jnp.diag(A))[:, None]
    W = jnp.zeros((nl, N - nb, N), jnp.int8)   # transposed: [l, col, row]
    out = jnp.zeros((N, N), jnp.float64)
    for k in range(nt):
        s = k * nb
        slab = (_jit_trail(A, W, scale, s, nb) if k
                else _jit_slab0(A, nb))          # (N, nb), zero tail
        if s + nb < N:
            colL, limbs = _jit_panel(slab, scale, s, nb, refine)
            out = _jit_colwrite(out, colL, s, nb)
            if k + 1 < nt:
                W = _cache_write(W, limbs, s)
        else:
            out = _jit_colwrite(out, _jit_tile(slab, refine), s, nb)
    return out


def potrf_f64_blocked(A, nb: int = 512, lower: bool = True,
                      refine: int = 2):
    """Blocked left-looking Cholesky at f64-equivalent accuracy.

    Step k updates block column k with ONE chunked limb product against
    the cached limbs of all finished columns (the N^3/3 bulk — the only
    O(N^3) exact work), factors the diagonal tile by f32+IR, and solves
    the panel by multiplying with the tile's Newton inverse.  Finished
    columns are split once (shared a-priori row scales, see
    _row_norm_scales) and appended to the cache.

    Reads only the ``lower``/upper triangle (stored-triangle contract);
    requires square A with N divisible by nb (ops-level callers pad).
    Real f64 only — c128 stays on the per-tile kernels.
    """
    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            "potrf_f64_blocked requires jax_enable_x64 (inputs would "
            "silently truncate to f32, breaking the FP64 contract)")
    A = jnp.asarray(A, jnp.float64)
    if not lower:
        # A = U^T U with U = L^T: factor the transpose (reads its lower
        # triangle = our stored upper) and return L^T.
        return potrf_f64_blocked(A.T, nb=nb, lower=True,
                                 refine=refine).T
    N = A.shape[0]
    assert A.shape[1] == N and N % nb == 0, (A.shape, nb)
    nt = N // nb
    if nt <= 1:
        return _potrf_tile_ir(A, refine=refine, need_inverse=False)[0]
    if utils.is_concrete(A):
        # eager callers ride the shape-cached executables: same math,
        # one panel compile reused across all nt panels (the unrolled
        # graph costs ~20s AOT per panel at N=8192 — VERDICT r4 item 2)
        return _potrf_f64_blocked_cached(A, nb, refine)
    from dplasma_tpu.observability import phases
    w, nl, kc = _plan(N, 53)
    scale = _row_norm_scales(jnp.diag(A))[:, None]
    # preallocated stacked limb cache, TRANSPOSED layout (nl, N-nb, N)
    # = Wt[l, col, row]: trail products then contract K-major on both
    # operands (measured r5: 29-175 TOPS for the row-major cache's
    # minor-axis rhs vs 333-387 transposed). Column blocks are written
    # in place by dynamic_update_slice — a growing concat re-copies
    # the whole cache every step (~4 GB of traffic at N=8192,
    # profiled r4)
    W = jnp.zeros((nl, N - nb, N), jnp.int8)
    cols = []
    for k in range(nt):
        s = k * nb
        slab = A[s:, s:s + nb]
        if k:
            with phases.span("update", timed=False):
                slab = _pair_dot_base(
                    [W[i, :s, s:] for i in range(nl)],
                    [W[i, :s, s:s + nb] for i in range(nl)], slab,
                    scale[s:], scale[s:s + nb].T, K=s, w=w, nl=nl,
                    kc=kc)
        with phases.span("panel", timed=False):
            Lkk, _ = _potrf_tile_ir(slab[:nb], refine=refine,
                                    need_inverse=False)
            if s + nb < N:
                # trsm + exact-residual IR replaces the Newton-inverse
                # panel (3x fewer exact nb^3 products per column; the
                # op count, not the flops, bounded the r3 sweep)
                pan = _panel_trsm_ir(Lkk, slab[nb:])
                colL = jnp.concatenate([Lkk, pan], axis=0)
            else:
                colL = Lkk
        cols.append(colL)
        if k + 1 < nt:
            with phases.span("split", timed=False):
                limbs = jnp.stack(_split_fixed(colL.T, scale[s:].T, w,
                                               nl))
                W = jax.lax.dynamic_update_slice(W, limbs, (0, s, s))
    with phases.span("assemble", timed=False):
        out = [jnp.concatenate(
            [jnp.zeros((j * nb, nb), jnp.float64), c], axis=0)
            for j, c in enumerate(cols)]
        return jnp.concatenate(out, axis=1)


# ---------------------------------------------------------------------
# FP64-equivalent LU and QR panel kernels (f32 seeds + limb-exact IR) —
# the d-precision analogues of CORE_zgetrf_rectil / CORE_zgeqrt for the
# blocked sweeps in ops.lu / ops.qr.  Only residuals ride exact limb
# products; every correction solve/product is f32 (second order).
# ---------------------------------------------------------------------


def lu_ir(pp, L, U, refine: int = 4, bits: int | None = None):
    """Refine a seed factorization pp ~= L U to f64-equivalent accuracy
    (pp is the already-row-permuted panel, L (m,nb) unit-lower
    trapezoidal, U (nb,nb) upper). ``bits`` pins EVERY residual to one
    limb-ladder rung (the mixed-precision IR solvers' f32x2 working
    factorization runs one step at bits=32); None keeps the default
    32,32,53,... ladder.

    Correction step: with exact E = pp - L U, G = L1^{-1} E1 U^{-1}
    gives dU = triu(G) U, dL1 = L1 stril(G) (so dL1 U + L1 dU = E1),
    and dL2 = (E2 - L2 dU) U^{-1} for the rows below.  ONLY the
    residual E rides an exact limb product (first two steps on the
    cheap bits=32 rung — their 2^-32 noise floor sits below the
    corrections they drive); every solve/product is f32 against the
    SEED inverses, capping contraction at ~eps32*kappa per step
    (measured ~1/100), which refine=4 turns into ~1e-8 of the seed
    error — at or below the kappa*eps64 floor for panel condition to
    ~1e5.  The r4 form Newton-refined BOTH factor inverses to f64
    inside every step (~4x the exact products, and its emulated-f64
    chains dominated the dd LU sweep's per-step time — profiled r5).
    """
    nb = U.shape[0]
    f32 = jnp.float32
    n_ = jnp.arange(nb)
    L1_32 = jnp.tril(L[:nb], -1).astype(f32).at[n_, n_].set(1.0)
    U32 = jnp.triu(U).astype(f32)
    eye = jnp.eye(nb, dtype=f32)
    L1i = jax.lax.linalg.triangular_solve(
        L1_32, eye, left_side=True, lower=True, unit_diagonal=True)
    # exactly-singular panels (legal: LAPACK completes with a zero U
    # diagonal and INFO>0) must not NaN-poison the refinement — the
    # guarded inverse is finite, the singular column's residual is
    # zero, so its (garbage-direction) correction vanishes and the
    # honest zero diagonal survives for INFO detection
    dg = jnp.diagonal(U32)
    Ui = jax.lax.linalg.triangular_solve(
        U32.at[n_, n_].set(jnp.where(dg == 0, 1.0, dg)), eye,
        left_side=True, lower=False)

    for r in range(refine):
        rbits = bits if bits is not None \
            else (32 if (r < 2 and refine > 2) else 53)
        E = gemm_residual(pp, L, U, bits=rbits)
        E32 = E.astype(f32)
        G = mm_f32(mm_f32(L1i, E32[:nb]), Ui)
        dU = mm_f32(jnp.triu(G), U32)
        dL1 = mm_f32(L1_32, jnp.tril(G, -1))
        if L.shape[0] > nb:
            dL2 = mm_f32(E32[nb:] - mm_f32(L[nb:].astype(f32), dU), Ui)
            dL = jnp.concatenate([dL1, dL2], axis=0)
        else:
            dL = dL1
        L = jnp.tril(L + dL.astype(jnp.float64), -1).at[n_, n_].set(1.0)
        U = jnp.triu(U + dU.astype(jnp.float64))
    return L, U


def geqrt_f64(panel):
    """Panel QR at f64-equivalent accuracy: CholeskyQR2 in limb
    arithmetic + Householder reconstruction (Ballard et al. TSQR-HR —
    the same construction kernels.householder uses for f32, here with
    every heavy product exact and every small factorization f32+IR).

    Returns (packed, V, T) in the CORE_zgeqrt layout.  Real f64;
    requires a numerically full-rank panel with cond below ~1e5 (the
    Gram matrix squares the condition and its Cholesky seeds in f32;
    the lean f32-correction IR in the reconstruction solves contracts
    ~eps32*kappa per step — MCA ``qr_panel=lapack`` keeps the slow
    rank-safe vendor panel for harder panels).
    """
    m, nb = panel.shape
    eps32 = float(jnp.finfo(jnp.float32).eps)

    def cholqr_pass(x, shift):
        G = gemm_f64(x.T, x)
        if shift:
            s = (11.0 * (m * nb + nb * (nb + 1))) * eps32
            G = G + (s * jnp.trace(G)) * jnp.eye(nb, dtype=G.dtype)
        Lg, Xg = _potrf_tile_ir(G)
        return gemm_f64(x, Xg.T), Lg.T   # (q, r) with r = Lg^T

    q, r1 = cholqr_pass(panel, True)
    q, r2 = cholqr_pass(q, False)
    return _tsqrhr_f64(q, gemm_f64(r2, r1))


def _tsqrhr_f64(q, r):
    """TSQR-HR tail shared by the cholqr and tree dd panels: recover
    compact-WY ``(packed, V, T)`` from a dd-accurate thin (q, r).  The
    sign/shift convention and packed layout are SHARED with the f32
    path (kernels.householder) so the two implementations cannot
    drift; only the product/LU/inverse kernels differ (limb-exact
    here)."""
    m, nb = q.shape
    from dplasma_tpu.kernels import blas as _kb
    from dplasma_tpu.kernels import householder as _hh
    s, b = _hh.reconstruct_sign_shift(q)
    p32 = _kb.getrf_nopiv_blocked(b[:nb].astype(jnp.float32))
    V1 = jnp.tril(p32.astype(jnp.float64), -1) + jnp.eye(nb)
    Ub = jnp.triu(p32).astype(jnp.float64)
    V1, Ub = lu_ir(b[:nb], V1, Ub)
    if m > nb:
        # V2 Ub = b2: right IR solve (one exact product per step —
        # the r4 Newton trtri cost ~4x that, profiled r5)
        V2 = trsm_f64(Ub, b[nb:], side="R", lower=False)
        v = jnp.concatenate([V1, V2], axis=0)
    else:
        v = V1
    # T = -(Ub S^{-1}) V1^{-T} (S^{-1} = S, unimodular real):
    # t V1^T = -(Ub S) as a right transposed IR solve
    t = trsm_f64(V1, -(Ub * s[None, :]), side="R", lower=True,
                 trans="T", unit=True)
    packed = _hh.reconstruct_pack(s, r, v, nb)
    return packed, v, t


def geqrt_f64_tree(panel, solve_iters: int = 3):
    """Tree-seeded dd panel QR: the TSQR/CAQR variant of
    :func:`geqrt_f64` (MCA ``panel.kernel tree`` on the dd route).

    The first limb CholeskyQR pass — two full-height exact products
    over an ill-conditioned panel — is replaced by an R-only f32 TSQR
    tree (:func:`dplasma_tpu.kernels.panels.tsqr` with
    ``need_q=False``: cheap batched f32 leaf QRs + the log-depth
    R reduction, no push-down) whose root R conditions ONE
    exact-residual IR right-solve ``q1 R32 = panel`` (~1.4
    full-height limb products at ``solve_iters=3`` vs the pass's 2).
    The second (unshifted) limb CholeskyQR pass then restores
    orthogonality at dd accuracy, and the shared TSQR-HR tail
    recovers ``(packed, V, T)``.  Same envelope as
    :func:`geqrt_f64`: numerically full-rank panels, cond below
    ~1e5.
    """
    from dplasma_tpu.kernels import panels as _panels
    # power-of-two COLUMN prescale keeps the f32 tree seed in range
    # for f64 magnitudes outside f32's span (column scaling leaves Q
    # invariant: only R unscales, exactly)
    m_ = jnp.max(jnp.abs(panel), axis=0, keepdims=True)
    d = 4.0 / _pow2_scale_bits(jnp.where(m_ > 0, m_, 1.0))
    As = panel * d
    _, r32 = _panels.tsqr(As.astype(jnp.float32), need_q=False)
    r1 = jnp.triu(r32).astype(jnp.float64)
    # pass 1: q1 = As r1^{-1} by exact-residual IR (f32-inverse seed)
    q1 = trsm_f64(r1, As, side="R", lower=False, iters=solve_iters)
    # pass 2: unshifted limb CholeskyQR on the near-orthonormal q1
    G = gemm_f64(q1.T, q1)
    Lg, Xg = _potrf_tile_ir(G)
    q = gemm_f64(q1, Xg.T)
    r = gemm_f64(Lg.T, r1) / d          # exact pow2 column unscale
    return _tsqrhr_f64(q, r)


def potrf_f64(A, lower: bool = True, refine: int = 3):
    """Cholesky of one tile at f64-equivalent accuracy.

    L0 = chol(f32(A)) seeds; each refinement step computes the exact
    residual E = A - L L^H (limb matmul), maps it through the factor
    inverse M = L^{-1} E L^{-H}, and applies the first-order correction
    L <- L (I + Phi(M)), Phi = strict-lower + half-diagonal. Error
    contracts ~300-1000x per step from an eps32 seed (measured);
    refine=3 reaches reference-threshold residuals to kappa ~ 1e6.
    Reads only the ``lower``/upper triangle of ``a`` (stored-triangle
    contract, as kernels.blas.potrf).
    """
    A = jnp.asarray(A, _wdtype(A))
    if not lower:
        return _ct(potrf_f64(_ct(A), lower=True, refine=refine))
    # full Hermitian from the stored lower triangle
    Afull = jnp.tril(A) + _ct(jnp.tril(A, -1))
    f32t = jnp.complex64 if jnp.iscomplexobj(A) else jnp.float32
    L = jax.lax.linalg.cholesky(
        Afull.astype(f32t), symmetrize_input=False).astype(A.dtype)
    X = trtri_f64(L, lower=True)
    for _ in range(refine):
        E = Afull - mm(L, _ct(L))
        M = mm(mm(X, E), _ct(X))
        phi = jnp.tril(M, -1) + 0.5 * jnp.diag(jnp.diag(M))
        L = L + mm(L, phi)
        L = jnp.tril(L)
    return L
