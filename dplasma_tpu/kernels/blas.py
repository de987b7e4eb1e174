"""Tile-level compute kernels (the CORE_z* substrate).

The reference's sequential CPU tile kernels (``src/cores/*.c``, PLASMA
descended: CORE_zgemm/ztrsm/zherk/zpotrf — ref src/cores/CMakeLists.txt)
become, on TPU:

- MXU matmuls via ``jax.lax.dot_general`` with explicit precision control
  (bf16x3/x6 passes for f32, "highest" for correctness-critical paths);
- ``lax.linalg`` primitives for small dense factorizations on a tile;
- Pallas kernels (``kernels/pallas``) for the hot fused paths.

Everything here is shape-static and jit-traceable; matrix-level blocked
algorithms in ``ops/`` compose these over tiles/panels.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from dplasma_tpu.resilience import inject as _inject

# Global matmul precision for f32 inputs on TPU. "highest" = full f32
# accumulate via multi-pass bf16 (correctness first; benches may lower it).
_PRECISION = lax.Precision.HIGHEST


def set_precision(p):
    global _PRECISION
    _PRECISION = p


def get_precision():
    return _PRECISION


def _acc_type(dtype):
    """Accumulator type for MXU products."""
    if dtype in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    return dtype


def _dd_active(dtype) -> bool:
    """Should f64/c128 matmuls route through the Ozaki limb GEMM?

    MCA ``dd_gemm``: ``auto`` (TPU only — where native f64 matmul is
    slow scalar emulation, ~2.5x slower than the limb path), ``always``
    (any backend; lets the CPU test mesh exercise the exact wiring the
    TPU uses), ``never``.
    """
    if dtype not in (jnp.float64, jnp.complex128):
        return False
    from dplasma_tpu.utils import config as _cfg

    mode = (_cfg.mca_get("dd_gemm") or "auto").lower()
    if mode == "never":
        return False
    if mode == "always":
        return True
    return jax.default_backend() == "tpu"


def _dd_dot(a, b):
    """f64/c128 matmul via exact bf16 limb GEMM (kernels.dd)."""
    from dplasma_tpu.kernels import dd as _dd

    return _dd.mm(a, b)


def dot(a, b, ta: bool = False, tb: bool = False, conj_a: bool = False,
        conj_b: bool = False):
    """op(a) @ op(b) with precision/accumulator control.

    ``ta``/``tb`` transpose; ``conj_*`` conjugate (for the C/Z cases the
    reference enumerates as dplasmaNoTrans/Trans/ConjTrans). d/z dtypes
    on MXU hardware route through the FP64-equivalent limb GEMM
    (kernels.dd) — the d-precision CORE_zgemm role, ref
    src/cores/CMakeLists.txt + zpotrf_wrapper.c:8 "@precisions ... d".
    """
    res_dtype = jnp.result_type(a.dtype, b.dtype)
    a = a.astype(res_dtype)
    b = b.astype(res_dtype)
    if conj_a:
        a = a.conj()
    if conj_b:
        b = b.conj()
    if ta:
        a = a.T
    if tb:
        b = b.T
    if _dd_active(res_dtype):
        return _inject.tap("gemm", _dd_dot(a, b))
    from dplasma_tpu.kernels import pallas_kernels as _pk
    if _pk.eligible(a, b):
        return _inject.tap(
            "gemm", _pk.matmul(a, b, precision=_PRECISION).astype(res_dtype))
    out = jnp.matmul(a, b, precision=_PRECISION,
                     preferred_element_type=_acc_type(res_dtype))
    return _inject.tap("gemm", out.astype(res_dtype))


def dot_in(a, b, dtype=None):
    """``a @ b`` with both operands rounded to ``dtype`` and the product
    accumulated in ``a``'s dtype: one MXU pass for bf16, the trailing
    updates of a factorization whose error a refinement removes.
    ``dtype`` None is :func:`dot`."""
    if dtype is None:
        return dot(a, b)
    out = jnp.matmul(a.astype(dtype), b.astype(dtype),
                     preferred_element_type=a.dtype)
    return _inject.tap("gemm", out)


def gemm(alpha, a, b, beta, c, ta=False, tb=False, conj_a=False, conj_b=False):
    """C = alpha op(A) op(B) + beta C (CORE_zgemm semantics).

    Dispatches to the fused Pallas kernel (one HBM round-trip for C) when
    enabled and eligible; falls back to XLA matmul + axpy otherwise.
    """
    from dplasma_tpu.kernels import pallas_kernels as _pk
    if (not (conj_a or conj_b) and isinstance(alpha, (int, float))
            and isinstance(beta, (int, float))):
        aa = a.T if ta else a
        bb = b.T if tb else b
        if _pk.eligible(aa, bb, c):
            return _inject.tap(
                "gemm", _pk.gemm(aa, bb, c, alpha=float(alpha),
                                 beta=float(beta), precision=_PRECISION))
    return alpha * dot(a, b, ta, tb, conj_a, conj_b) + beta * c


def tri(x, lower: bool = True, unit: bool = False):
    """Extract the named triangle (optionally with unit diagonal),
    non-square safe. Shared by trmm/trsm/lantr/blas3."""
    t = jnp.tril(x) if lower else jnp.triu(x)
    if unit:
        r = jnp.arange(x.shape[0])[:, None]
        c = jnp.arange(x.shape[1])[None, :]
        t = jnp.where(r == c, jnp.ones((), x.dtype), t)
    return t


def potrf(a, lower: bool = True):
    """Cholesky of one tile (CORE_zpotrf). Reads ONLY the ``lower``/upper
    triangle of ``a`` (the opposite triangle may hold scratch, per the
    reference's stored-triangle contract); returns the triangular factor
    with the opposite triangle zeroed."""
    if _dd_active(a.dtype):
        from dplasma_tpu.kernels import dd as _dd
        return _inject.tap("potrf", _dd.potrf_f64(a, lower=lower))
    if lower:
        return _inject.tap(
            "potrf", lax.linalg.cholesky(a, symmetrize_input=False))
    # upper storage: the Hermitian matrix's lower representation is a^H;
    # A = U^H U with U = chol(a^H)^H
    return _inject.tap(
        "potrf",
        lax.linalg.cholesky(a.conj().T, symmetrize_input=False).conj().T)


def _inv_trsm_active() -> bool:
    """Should trsm run as (triangular inverse) x (matmul)?

    Inverting the nb-sized triangle once (cheap solve against the
    identity) and multiplying is the trick cuBLAS trsm uses internally.
    MCA ``trsm_inv``: ``auto``/``never`` use the native solve —
    an A/B grid over all side/uplo/trans configs measured XLA's native
    solve at 8-44 TF/s vs 6-13 for the inverse form on current MXU
    hardware (only L/upper/T favors inv) — ``always`` forces the
    inverse form (any dtype), kept as a per-algorithm tuning knob.
    """
    from dplasma_tpu.utils import config as _cfg

    return (_cfg.mca_get("trsm_inv") or "auto").lower() == "always"


def trsm(a, b, *, side="L", lower=True, trans="N", unit=False, alpha=1.0):
    """Triangular solve: solves op(A) X = alpha B (side=L) or
    X op(A) = alpha B (side=R). CORE_ztrsm semantics."""
    if _dd_active(jnp.result_type(a.dtype, b.dtype)):
        from dplasma_tpu.kernels import dd as _dd
        return _inject.tap(
            "trsm", _dd.trsm_f64(a, b, side=side, lower=lower, trans=trans,
                                 unit=unit, alpha=alpha))
    transpose = trans in ("T", "C")
    conj = trans == "C"
    if _inv_trsm_active():
        n = a.shape[0]
        inv_op = lax.linalg.triangular_solve(
            a, jnp.eye(n, dtype=a.dtype),
            left_side=True, lower=lower, transpose_a=transpose,
            conjugate_a=conj, unit_diagonal=unit)
        if side == "L":
            return _inject.tap("trsm", dot(inv_op, alpha * b))
        return _inject.tap("trsm", dot(alpha * b, inv_op))
    x = lax.linalg.triangular_solve(
        a, alpha * b,
        left_side=(side == "L"),
        lower=lower,
        transpose_a=transpose,
        conjugate_a=conj,
        unit_diagonal=unit,
    )
    return _inject.tap("trsm", x)


def trmm(a, b, *, side="L", lower=True, trans="N", unit=False, alpha=1.0):
    """Triangular matrix multiply B = alpha op(A) B (or B op(A))."""
    t = tri(a, lower=lower, unit=unit)
    if trans == "T":
        t = t.T
    elif trans == "C":
        t = t.conj().T
    if side == "L":
        return alpha * dot(t, b)
    return alpha * dot(b, t)


def syrk(alpha, a, beta, c, *, lower=True, trans="N"):
    """C = alpha A A^T + beta C, symmetric rank-k (triangle-correct on the
    full tile; callers keep only the relevant triangle)."""
    if trans == "N":
        upd = dot(a, a, tb=True)
    else:
        upd = dot(a, a, ta=True)
    return alpha * upd + beta * c


def herk(alpha, a, beta, c, *, lower=True, trans="N"):
    """C = alpha A A^H + beta C (Hermitian rank-k)."""
    if trans == "N":
        upd = dot(a, a, tb=True, conj_b=True)
    else:
        upd = dot(a, a, ta=True, conj_a=True)
    return alpha * upd + beta * c


def getrf_nopiv(a):
    """LU without pivoting of one tile (CORE_zgetrf_nopiv): returns packed
    L\\U (unit L implicit). On the TPU an eligible f32 tile is factored
    in one Pallas kernel (:func:`~dplasma_tpu.kernels.pallas_lu.
    lu_nopiv_tile`); elsewhere, and for other tiles, by an XLA loop of
    rank-1 updates."""
    from dplasma_tpu.kernels import pallas_lu
    if jax.default_backend() == "tpu" and pallas_lu.nopiv_eligible(a):
        return _inject.tap("getrf", pallas_lu.lu_nopiv_tile(a))
    n = a.shape[0]

    def body(k, m):
        col = m[:, k]
        piv = m[k, k]
        scale = jnp.where(jnp.arange(m.shape[0]) > k, 1.0 / piv, 0.0)
        l = col * scale.astype(m.dtype)
        row = jnp.where(jnp.arange(m.shape[1]) > k, m[k, :], 0.0)
        m = m - jnp.outer(l, row).astype(m.dtype)
        m = m.at[:, k].set(jnp.where(jnp.arange(m.shape[0]) > k, l, m[:, k]))
        return m

    return _inject.tap("getrf", lax.fori_loop(0, min(a.shape), body, a))


def getrf_nopiv_blocked(a, base: int = 32):
    """Blocked-recursive LU without pivoting: packed L\\U of a square
    tile. Same contract as :func:`getrf_nopiv`, but the O(n) sequential
    rank-1 loop only runs inside ``base``-sized diagonal blocks — all
    off-diagonal work is trsm/matmul (MXU-shaped). Used by the
    CholeskyQR2 Householder reconstruction panel (ops level never calls
    unpivoted LU on user data)."""
    n = a.shape[0]
    if n <= base:
        return getrf_nopiv(a)
    n1 = n // 2
    a11, a12 = a[:n1, :n1], a[:n1, n1:]
    a21, a22 = a[n1:, :n1], a[n1:, n1:]
    p11 = getrf_nopiv_blocked(a11, base)
    u12 = trsm(p11, a12, side="L", lower=True, unit=True)
    l21 = trsm(p11, a21, side="R", lower=False)
    p22 = getrf_nopiv_blocked(a22 - dot(l21, u12), base)
    top = jnp.concatenate([p11, u12], axis=1)
    bot = jnp.concatenate([l21, p22], axis=1)
    return jnp.concatenate([top, bot], axis=0)


def lauum(a, lower: bool = True):
    """Tile LAUUM: L^H L (lower) or U U^H (upper) of triangular tile."""
    if lower:
        t = jnp.tril(a)
        return dot(t, t, ta=True, conj_a=True)
    t = jnp.triu(a)
    return dot(t, t, tb=True, conj_b=True)


def trtri(a, *, lower=True, unit=False):
    """Tile triangular inverse via solve against identity."""
    if _dd_active(a.dtype):
        from dplasma_tpu.kernels import dd as _dd
        return _dd.trtri_f64(a, lower=lower, unit=unit)
    n = a.shape[0]
    eye = jnp.eye(n, dtype=a.dtype)
    return lax.linalg.triangular_solve(
        a, eye, left_side=True, lower=lower, unit_diagonal=unit)
