"""Norm-based residual verification.

Reference: ``src/dplasma_zcheck.c`` (check_zpotrf, check_zaxmb, check_zqr…)
— the `-x` self-check pattern: regenerate from the seed, compute an
analytic residual, pass iff residual < threshold (60) after scaling by
eps·N (ref tests/testing_zpotrf.c:86-121). No golden files, ever.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dplasma_tpu.descriptors import TileMatrix
from dplasma_tpu.kernels import blas
from dplasma_tpu.ops import norms

THRESHOLD = 60.0

# Each residual is one jitted program: op by op, every limb op of a
# dd-precision product compiles on its own (hundreds of compiles on a
# TPU). A fresh jit per call, so MCA and grid settings read at trace
# time are never stale.


def _eps(dtype):
    if jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating):
        return float(jnp.finfo(jnp.finfo(dtype).dtype).eps)
    return float(jnp.finfo(dtype).eps)


def _tiny(dtype):
    """Smallest normal of the input's REAL dtype — the denominator
    clamp. (A float32 tiny under f64 inputs over-clamps by ~270 orders
    of magnitude; a f64 tiny under f32 would underflow to 0.)"""
    if jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating):
        return float(jnp.finfo(jnp.finfo(dtype).dtype).tiny)
    return float(jnp.finfo(dtype).tiny)


def check_potrf(A0: TileMatrix, LL: TileMatrix, uplo: str = "L"):
    """||A - L L^H|| / (N ||A|| eps) — check_zpotrf semantics."""
    r = jax.jit(functools.partial(_potrf_residual, uplo=uplo))(A0, LL)
    return float(r), bool(r < THRESHOLD)


def _potrf_residual(A0: TileMatrix, LL: TileMatrix, uplo: str):
    N = A0.desc.N
    a = norms._sym_full(A0, uplo, conj=True)
    x = LL.to_dense()
    if uplo.upper() == "L":
        t = jnp.tril(x)
        rec = blas.dot(t, t, tb=True, conj_b=True)
    else:
        t = jnp.triu(x)
        rec = blas.dot(t, t, ta=True, conj_a=True)
    res = jnp.max(jnp.abs(a - rec))
    anorm = jnp.max(jnp.abs(a))
    # zero-norm A0 (e.g. an all-zero generator) must give a finite
    # residual, not 0/0 = NaN
    return res / jnp.maximum(anorm * _eps(A0.dtype) * N, _tiny(A0.dtype))


def check_axmb(A0: TileMatrix, b: TileMatrix, x: TileMatrix,
               uplo: str | None = None):
    """||b - A x||_inf / (||A|| ||x|| N eps) — check_zaxmb semantics.
    ``uplo`` set means A0 stores a Hermitian triangle."""
    val = jax.jit(functools.partial(_axmb_residual, uplo=uplo))(A0, b, x)
    return float(val), bool(val < THRESHOLD)


def _axmb_residual(A0: TileMatrix, b: TileMatrix, x: TileMatrix,
                   uplo: str | None):
    N = A0.desc.N
    if uplo:
        a = norms._sym_full(A0, uplo, conj=True)
    else:
        a = A0.to_dense()
    bd = b.to_dense()
    xd = x.to_dense()
    r = bd - blas.dot(a, xd)
    num = jnp.max(jnp.abs(r))
    den = (jnp.max(jnp.abs(a)) * jnp.max(jnp.abs(xd)) * _eps(A0.dtype) * N)
    return num / jnp.maximum(den, _tiny(A0.dtype))


def check_solve(A0: TileMatrix, b: TileMatrix, x: TileMatrix,
                uplo: str | None = None, scale: float = 100.0):
    """Normwise backward error ``||b - A x|| / (||A|| ||x|| + ||b||)``
    against a dtype-scaled threshold (``scale * eps``, default the
    100·u floor the mixed-precision IR solvers converge to) — the
    measure the IR convergence test itself uses, unlike
    :func:`check_axmb`'s eps·N-scaled residual. ``uplo`` set means A0
    stores a Hermitian triangle. Max-norms throughout (consistent with
    the engine's test); the ``_tiny`` clamp keeps a zero-norm system
    finite, never 0/0."""
    if uplo:
        a = norms._sym_full(A0, uplo, conj=True)
    else:
        a = A0.to_dense()
    bd = b.to_dense()
    xd = x.to_dense()
    r = bd - blas.dot(a, xd)
    den = (jnp.max(jnp.abs(a)) * jnp.max(jnp.abs(xd))
           + jnp.max(jnp.abs(bd)))
    val = jnp.max(jnp.abs(r)) / jnp.maximum(den, _tiny(A0.dtype))
    return float(val), bool(val < scale * _eps(A0.dtype))


def check_gels(A0: TileMatrix, b: TileMatrix, xd):
    """Least-squares optimality ``||A^H (A x - b)|| / (||A||_F^2 ||x||_F
    eps max(M,N))`` — the gels testers' normal-equations gate (the LS
    residual itself does not vanish; its projection onto range(A)
    must). ``xd`` is the dense N-row solution; rows of ``b`` beyond
    A's M are ignored (the workspace rows of the gels contract)."""
    Ad = A0.to_dense()
    M, N = A0.desc.M, A0.desc.N
    res = blas.dot(Ad, xd[:N]) - b.to_dense()[:M]
    res = blas.dot(Ad, res, ta=True, conj_a=True)
    nrm = jnp.linalg.norm(Ad) ** 2 * jnp.linalg.norm(xd[:N])
    den = nrm * _eps(A0.dtype) * max(M, N)
    val = jnp.linalg.norm(res) / jnp.maximum(den, _tiny(A0.dtype))
    return float(val), bool(val < THRESHOLD)


def check_gemm(Cref, C):
    """Relative max-norm discrepancy between two tile matrices."""
    a = Cref.to_dense()
    bmat = C.to_dense()
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1.0)
    r = jnp.max(jnp.abs(a - bmat)) / (scale * _eps(C.dtype)
                                      * max(C.desc.N, 1))
    return float(r), bool(r < THRESHOLD)


def check_qr(A0: TileMatrix, Q, R):
    """||A - Q R|| / (||A|| max(M,N) eps)."""
    a = A0.to_dense()
    rec = blas.dot(Q, R)
    # the max(.., tiny) clamp keeps a zero-norm A0 finite even if the
    # 1.0 floor is ever scaled away
    r = jnp.max(jnp.abs(a - rec)) / jnp.maximum(
        jnp.maximum(jnp.max(jnp.abs(a)), 1.0)
        * _eps(A0.dtype) * max(A0.desc.M, A0.desc.N), _tiny(A0.dtype))
    return float(r), bool(r < THRESHOLD)


def check_orthogonality(Q):
    """||I - Q^H Q|| / (N eps)."""
    n = Q.shape[1]
    g = blas.dot(Q, Q, ta=True, conj_a=True)
    r = jnp.max(jnp.abs(g - jnp.eye(n, dtype=Q.dtype))) / (
        _eps(Q.dtype) * n)
    return float(r), bool(r < THRESHOLD)


def check_inverse(A0: TileMatrix, Ainv: TileMatrix, uplo: str | None = None):
    """||I - A A^{-1}|| / (N ||A|| ||A^{-1}|| eps) — check_zpoinv."""
    N = A0.desc.N
    a = norms._sym_full(A0, uplo, conj=True) if uplo else A0.to_dense()
    ai = norms._sym_full(Ainv, uplo, conj=True) if uplo else Ainv.to_dense()
    r = jnp.max(jnp.abs(jnp.eye(N, dtype=a.dtype) - blas.dot(a, ai)))
    den = jnp.max(jnp.abs(a)) * jnp.max(jnp.abs(ai)) * _eps(A0.dtype) * N
    val = r / jnp.maximum(den, _tiny(A0.dtype))
    return float(val), bool(val < THRESHOLD)
