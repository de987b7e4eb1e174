"""Batched single-device execution paths: one compiled executable
factors/solves a whole stacked problem batch.

The serving workload is many medium-size problems; dispatching each
through the unbatched sweeps pays one executable launch (and one
compile-cache lookup) per problem. Here the *same* tile sweeps run
under ``jax.vmap`` over a stacked batch ``(B, n, n)`` + ``(B, n,
nrhs)``: XLA sees one program whose every matmul/solve carries a batch
dimension, so the whole batch rides single MXU/VPU dispatches.

The lift is exactly the one :mod:`dplasma_tpu.ops.map` performs per
tile — reshape to a tile tensor and vmap the operator — applied one
level up (vmap over whole problems; the batch-axis-aware
``map.to_tiles``/``from_tiles`` helpers came out of this lift).

Correctness contract (tested): a batched op matches a Python loop of
the unbatched op element-for-element — bit-for-bit where XLA lowers
the same op sequence, and always within the
:func:`~dplasma_tpu.ops.checks.check_solve` backward-error gate.

Iterative refinement (``posv_ir``/``gesv_ir``) batches on the existing
TRACED fixed-trip masked loop of :func:`dplasma_tpu.ops.refine.
ir_solve`: under vmap the convergence mask is per batch element, so
each problem exits refinement independently (converged elements stop
updating via ``where`` while stragglers keep refining). Escalation is
deliberately OFF inside the batch — under vmap a ``lax.cond`` runs
both branches for the whole batch, so one divergent element would
charge everyone the full-precision factorization. Divergence instead
surfaces per element in ``info["converged"]`` and the service's
per-request resilience ladder escalates ONLY the failed request
(:mod:`dplasma_tpu.serving.service`).

Padding semantics (the bucket contract of
:mod:`dplasma_tpu.serving.cache`): factor entry points install the
identity on the padded diagonal via :meth:`TileMatrix.pad_diag`, so a
problem padded from ``n`` to a bucket ``nB`` solves the block system
``blkdiag(A, I) [x; y] = [b; 0]`` — ``x`` is exact and ``y = 0``.
Partial pivoting may permute padding rows into the factor (they carry
the max-magnitude 1.0), which is why :func:`getrf_batched` returns the
*padded* factor: the padded system's solve is exact for any pivot
order, but slicing the factor to ``(n, n)`` would drop the coupling
rows.
"""
from __future__ import annotations

import jax

from dplasma_tpu.descriptors import TileMatrix

#: ops servable through the batched paths (service dispatch table)
OPS = ("posv", "gesv", "potrf", "getrf", "posv_ir", "gesv_ir")


def _tm(a, nb: int) -> TileMatrix:
    """One problem's dense array as a square-tiled TileMatrix (the
    per-element view under vmap — shapes here are UNBATCHED)."""
    return TileMatrix.from_dense(a, nb, nb)


def _check_stacked(A, B=None):
    assert A.ndim == 3 and A.shape[-1] == A.shape[-2], \
        f"batched ops want (B, n, n) stacks, got {A.shape}"
    if B is not None:
        assert B.ndim == 3 and B.shape[:2] == (A.shape[0], A.shape[1]), \
            f"rhs stack {B.shape} does not match {A.shape}"


# ---------------------------------------------------------------------
# Cholesky family
# ---------------------------------------------------------------------

def potrf_batched(A, nb: int, uplo: str = "L"):
    """Batched tile Cholesky: ``(B, n, n) -> (B, n, n)`` factors (the
    ``uplo`` triangle of each element is meaningful)."""
    from dplasma_tpu.ops import potrf as potrf_mod
    _check_stacked(A)

    def one(a):
        return potrf_mod.potrf(_tm(a, nb), uplo).to_dense()

    return jax.vmap(one)(A)


def potrs_batched(L, B, nb: int, uplo: str = "L"):
    """Batched triangular solves from stacked Cholesky factors: the
    factor is re-tiled with a unit padded diagonal (``pad_diag``), so
    the backward sweep never divides by padding zeros."""
    from dplasma_tpu.ops import potrf as potrf_mod
    _check_stacked(L, B)

    def one(l, b):
        Lt = _tm(l, nb).pad_diag()
        return potrf_mod.potrs(Lt, _tm(b, nb), uplo).to_dense()

    return jax.vmap(one)(L, B)


def posv_batched(A, B, nb: int, uplo: str = "L"):
    """Batched SPD factor+solve: ``(B, n, n), (B, n, nrhs) ->
    (B, n, nrhs)`` solutions (one executable for the whole batch)."""
    from dplasma_tpu.ops import potrf as potrf_mod
    _check_stacked(A, B)

    def one(a, b):
        _, X = potrf_mod.posv(_tm(a, nb), _tm(b, nb), uplo)
        return X.to_dense()

    return jax.vmap(one)(A, B)


# ---------------------------------------------------------------------
# LU family
# ---------------------------------------------------------------------

def getrf_batched(A, nb: int):
    """Batched pivoted LU: ``(B, n, n) -> ((B, Mp, Mp), (B, Mp))`` —
    the PADDED packed factors and pivot permutations (``A[perm] =
    LU``). The padding rows stay in the factor deliberately: partial
    pivoting may elect a unit padding row (see module docstring), so
    the ``(n, n)`` slice alone cannot reproduce the solve."""
    from dplasma_tpu.ops import lu as lu_mod
    _check_stacked(A)

    def one(a):
        F, perm = lu_mod.getrf_1d(_tm(a, nb))
        return F.data, perm

    return jax.vmap(one)(A)


def getrs_batched(LUp, perm, B, nb: int, trans: str = "N"):
    """Batched pivoted solves from :func:`getrf_batched`'s padded
    factors: ``(B, Mp, Mp), (B, Mp), (B, n, nrhs) -> (B, n, nrhs)``."""
    from dplasma_tpu.descriptors import TileDesc
    from dplasma_tpu.ops import lu as lu_mod
    assert LUp.ndim == 3 and B.ndim == 3, (LUp.shape, B.shape)
    n = B.shape[1]
    desc = TileDesc(n, n, nb, nb)
    assert LUp.shape[1:] == (desc.Mp, desc.Np), (LUp.shape, desc)

    def one(f, p, b):
        X = lu_mod.getrs(trans, TileMatrix(f, desc), p, _tm(b, nb))
        return X.to_dense()

    return jax.vmap(one)(LUp, perm, B)


def gesv_batched(A, B, nb: int):
    """Batched general factor+solve: ``(B, n, n), (B, n, nrhs) ->
    (B, n, nrhs)`` via partial-pivoted LU."""
    from dplasma_tpu.ops import lu as lu_mod
    _check_stacked(A, B)

    def one(a, b):
        _, _, X = lu_mod.gesv_1d(_tm(a, nb), _tm(b, nb))
        return X.to_dense()

    return jax.vmap(one)(A, B)


# ---------------------------------------------------------------------
# Mixed-precision IR solvers
# ---------------------------------------------------------------------

def posv_ir_batched(A, B, nb: int, *, precision=None, max_iters=None,
                    tol=None):
    """Batched mixed-precision SPD solve: factor each element in the
    working precision, refine to f64-equivalent on the traced masked
    loop — each batch element converges (and stops updating)
    independently. Returns ``(X, info)`` with every ``info`` leaf
    carrying a leading batch axis (``converged``: ``(B,)`` bools).
    No in-batch escalation (see module docstring)."""
    from dplasma_tpu.ops import refine
    _check_stacked(A, B)

    def one(a, b):
        X, info = refine.posv_ir(_tm(a, nb), _tm(b, nb),
                                 precision=precision,
                                 max_iters=max_iters, tol=tol,
                                 escalate=False)
        return X.to_dense(), info

    return jax.vmap(one)(A, B)


def gesv_ir_batched(A, B, nb: int, *, precision=None, max_iters=None,
                    tol=None):
    """Batched mixed-precision general solve (pivoted LU factor +
    iterative refinement); contract as :func:`posv_ir_batched`."""
    from dplasma_tpu.ops import refine
    _check_stacked(A, B)

    def one(a, b):
        X, info = refine.gesv_ir(_tm(a, nb), _tm(b, nb),
                                 precision=precision,
                                 max_iters=max_iters, tol=tol,
                                 escalate=False)
        return X.to_dense(), info

    return jax.vmap(one)(A, B)


def backward_errors(A, B, X):
    """Per-element normwise backward errors of a solved batch:
    ``max|b - A x| / (max(max|A|, 1) * max|x| + max|b|)`` — computed
    INSIDE the compiled executable (fused with the solve; the host
    gate then reads one scalar per request instead of re-doing the
    residual in numpy). The ``max(.., 1)`` clamp is the identity
    padding's contribution made explicit: padded operands carry 1.0 on
    the padded diagonal, and the padded residual rows are exactly zero
    (A pads identity, b and x pad zero), so numerator and verdict are
    padding-invariant."""
    import jax.numpy as jnp
    r = B - jnp.matmul(A, X, precision=jax.lax.Precision.HIGHEST)
    num = jnp.max(jnp.abs(r), axis=(-2, -1))
    den = (jnp.maximum(jnp.max(jnp.abs(A), axis=(-2, -1)),
                       jnp.asarray(1.0, A.dtype))
           * jnp.max(jnp.abs(X), axis=(-2, -1))
           + jnp.max(jnp.abs(B), axis=(-2, -1)))
    tiny = jnp.asarray(jnp.finfo(A.dtype).tiny, A.dtype)
    return num / jnp.maximum(den, tiny)


# ---------------------------------------------------------------------
# The service's uniform solve entry
# ---------------------------------------------------------------------

def solve_batched(op: str, A, B, nb: int, **kw):
    """Uniform ``(X, info|None)`` entry over every servable op — the
    single body the executable cache compiles per bucket."""
    if op == "posv":
        return posv_batched(A, B, nb, **kw), None
    if op == "gesv":
        return gesv_batched(A, B, nb, **kw), None
    if op == "posv_ir":
        return posv_ir_batched(A, B, nb, **kw)
    if op == "gesv_ir":
        return gesv_ir_batched(A, B, nb, **kw)
    raise ValueError(f"unservable op {op!r} (choose from "
                     f"{[o for o in OPS if o not in ('potrf', 'getrf')]})")
