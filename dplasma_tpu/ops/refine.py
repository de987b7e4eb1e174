"""Mixed-precision iterative-refinement solvers: factor low, refine to
f64-equivalent accuracy.

The bench ladder (BENCH_r05) shows the f64-equivalent routes paying the
full Ozaki dd-GEMM cost (~36 int8 products per matmul) for every flop
of the O(n^3) factorization, while the probed peaks leave a 5-20x
ceiling on the table (bf16/int8 MXU rates vs the f64-equiv bound).
Mixed-precision iterative refinement (Carson & Higham's three-precision
analysis; Haidar et al.'s tensor-core IR solvers, SC'18) inverts that
cost structure: factor A ONCE in a cheap working precision at the MXU
rate, then recover an f64-accurate *solution* by looping the O(n^2)
refinement step

    r = b - A x          (f64-equivalent, kernels.dd.gemm_residual)
    d = solve(F_w, r)    (cached low-precision factors)
    x = x + d            (x carried in f64 / dd representation)

until the normwise backward error ||r|| / (||A|| ||x|| + ||b||) reaches
the ~100*u_f64 floor. Only the residual pays dd cost; the factorization
runs at the working-precision rate.

Working precisions (MCA ``ir.precision``, default ``f32``):

* ``int8`` — the factor's f32 working matrix runs its trailing
  updates (the sweep's far/agg flushes and lookahead products) through
  the block-scaled int8 GEMM (:mod:`dplasma_tpu.kernels.quant`) while
  panels/trsm/diagonal kernels stay f32; per-update ABFT ones-probes
  guard divergence (surfaced as ``quant_guard_max``), and actual
  divergence escalates on non-contraction like every other rung;
* ``bf16`` — operands and factors are *rounded through bf16 storage*
  (compute accumulates in f32, exactly the MXU's bf16-input contract);
  error contracts ~kappa*u_bf16 per step, so more iterations;
* ``f32``  — plain f32 factorization (one MXU pass per product);
* ``f32x2`` — double-single: the f32 factor takes ONE extra
  refinement step whose residual rides :func:`kernels.dd.gemm_residual`
  at ``bits=32`` (the nl=5 limb ladder rung, ~2.4x the full-dd rate),
  giving ~2x f32 factor accuracy and near-one-iteration convergence.

Solves ride the EXISTING blocked paths (``ops.potrf.potrs``,
``ops.lu.getrs``, ``ops.blas3.trsm``) at the factor's dtype;
``gels_ir`` refines least-squares via semi-normal equations on the QR
``R`` factor (Bjorck: R^T R d = A^T r — no Q needed per iteration).

Control flow is dual-mode, like every dd route in the repo:

* **eager** (concrete inputs — the bench path and the driver's
  ``--phase-profile`` attributed pass): a host loop with an early exit
  on convergence, divergence detection (non-finite or stalled backward
  error), and escalation by actually *running* the full-precision
  route (the dd factorization on MXU backends);
* **traced** (inside ``jax.jit`` — the drivers' timed loop): exactly
  ``max_iters`` masked refinement steps (converged solutions stop
  updating via ``where``), with escalation as a ``lax.cond`` over the
  full-precision solve so divergence still produces a correct answer
  in one executable.

Non-convergence *reclassifies* rather than fails: the escalation rung
re-solves with the full f64-equivalent factorization (the route the
repo already trusts), mirroring the PR 2 remediation ladder's
algorithm-escalation step — and the driver bodies additionally wire
that same escape as a ladder ``fallbacks`` rung, so a run whose IR
output is unhealthy walks the ladder like any other fault. The
non-finite census on the backward error doubles as the convergence
guard (a NaN residual is divergence, not a verdict).

:func:`gesv_mxp` is HPL-MxP's solve: the LU without pivoting at bf16
trailing updates, refined by restarted GMRES (right-preconditioned by
that LU) instead of the classical step, in one ``lax.while_loop``
that stops at convergence, with no escalation.

Every stage carries a phase span (``factor`` / ``solve`` /
``residual`` / ``correct`` / ``escalate``) for the PR 5 attribution
ledger; :func:`dplasma_tpu.observability.roofline.refine_phase_model`
prices ``factor`` at the working-precision MXU rate and ``residual``
at the dd rate.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
from jax import lax

from dplasma_tpu import utils
from dplasma_tpu.descriptors import TileMatrix
from dplasma_tpu.kernels import dd as _dd
from dplasma_tpu.kernels import quant as _quant
from dplasma_tpu.observability import phases
from dplasma_tpu.ops import blas3, norms
from dplasma_tpu.utils import config as _cfg

#: supported working precisions, cheapest-to-strongest
PRECISIONS = ("int8", "bf16", "f32", "f32x2")

_cfg.mca_register(
    "ir.precision", "f32",
    "Working precision of the mixed-precision IR solvers "
    "(posv_ir/gesv_ir/gels_ir): int8 (f32 factor whose trailing "
    "updates ride the block-scaled int8 GEMM, kernels.quant), bf16 "
    "(operands/factors rounded through bf16 storage — the MXU's "
    "native input width), f32, or f32x2 (double-single: the f32 "
    "factor takes one extra refinement step on the kernels.dd "
    "bits=32 limb ladder rung).")
_cfg.mca_register(
    "ir.max_iters", "10",
    "Refinement-iteration budget of the IR solvers; a solve that has "
    "not reached ir.tol within the budget escalates to the full "
    "f64-equivalent factorization route.")
_cfg.mca_register(
    "ir.tol", "0",
    "Normwise-backward-error convergence target of the IR solvers "
    "(||b-Ax|| / (||A|| ||x|| + ||b||)); 0 = auto, 100x the f64 unit "
    "roundoff (the check_solve acceptance floor).")


def ir_params(precision=None, max_iters=None, tol=None, eps=None):
    """Resolve the IR configuration: explicit args win, else the MCA
    ``ir.*`` tier. Returns ``(precision, max_iters, tol)`` with the
    auto tolerance expanded to ``100*eps`` (``eps`` defaults to f64
    unit roundoff)."""
    p = (precision if precision is not None
         else (_cfg.mca_get("ir.precision") or "f32")).lower()
    if p not in PRECISIONS:
        raise ValueError(
            f"ir.precision {p!r} not in {PRECISIONS}")
    n = max_iters if max_iters is not None \
        else _cfg.mca_get_int("ir.max_iters", 10)
    t = tol
    if t is None:
        try:
            t = float(_cfg.mca_get("ir.tol", "0"))
        except ValueError:
            t = 0.0
    if t <= 0:
        t = 100.0 * (2.0 ** -52 if eps is None else eps)
    return p, max(int(n), 1), float(t)


def _round_wp(x, precision: str):
    """Round an array through the working precision's STORAGE width.

    bf16 rounds through bfloat16 (then holds f32 for the compute
    kernels — the MXU accumulates bf16 inputs in f32); int8/f32/f32x2
    cast to f32 (int8's quantization is per-*update*, not storage —
    kernels.quant quantizes each trailing product's operands on the
    fly; the f32x2 extra accuracy comes from the factor-refinement
    step, not the storage)."""
    f32 = jnp.float32
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(f32)
    return x.astype(f32)


def _tile(dense, like: TileMatrix) -> TileMatrix:
    return TileMatrix.from_dense(dense, like.desc.mb, like.desc.nb,
                                 like.desc.dist)


def _maxabs(x):
    return jnp.max(jnp.abs(x))


# ---------------------------------------------------------------------
# The refinement engine
# ---------------------------------------------------------------------

def ir_solve(x, *, residual, correct, backward, escalate, tol: float,
             max_iters: int, eager=None):
    """The generic iterative-refinement engine every solver here rides
    (and the extension point for new workloads): ``residual(x) -> r``
    (f64-equivalent), ``correct(r) -> d`` (working-precision solve,
    f64 out), ``backward(r, x) -> scalar`` (normwise backward error),
    ``escalate() -> x`` (full-precision route; None disables).

    Eager mode (the default when ``x`` is concrete) runs a host loop
    with early exit + divergence detection (non-finite or
    non-contracting backward error); traced mode runs exactly
    ``max_iters`` masked steps and folds escalation into a
    ``lax.cond``. Returns ``(x, info)`` with ``info`` a pytree of
    arrays: ``backward_errors`` (fixed length ``max_iters + 1``,
    padded with -1 past the executed iterations — a FINITE "no
    verdict" sentinel, never NaN: the driver's resilience health scan
    censuses non-finites across the whole output pytree, and a healthy
    early-converging solve must not trip it; a non-finite measured
    error also records as -1, the divergence story lives in
    ``converged``/``escalated``), ``iterations``, ``converged``,
    ``escalated``."""
    if eager is None:
        eager = utils.is_concrete(x)
    pad = jnp.asarray(-1.0, x.dtype)
    if eager:
        bwds = []
        converged = False
        nsolves = 0
        prev = None
        for _ in range(max_iters):
            with phases.span("residual") as _f:
                r = _f(residual(x))
            bwd = float(backward(r, x))
            bwds.append(bwd)
            if bwd <= tol:
                converged = True
                break
            if bwd != bwd or (prev is not None and bwd >= prev):
                # divergence guard (the ABFT-style non-finite census
                # plus a no-progress check): stop burning iterations,
                # the escalation rung owns this solve now
                break
            prev = bwd
            with phases.span("correct") as _f:
                x = _f(x + correct(r))
            nsolves += 1
        else:
            # budget exhausted right after a correction: that corrected
            # x deserves its convergence verdict before the (expensive)
            # escalation rung re-factors — a solve converging at exactly
            # max_iters steps is a convergence, not a divergence
            with phases.span("residual") as _f:
                r = _f(residual(x))
            bwd = float(backward(r, x))
            bwds.append(bwd)
            converged = bwd <= tol
        escalated = False
        if not converged and escalate is not None:
            # the escalated x is the trusted full-precision route's
            # answer; its quality is the testers' -x check's business,
            # not an IR iteration — the history keeps the fixed
            # max_iters+1 layout of the traced mode
            with phases.span("escalate") as _f:
                x = _f(escalate())
            escalated = True
        hist = [jnp.asarray(b if math.isfinite(b) else -1.0, x.dtype)
                for b in bwds]
        hist += [pad] * (max_iters + 1 - len(hist))
        info = {"backward_errors": jnp.stack(hist),
                "iterations": jnp.asarray(nsolves, jnp.int32),
                "converged": jnp.asarray(converged),
                "escalated": jnp.asarray(escalated)}
        return x, info
    # traced: fixed-trip masked loop (the timed driver path). Work
    # after convergence is masked, not skipped — the executable's
    # shape is data-independent.
    done = jnp.asarray(False)
    iters = jnp.asarray(0, jnp.int32)
    hist = []
    for _ in range(max_iters):
        r = residual(x)
        bwd = backward(r, x)
        hist.append(jnp.where(done | ~jnp.isfinite(bwd), pad,
                              bwd.astype(x.dtype)))
        newly = bwd <= tol
        d = correct(r)
        x = jnp.where(done | newly, x, x + d)
        iters = iters + jnp.where(done | newly, 0, 1).astype(jnp.int32)
        done = done | newly
    # the budget's final correction gets its convergence verdict too
    # (one O(n^2) residual — without it a solve converging at exactly
    # max_iters steps would take the full-factorization escalation)
    r = residual(x)
    bwd = backward(r, x)
    hist.append(jnp.where(done | ~jnp.isfinite(bwd), pad,
                          bwd.astype(x.dtype)))
    done = done | (bwd <= tol)
    if escalate is not None:
        x = lax.cond(done, lambda op: op, lambda op: escalate(), x)
    info = {"backward_errors": jnp.stack(hist), "iterations": iters,
            "converged": done,
            "escalated": (jnp.asarray(escalate is not None) & ~done)}
    return x, info


def _backward_fn(anorm, bnorm, tiny):
    def backward(r, x):
        return _maxabs(r) / jnp.maximum(
            anorm * _maxabs(x) + bnorm, tiny)
    return backward


def _factor_refine_chol(af, L32, f64t):
    """One f64-equivalent refinement step of a whole-matrix Cholesky
    factor on the dd bits=32 ladder rung: E = A - L L^T exact,
    correction L <- L (I + Phi(L^-1 E L^-T)) in f32 (second order) —
    the :func:`kernels.dd._potrf_tile_ir` step at matrix scale. This
    IS the f32x2 working-precision factorization."""
    f32 = jnp.float32
    n = L32.shape[0]
    L = jnp.tril(L32).astype(f64t)
    E = _dd.gemm_residual(af.astype(f64t), L, L.T, bits=32)
    Li = lax.linalg.triangular_solve(
        jnp.tril(L32), jnp.eye(n, dtype=f32), left_side=True,
        lower=True)
    M = _dd.mm_f32(_dd.mm_f32(Li, E.astype(f32)), Li.T)
    phi = jnp.tril(M, -1) + 0.5 * jnp.diag(jnp.diag(M))
    corr = _dd.mm_f32(jnp.tril(L32), phi)
    return jnp.tril(L + corr.astype(f64t))


def _factor_refine_r(ad, R32, f64t):
    """One bits=32 refinement step of the QR ``R`` factor via its Gram
    identity R^T R = A^T A (the CholeskyQR2 correction, upper form):
    E = G - R^T R exact on the dd bits=32 rung, correction
    R <- (I + Phi(R^-T E R^-1)) R in f32 — the f32x2 working-precision
    R for the semi-normal-equation solves."""
    f32 = jnp.float32
    n = R32.shape[0]
    R = jnp.triu(R32).astype(f64t)
    G = _dd.gemm_f64(ad.T, ad, bits=32)
    E = _dd.gemm_residual(G, R.T, R, bits=32)
    Ri = lax.linalg.triangular_solve(
        jnp.triu(R32), jnp.eye(n, dtype=f32), left_side=True,
        lower=False)
    M = _dd.mm_f32(_dd.mm_f32(Ri.T, E.astype(f32)), Ri)
    phi = jnp.triu(M, 1) + 0.5 * jnp.diag(jnp.diag(M))
    corr = _dd.mm_f32(phi, jnp.triu(R32))
    return jnp.triu(R + corr.astype(f64t))


def _require_f64(A: TileMatrix, who: str):
    import jax
    if A.dtype != jnp.float64:
        raise TypeError(f"{who} refines to f64-equivalent accuracy: "
                        f"input must be float64, got {A.dtype}")
    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            f"{who} requires jax_enable_x64 (the dd residuals would "
            "silently truncate to f32)")


# ---------------------------------------------------------------------
# User-facing solvers
# ---------------------------------------------------------------------

def posv_ir(A: TileMatrix, B: TileMatrix, uplo: str = "L", *,
            precision=None, max_iters=None, tol=None,
            escalate: bool = True):
    """SPD solve A X = B by Cholesky in a low working precision +
    iterative refinement to f64-equivalent backward error.

    ``A`` stores the ``uplo`` triangle (posv contract); returns
    ``(X, info)`` with ``X`` f64 and ``info`` the refinement record
    (:func:`summarize` turns it into the run-report ``"refine"``
    entry). ``escalate=False`` disables the full-precision fallback
    (the caller owns divergence)."""
    from dplasma_tpu.ops import potrf as potrf_mod
    _require_f64(A, "posv_ir")
    prec, iters, tol_ = ir_params(precision, max_iters, tol)
    f64t = A.dtype
    af = norms._sym_full(A, uplo, conj=True)
    bd = B.to_dense().astype(f64t)
    tiny = float(jnp.finfo(f64t).tiny)
    eager = utils.is_concrete(A.data)

    guards = []
    with phases.span("factor") as _f:
        Aw = _tile(_round_wp(af, prec), A)
        if prec == "int8":
            # int8 rung: trailing updates of the sweep ride the
            # block-scaled int8 GEMM; panels/trsm stay f32. The scope
            # yields the ABFT ones-probe residuals per routed update.
            with _quant.update_scope() as guards:
                Lw = potrf_mod.potrf(Aw, "L")
        else:
            Lw = potrf_mod.potrf(Aw, "L")
        if prec == "bf16":
            Lw = Lw.like(_round_wp(Lw.data, prec))
        elif prec == "f32x2":
            Lw = _tile(_factor_refine_chol(af, Lw.to_dense(), f64t), A)
        _f(Lw.data)

    def solve_w(rhs):
        out = potrf_mod.potrs(Lw, _tile(_round_wp(rhs, prec)
                                        if prec != "f32x2" else rhs,
                                        B), "L")
        return out.to_dense().astype(f64t)

    with phases.span("solve") as _f:
        x = _f(solve_w(bd))
    backward = _backward_fn(_maxabs(af), _maxabs(bd), tiny)

    def escalate_fn():
        _, X = potrf_mod.posv(A, B, uplo)
        return X.to_dense().astype(f64t)

    x, info = ir_solve(
        x,
        residual=lambda xv: _dd.gemm_residual(bd, af, xv),
        correct=solve_w, backward=backward,
        escalate=escalate_fn if escalate else None,
        tol=tol_, max_iters=iters, eager=eager)
    if prec == "int8":
        info = dict(info, quant_guard_max=_quant.guard_max(guards))
    return _tile(x, B), info


def gesv_ir(A: TileMatrix, B: TileMatrix, *, precision=None,
            max_iters=None, tol=None, escalate: bool = True):
    """General solve A X = B by pivoted LU in a low working precision +
    iterative refinement to f64-equivalent backward error. Returns
    ``(X, info)`` (see :func:`posv_ir`).

    The factor rides :func:`~dplasma_tpu.ops.lu.getrf_ptgpanel`: under
    an active device mesh that is the realized distributed panel (the
    grid-correct pivoted route); single-process grids take the
    identical-contract :func:`~dplasma_tpu.ops.lu.getrf_1d` path."""
    from dplasma_tpu.ops import lu as lu_mod
    _require_f64(A, "gesv_ir")
    prec, iters, tol_ = ir_params(precision, max_iters, tol)
    f64t = A.dtype
    ad = A.to_dense().astype(f64t)
    bd = B.to_dense().astype(f64t)
    tiny = float(jnp.finfo(f64t).tiny)
    eager = utils.is_concrete(A.data)

    guards = []
    with phases.span("factor") as _f:
        Aw = _tile(_round_wp(ad, prec), A)
        if prec == "int8":
            # quantized Schur updates (_lu_apply_block); the panel's
            # pivot search and U solves stay f32
            with _quant.update_scope() as guards:
                LUw, perm = lu_mod.getrf_ptgpanel(Aw)
        else:
            LUw, perm = lu_mod.getrf_ptgpanel(Aw)
        if prec == "bf16":
            LUw = LUw.like(_round_wp(LUw.data, prec))
        elif prec == "f32x2":
            # refine L, U for the FIXED pivot order on the bits=32
            # rung (kernels.dd.lu_ir with a pinned single-step ladder)
            pk = LUw.data
            r_ = jnp.arange(pk.shape[0])
            L32 = jnp.tril(pk, -1).astype(f64t).at[
                r_, r_].set(jnp.ones((), f64t))
            U32 = jnp.triu(pk).astype(f64t)
            pp = A.pad_diag().data.astype(f64t)[perm]
            L, U = _dd.lu_ir(pp, L32, U32, refine=1, bits=32)
            LUw = LUw.like(jnp.triu(U) + jnp.tril(L, -1))
        _f(LUw.data)

    def solve_w(rhs):
        out = lu_mod.getrs("N", LUw, perm,
                           _tile(_round_wp(rhs, prec)
                                 if prec != "f32x2" else rhs, B))
        return out.to_dense().astype(f64t)

    with phases.span("solve") as _f:
        x = _f(solve_w(bd))
    backward = _backward_fn(_maxabs(ad), _maxabs(bd), tiny)

    def escalate_fn():
        # eager: the grid-correct distributed panel. Traced: this body
        # lands inside ir_solve's lax.cond, whose branches must carry
        # NO explicit collectives (analysis.spmdcheck's rank-divergent-
        # cond rule would reject the program --spmdcheck verifies) —
        # the 1-D route is GSPMD-partitioned, so its schedule belongs
        # to XLA and the cond stays structurally uniform
        if eager:
            F, p = lu_mod.getrf_ptgpanel(A)
        else:
            F, p = lu_mod.getrf_1d(A)
        X = lu_mod.getrs("N", F, p, B)
        return X.to_dense().astype(f64t)

    x, info = ir_solve(
        x,
        residual=lambda xv: _dd.gemm_residual(bd, ad, xv),
        correct=solve_w, backward=backward,
        escalate=escalate_fn if escalate else None,
        tol=tol_, max_iters=iters, eager=eager)
    if prec == "int8":
        info = dict(info, quant_guard_max=_quant.guard_max(guards))
    return _tile(x, B), info


def gels_ir(A: TileMatrix, B: TileMatrix, *, precision=None,
            max_iters=None, tol=None, escalate: bool = True):
    """Overdetermined least squares min ||A X - B|| (M >= N) by QR in a
    low working precision + iterative refinement via SEMI-NORMAL
    equations on the R factor: each correction solves
    R^T R d = A^T r with two triangular sweeps — no Q application per
    iteration (Bjorck's corrected semi-normal equations; the one
    bits=32-refined R of the f32x2 precision is exactly the CSNE
    stabilizer). Convergence is measured on the PROJECTED residual
    ||A^T r|| / (||A|| (||A|| ||x|| + ||b||)) — the LS residual itself
    does not vanish. Returns ``(X, info)`` with ``X`` N-row f64."""
    from dplasma_tpu.ops import qr as qr_mod
    _require_f64(A, "gels_ir")
    assert A.desc.M >= A.desc.N, \
        "gels_ir: overdetermined (M >= N) only; use ops.qr.gels"
    prec, iters, tol_ = ir_params(precision, max_iters, tol)
    f64t = A.dtype
    N = A.desc.N
    ad = A.to_dense().astype(f64t)
    bd = B.to_dense().astype(f64t)[:A.desc.M]
    tiny = float(jnp.finfo(f64t).tiny)
    eager = utils.is_concrete(A.data)

    guards = []
    with phases.span("factor") as _f:
        Aw = _tile(_round_wp(ad, prec), A)
        if prec == "int8":
            # quantized wide compact-WY applies (ops.qr._quant_apply_q)
            with _quant.update_scope() as guards:
                Afw, Tfw = qr_mod.geqrf(Aw)
        else:
            Afw, Tfw = qr_mod.geqrf(Aw)
        r32 = jnp.triu(Afw.to_dense()[:N, :N])
        if prec == "bf16":
            r32 = _round_wp(r32, prec)
        if prec == "f32x2":
            Rw = _tile(_factor_refine_r(ad, r32, f64t), A)
        else:
            Rw = _tile(r32, A)
        _f(Rw.data)

    def snd_solve(s):
        """d = R^{-1} R^{-T} s via the existing blocked trsm path."""
        St = _tile(s if prec == "f32x2" else _round_wp(s, prec), Rw)
        y = blas3.trsm(1.0, Rw, St, side="L", uplo="U", trans="T")
        d = blas3.trsm(1.0, Rw, y, side="L", uplo="U", trans="N")
        return d.to_dense().astype(f64t)

    with phases.span("solve") as _f:
        # x0 from the semi-normal equations directly (R^T R x = A^T b)
        x = _f(snd_solve(_dd.gemm_f64(ad.T, bd)))
    anorm = _maxabs(ad)
    bnorm = _maxabs(bd)

    def residual(xv):
        # projected residual s = A^T (b - A x), both products
        # f64-equivalent (dd limb GEMMs)
        r = _dd.gemm_residual(bd, ad, xv)
        return _dd.gemm_f64(ad.T, r)

    def backward(s, xv):
        return _maxabs(s) / jnp.maximum(
            anorm * (anorm * _maxabs(xv) + bnorm), tiny)

    def escalate_fn():
        X = qr_mod.gels(A, B)
        return X.to_dense().astype(f64t)[:N]

    x, info = ir_solve(
        x, residual=residual, correct=snd_solve, backward=backward,
        escalate=escalate_fn if escalate else None,
        tol=tol_, max_iters=iters, eager=eager)
    if prec == "int8":
        info = dict(info, quant_guard_max=_quant.guard_max(guards))
    return _tile(x, B), info


#: Krylov vectors per GMRES cycle of :func:`gesv_mxp` (restart length)
MXP_RESTART = 8
#: most GMRES cycles :func:`gesv_mxp` runs before it gives up
MXP_MAX_CYCLES = 4


def gesv_mxp(A: TileMatrix, B: TileMatrix, *, tol=None):
    """General solve A X = B as HPL-MxP runs it: LU WITHOUT pivoting of
    an f32 copy of ``A`` with bf16 trailing updates (one MXU pass, f32
    accumulation), then GMRES-based iterative refinement to
    f64-equivalent backward error. Returns ``(X, info)`` as
    :func:`gesv_ir` does; ``info`` adds ``krylov_steps``.

    The factor is :func:`~dplasma_tpu.ops.lu.getrf_nopiv` (under a
    device mesh, the block-cyclic sweep without pivoting); it must not
    need pivots, as HPL-MxP's diagonally dominant matrix does not.
    Refinement: restarted GMRES (:data:`MXP_RESTART` vectors, at most
    :data:`MXP_MAX_CYCLES` cycles) on the right-preconditioned system
    ``A M^-1``, with ``M^-1 = U^-1 L^-1`` applied by
    :func:`~dplasma_tpu.ops.lu.getrs` in f32 (on the slabs under a
    mesh). The outer residuals ``b - A x`` and the Arnoldi products
    ``A z`` are f64-equivalent through one
    :class:`~dplasma_tpu.kernels.dd.LimbMatrix` (``A`` split once per
    call); Gram-Schmidt (twice, classical) and the Hessenberg
    least squares run in f64 on replicated vectors, one system per
    column of ``B``. The solve starts at x = 0, so the first Krylov
    vector is the low-precision solve ``M^-1 b``. A cycle stops once
    its residual estimate meets the tolerance (MCA ``ir.tol``; 100 f64
    eps by default) in the normwise backward error, and the whole solve
    once the true residual does, or once a cycle fails to halve it (the
    floor of the arithmetic: the TPU's f64 is an f32 pair, about 2^-48).
    There is no escalation: a solve that does not converge says so in
    ``info["converged"]``."""
    from dplasma_tpu.ops import lu as lu_mod
    _require_f64(A, "gesv_mxp")
    _, _, tol_ = ir_params("f32", 1, tol)
    f64t = A.dtype
    f32 = jnp.float32
    ad = A.to_dense().astype(f64t)
    bd = B.to_dense().astype(f64t)
    tiny = float(jnp.finfo(f64t).tiny)

    with phases.span("factor") as _f:
        LUw = lu_mod.getrf_nopiv(_tile(ad.astype(f32), A),
                                 update_dtype=jnp.bfloat16)
        _f(LUw.data)
    perm = lu_mod.nopiv_perm(LUw)

    def precond(v):
        out = lu_mod.getrs("N", LUw, perm, _tile(v.astype(f32), B))
        return out.to_dense().astype(f64t)

    op = _dd.LimbMatrix(ad)
    anorm, bnorm = _maxabs(ad), _maxabs(bd)
    backward = _backward_fn(anorm, bnorm, tiny)

    def residual(xv):
        with phases.span("residual"):
            return op.residual(bd, xv)

    def cycle(r, xv):
        # the estimate is a 2-norm, at least the inf-norm it stands for
        limit = tol_ * (anorm * _maxabs(xv) + bnorm)
        with phases.span("correct"):
            return _gmres_cycle(r, limit, precond, op.matmul)

    hist0 = jnp.full((MXP_MAX_CYCLES + 1,), -1.0, f64t)

    def record(hist, it, bwd):
        return hist.at[it].set(jnp.where(jnp.isfinite(bwd),
                                         bwd.astype(f64t), -1.0))

    # x0 = 0: the first Krylov vector is the low-precision solve M^-1 b,
    # so the program traces one preconditioner solve and one residual
    x = jnp.zeros_like(bd)
    bwd = backward(bd, x)
    zero = jnp.asarray(0, jnp.int32)
    state = (zero, x, bd, bwd, bwd <= tol_, record(hist0, 0, bwd), zero)

    def more(st):
        it, _, _, _, stop, _, _ = st
        return ~stop & (it < MXP_MAX_CYCLES)

    def step(st):
        it, xv, rv, prev, _, hist, ks = st
        d, steps = cycle(rv, xv)
        xv = xv + d
        rv = residual(xv)
        bwd = backward(rv, xv)
        # a cycle that does not halve the backward error has met the
        # floor of the arithmetic (or diverged): more cycles cannot help
        stop = (bwd <= tol_) | ~(bwd <= 0.5 * prev)
        return (it + 1, xv, rv, bwd, stop, record(hist, it + 1, bwd),
                ks + steps)

    it, x, _, bwd, _, hist, ks = lax.while_loop(more, step, state)
    info = {"backward_errors": hist, "iterations": it,
            "converged": bwd <= tol_, "escalated": jnp.asarray(False),
            "krylov_steps": ks}
    return _tile(x, B), info


def _gmres_cycle(r, limit, precond, matvec):
    """One restarted-GMRES cycle on ``A M^-1 u = r`` for each column of
    ``r`` (N, c), started at u = 0: ``(d, steps)`` with ``d = M^-1 u``
    the correction (the preconditioned Krylov vectors ``z_j = M^-1
    v_j`` are kept, as flexible GMRES keeps them) and ``steps`` the
    Krylov steps taken (the most over the columns). A column stops
    once its residual estimate is at most ``limit``; the cycle, once
    every column has stopped or :data:`MXP_RESTART` steps are done."""
    m = MXP_RESTART
    n, c = r.shape
    f64t = r.dtype

    def nrm(v):
        return jnp.sqrt(jnp.sum(v * v, axis=0))

    beta = nrm(r)
    V = jnp.zeros((m + 1, n, c), f64t).at[0].set(
        r / jnp.where(beta > 0, beta, 1.0))
    Z = jnp.zeros((m, n, c), f64t)
    H = jnp.zeros((m, m, c), f64t)           # R of the rotated Hessenberg
    cs = jnp.zeros((m, c), f64t)
    sn = jnp.zeros((m, c), f64t)
    g = jnp.zeros((m + 1, c), f64t).at[0].set(beta)
    live = beta > limit
    steps = jnp.zeros((c,), jnp.int32)

    def more(st):
        j, live = st[0], st[7]
        return (j < m) & jnp.any(live)

    def step(st):
        j, V, Z, H, cs, sn, g, live, steps = st
        z = precond(V[j])
        w = matvec(z)
        # classical Gram-Schmidt, twice, against v_0..v_j
        past = (jnp.arange(m + 1) <= j)[:, None]
        h = jnp.zeros((m + 1, c), f64t)
        for _ in range(2):
            hk = jnp.where(past, jnp.sum(V * w[None], axis=1), 0.0)
            w = w - jnp.sum(hk[:, None, :] * V, axis=0)
            h = h + hk
        hn = nrm(w)
        V = V.at[j + 1].set(w / jnp.where(hn > 0, hn, 1.0))
        Z = Z.at[j].set(z)
        # the rotations of the earlier steps, then a new one for hn
        for i in range(m - 1):
            hi, hi1 = h[i], h[i + 1]
            on = i < j
            h = h.at[i].set(jnp.where(on, cs[i] * hi + sn[i] * hi1, hi))
            h = h.at[i + 1].set(jnp.where(on, cs[i] * hi1 - sn[i] * hi,
                                          hi1))
        hj = h[j]
        rad = jnp.sqrt(hj * hj + hn * hn)
        safe = jnp.where(rad > 0, rad, 1.0)
        cj, sj = jnp.where(rad > 0, hj / safe, 1.0), hn / safe
        col = h[:m].at[j].set(rad)
        H = H.at[:, j].set(jnp.where(live, col, H[:, j]))
        cs = cs.at[j].set(jnp.where(live, cj, cs[j]))
        sn = sn.at[j].set(jnp.where(live, sj, sn[j]))
        gj = g[j]
        g = jnp.where(live, g.at[j].set(cj * gj).at[j + 1].set(-sj * gj),
                      g)
        steps = steps + live.astype(jnp.int32)
        live = live & (jnp.abs(g[j + 1]) > limit)
        return (j + 1, V, Z, H, cs, sn, g, live, steps)

    st = lax.while_loop(more, step, (jnp.asarray(0, jnp.int32), V, Z, H,
                                     cs, sn, g, live, steps))
    _, _, Z, H, _, _, g, _, steps = st
    # back substitution on each column's own steps
    y = [None] * m
    for i in range(m - 1, -1, -1):
        acc = g[i]
        for k in range(i + 1, m):
            acc = acc - H[i, k] * y[k]
        on = i < steps
        y[i] = jnp.where(on, acc / jnp.where(on, H[i, i], 1.0), 0.0)
    d = jnp.sum(jnp.stack(y)[:, None, :] * Z, axis=0)
    return d, jnp.max(steps)


# ---------------------------------------------------------------------
# Reporting helpers
# ---------------------------------------------------------------------

def summarize(info, *, op: str, precision=None, tol=None) -> dict:
    """Fold a (concrete) refinement ``info`` pytree into the
    run-report schema-v7 ``"refine"`` entry."""
    import numpy as np
    prec, _, tol_ = ir_params(precision, None, tol)
    # -1 is the engine's finite "no verdict" padding (and the record
    # of a non-finite measurement); real backward errors are >= 0
    hist = [float(v) for v in np.asarray(info["backward_errors"])
            if v >= 0]
    out = {"op": op, "precision": prec,
           "iterations": int(np.asarray(info["iterations"])),
           "backward_errors": hist,
           "converged": bool(np.asarray(info["converged"])),
           "escalated": bool(np.asarray(info["escalated"])),
           "tol": tol_}
    if "krylov_steps" in info:
        out["krylov_steps"] = int(np.asarray(info["krylov_steps"]))
    if "quant_guard_max" in info:
        # int8 rung: the max ABFT ones-probe residual over the routed
        # trailing updates (the per-update divergence guard)
        out["quant_guard_max"] = float(
            np.asarray(info["quant_guard_max"]))
    return out


# ---------------------------------------------------------------------
# Analytic DAG (factor + solve + refine task structure)
# ---------------------------------------------------------------------

def dag(A: TileMatrix, kind: str = "posv", recorder=None, *,
        iterations=None):
    """Record the IR solver's task structure — ``factor`` (the
    working-precision factorization), ``solve`` (the initial
    low-precision solve), then per refinement iteration ``residual(i)``
    (f64-equivalent r = b - A x) and ``correct(i)`` (the cached-factor
    correction solve) — with operand-tagged tile declarations
    (``A``/``B``/``F``/``X``/``R``) so :mod:`dplasma_tpu.analysis.
    dagcheck` proves the chain race-free, flow-covered and
    owner-consistent.

    The granularity is deliberately the XLA dispatch level (each stage
    is a handful of fused executables, not a tile sweep — the factor's
    own tile DAG is the underlying op's ``dag()``); ``iterations``
    defaults to the MCA ``ir.max_iters`` budget, the trace-time trip
    count of the compiled masked loop."""
    from dplasma_tpu import native
    from dplasma_tpu.utils import profiling
    rec = recorder if recorder is not None else profiling.recorder
    if iterations is None:
        _, it_budget, _ = ir_params()
    else:
        it_budget = max(int(iterations), 1)
    MT, NT = A.desc.MT, A.desc.NT
    ranks = native.rank_grid(A.desc.dist, MT, NT)
    rank0 = int(ranks[0, 0])
    a_tiles = [("A", i, j) for i in range(MT) for j in range(NT)]
    f_tiles = [("F", i, j) for i in range(MT) for j in range(NT)]
    x_tiles = [("X", i, 0) for i in range(MT)]
    b_tiles = [("B", i, 0) for i in range(MT)]
    r_tiles = [("R", i, 0) for i in range(MT)]
    if getattr(rec, "meta", None) is not None:
        rec.meta["refine"] = {"kind": kind, "iterations": it_budget}

    pri = 3 * (it_budget + 1)
    fac = rec.task("factor", 0, priority=pri + 2, rank=rank0,
                   reads=a_tiles, writes=f_tiles)
    sol = rec.task("solve", 0, priority=pri + 1, rank=rank0,
                   reads=f_tiles + b_tiles, writes=x_tiles)
    rec.edge(fac, sol, "F")
    prev_x = sol
    for i in range(it_budget):
        rt = rec.task("residual", i, priority=pri - 3 * i,
                      rank=rank0,
                      reads=a_tiles + b_tiles + x_tiles,
                      writes=r_tiles)
        rec.edge(prev_x, rt, "X")
        ct = rec.task("correct", i, priority=pri - 3 * i - 1,
                      rank=rank0,
                      reads=f_tiles + r_tiles + x_tiles,
                      writes=x_tiles)
        rec.edge(rt, ct, "R")
        rec.edge(fac, ct, "F")
        rec.edge(prev_x, ct, "X")
        prev_x = ct
    return rec
