"""Pallas TPU kernel: blocked partial-pivoting LU panel.

The f32 LU sweeps are panel-bound: XLA's LuDecompositionBlock custom
call runs ~3.6 ns/element at panel shapes (vs ~1.25 wide), and panel
area sums to N^2/2 regardless of blocking — at N=16384 that is ~55% of
the whole sgetrf runtime (measured r4/r5; the fake-panel ceiling of the
sweep is ~20 TF/s).  The r4 probe — a naive full-width masked rank-1
sweep — lost 3.4x to the vendor call because every column paid
one-hot selects over the entire (M, nb) panel.

This kernel is the properly BLOCKED design the r4 postmortem named
(the role of the reference's multithreaded recursive panel,
src/cores/core_zgetrf_rectil.c:1-728, on a VMEM/MXU machine):

* the whole (M, nb) panel is VMEM-resident (M*nb*4 <= ~8 MB);
* columns advance in JB-wide register blocks: each column's pivot
  select / swap / scale / rank-1 touches only its (M, JB) strip —
  the one-hot work the r4 probe paid over (M, nb) drops by nb/JB;
* rows are swapped PHYSICALLY, so the block's U rows sit at static
  positions: the trailing update is one static row-slice plus one
  rank-JB MXU dot per block.

Pivot ties break to the LOWEST row index (a pure-reduction argmax),
the invariant the pad-row safety of the eager dd sweeps pins.

Outputs the packed L\\U panel and the LAPACK-style swap sequence.
Gated behind MCA ``lu.pallas_panel`` (off by default until it beats
the vendor call on the measured ladder; the measurement is recorded
in CHANGELOG either way — VERDICT r5 item 3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

JB = 8  # column register-block width


def _swap_rows(B, rows, j, piv):
    """Masked physical swap of rows j (static) and piv (traced)."""
    rj = jnp.sum(jnp.where(rows == j, B, 0.0), axis=0, keepdims=True)
    rp = jnp.sum(jnp.where(rows == piv, B, 0.0), axis=0,
                 keepdims=True)
    return jnp.where(rows == j, rp, jnp.where(rows == piv, rj, B))


def _panel_kernel(nb: int, a_ref, out_ref, piv_ref):
    M = a_ref.shape[0]
    A = a_ref[...]                                   # (M, nb) f32
    rows = jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0)
    rowv = rows[:, 0]
    pivvec = jnp.zeros((nb,), jnp.int32)
    for j0 in range(0, nb, JB):
        S = A[:, j0:j0 + JB]                         # (M, JB) strip
        left = A[:, :j0]
        trail = A[:, j0 + JB:]
        for jj in range(JB):
            j = j0 + jj
            col = S[:, jj:jj + 1]
            # lowest-index argmax by reductions only (no one-hot
            # over the full panel, no argmax lowering)
            cand = jnp.where(rowv >= j, jnp.abs(col[:, 0]),
                             jnp.float32(-1.0))
            mx = jnp.max(cand)
            piv = jnp.min(jnp.where(cand == mx, rowv,
                                    jnp.int32(M))).astype(jnp.int32)
            pivvec = pivvec.at[j].set(piv)
            # physical swap: strip + finished + trailing columns
            S = _swap_rows(S, rows, j, piv)
            if j0:
                left = _swap_rows(left, rows, j, piv)
            if trail.shape[1]:
                trail = _swap_rows(trail, rows, j, piv)
            # scale + rank-1 inside the strip
            col = S[:, jj:jj + 1]
            d = jnp.sum(jnp.where(rowv == j, col[:, 0], 0.0))
            inv = jnp.where(d != 0.0, 1.0 / d, 0.0)
            lcol = col * inv
            urow = jnp.sum(jnp.where(rows == j, S, 0.0), axis=0,
                           keepdims=True)
            below = rows > j
            cidx = jax.lax.broadcasted_iota(jnp.int32, (M, JB), 1)
            S = jnp.where(below & (cidx > jj), S - lcol * urow, S)
            S = jnp.where(below & (cidx == jj), lcol, S)
        if trail.shape[1]:
            # U12 = L11^{-1} A12: the block's rows sit at STATIC
            # positions after the physical swaps, so the unit-lower
            # substitution unrolls over JB static scalar coefficients
            A12 = trail[j0:j0 + JB, :]
            L11 = S[j0:j0 + JB, :]
            u = [A12[i] for i in range(JB)]
            for i in range(JB):
                for t in range(i):
                    u[i] = u[i] - L11[i, t] * u[t]
            U12 = jnp.stack(u)
            # A22 -= L21 @ U12 (one rank-JB MXU dot); block rows take
            # the finished U12
            Lblk = jnp.where(rows >= j0 + JB, S, 0.0)
            upd = trail - jax.lax.dot_general(
                Lblk, U12, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            U12pad = jnp.pad(U12, ((j0, M - j0 - JB), (0, 0)))
            inblk = (rowv >= j0) & (rowv < j0 + JB)
            trail = jnp.where(inblk[:, None], U12pad, upd)
        A = jnp.concatenate([left, S, trail], axis=1) \
            if (j0 or trail.shape[1]) else S
    out_ref[...] = A
    piv_ref[...] = pivvec


@functools.partial(jax.jit, static_argnums=(1,))
def _panel_call(a, interpret: bool):
    M, nb = a.shape
    kern = functools.partial(_panel_kernel, nb)
    out, piv = pl.pallas_call(
        kern,
        out_shape=[
            jax.ShapeDtypeStruct((M, nb), jnp.float32),
            jax.ShapeDtypeStruct((nb,), jnp.int32),
        ],
        interpret=interpret,
    )(a)
    return out, piv


def eligible(a) -> bool:
    """Trace-time gate for the fused LU panel: f32 +
    JB-aligned width + whole panel within the VMEM residency budget
    (the ONE home of the gate both ops.lu dispatch branches share)."""
    from dplasma_tpu.kernels import pallas_qr
    if a.ndim != 2 or a.dtype != jnp.float32:
        return False
    return pallas_qr.eligible_shape(a.shape[0], a.shape[1])


def lu_panel(a, interpret: bool | None = None):
    """Packed L\\U + permutation of an (M, nb) f32 panel: ``a[perm] =
    L U`` (perm derived from the kernel's swap sequence). M*nb*4 bytes
    must fit VMEM (callers chunk at 8192 rows x 256 cols)."""
    a = jnp.asarray(a, jnp.float32)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    with jax.enable_x64(False):
        packed, ipiv = _panel_call(a, interpret)
    M = a.shape[0]
    perm = jnp.arange(M, dtype=jnp.int32)

    def body(j, p):
        piv = ipiv[j]
        pj = p[j]
        pp = p[piv]
        return p.at[j].set(pp).at[piv].set(pj)

    perm = jax.lax.fori_loop(0, a.shape[1], body, perm)
    return packed, perm

# ---------------------------------------------------------------------
# LU without pivoting of one square tile
# ---------------------------------------------------------------------

#: largest tile :func:`lu_nopiv_tile` takes: the step temporaries of a
#: 1024 tile ask the TPU compiler for 16.3 of its 16 MiB of scoped VMEM
NOPIV_MAX = 512


def _nopiv_kernel(a_ref, out_ref):
    n = a_ref.shape[0]
    r1 = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    c1 = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def step(j, A):
        # column j and row j by masked reductions: no dynamic slicing
        colj = jnp.sum(jnp.where(c1 == j, A, 0.0), axis=1, keepdims=True)
        rowj = jnp.sum(jnp.where(r1 == j, A, 0.0), axis=0, keepdims=True)
        d = jnp.sum(jnp.where(r1 == j, colj, 0.0), axis=0, keepdims=True)
        lcol = jnp.where(r1 > j, colj / d, 0.0)
        A = A - lcol * jnp.where(c1 > j, rowj, 0.0)
        return jnp.where((c1 == j) & (r1 > j), lcol, A)

    out_ref[...] = jax.lax.fori_loop(0, n, step, a_ref[...])


@functools.partial(jax.jit, static_argnums=(1,))
def _nopiv_call(a, interpret: bool):
    return pl.pallas_call(
        _nopiv_kernel,
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.float32),
        interpret=interpret,
    )(a)


def nopiv_eligible(a) -> bool:
    """f32 square tile, lane-aligned, at most :data:`NOPIV_MAX`."""
    return (a.ndim == 2 and a.dtype == jnp.float32
            and a.shape[0] == a.shape[1] and a.shape[0] % 128 == 0
            and a.shape[0] <= NOPIV_MAX)


def lu_nopiv_tile(a, interpret: bool | None = None):
    """Packed L\\U (unit L implicit) of a square f32 tile without
    pivoting, in one kernel: the tile stays in VMEM and each of its n
    elimination steps is a handful of whole-tile vector passes (the
    contract of :func:`dplasma_tpu.kernels.blas.getrf_nopiv`, whose
    XLA loop runs each step as separate operations)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    with jax.enable_x64(False):
        return _nopiv_call(jnp.asarray(a, jnp.float32), interpret)
