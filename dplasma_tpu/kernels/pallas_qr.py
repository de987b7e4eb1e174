"""Pallas TPU kernel: fused blocked Householder QR panel.

The geqrf panel chain is the QR dual of the LU panel bottleneck
(kernels/pallas_lu.py): XLA's QR decomposition is a slow sequential
blocked-Householder loop at panel shapes (~2-3 ms per nb=1024 panel,
measured r5), and panel area sums to N^2/2 regardless of blocking.
This kernel fuses the whole panel factorization into ONE VMEM-resident
pass, the role of the reference's CORE_zgeqrt
(src/cores/core_zgeqrt... via PLASMA) on a VMEM/MXU machine:

* the whole (M, nb) f32 panel is VMEM-resident (M*nb*4 <= ~8 MB);
* columns advance in JB-wide register blocks: each column's
  norm / reflector / apply touches only its (M, JB) strip via masked
  reductions (no one-hot over the full panel);
* per block, the JB reflectors aggregate into a compact-WY triangle
  T_blk by the larft recurrence (JB x JB — register-sized), and the
  trailing columns take ONE rank-JB MXU apply
  ``C -= V (T^H (V^H C))`` instead of JB rank-1 sweeps.

Outputs the LAPACK-packed panel (R on/above the diagonal, V below,
unit diagonal implicit) and the nb taus; the host wrapper rebuilds the
full compact-WY T with :func:`~dplasma_tpu.kernels.householder.larft`
(one matmul + small solve), so :func:`geqrt_panel` returns the exact
``(packed, V, T)`` contract of ``householder.geqrt``.

Reflector sign convention matches LAPACK (beta = -sign(alpha)*norm),
so the packed R agrees with the vendor panel's up to roundoff.
Selected via MCA ``panel.kernel pallas`` (kernels/panels.py), gated
by the per-feature pallas runtime probe; the XLA tree panel is the
fallback everywhere the probe fails.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

JB = 8  # column register-block width (= the f32 sublane quantum)


def _geqrt_kernel(nb: int, a_ref, out_ref, tau_ref):
    M = a_ref.shape[0]
    A = a_ref[...]                                    # (M, nb) f32
    rows = jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0)
    rowv = rows[:, 0]
    tauvec = jnp.zeros((nb,), jnp.float32)
    for j0 in range(0, nb, JB):
        S = A[:, j0:j0 + JB]                          # (M, JB) strip
        trail = A[:, j0 + JB:]
        cidx = jax.lax.broadcasted_iota(jnp.int32, (M, JB), 1)
        taus_blk = []
        for jj in range(JB):
            j = j0 + jj
            col = S[:, jj]
            x = jnp.where(rowv >= j, col, 0.0)
            alpha = jnp.sum(jnp.where(rowv == j, col, 0.0))
            ssq = jnp.sum(jnp.where(rowv > j, x * x, 0.0))
            norm = jnp.sqrt(alpha * alpha + ssq)
            # LAPACK sign choice: beta = -sign(alpha) * norm
            beta = jnp.where(alpha >= 0.0, -norm, norm)
            live = norm > 0.0
            tau = jnp.where(live, (beta - alpha) / jnp.where(
                live, beta, 1.0), 0.0)
            denom = alpha - beta
            vinv = jnp.where(denom != 0.0, 1.0 / jnp.where(
                denom != 0.0, denom, 1.0), 0.0)
            v = jnp.where(rowv > j, x * vinv,
                          jnp.where(rowv == j, 1.0, 0.0))
            tauvec = tauvec.at[j].set(tau)
            taus_blk.append(tau)
            # apply H_j to the strip columns RIGHT of jj only (the
            # stored V columns to the left must not be re-hit; v
            # vanishes above row j, so finished R rows are untouched),
            # then write column jj's packed form: beta on the
            # diagonal, v below
            w = jnp.sum(v[:, None] * S, axis=0, keepdims=True)
            S = jnp.where(cidx > jj, S - tau * v[:, None] * w, S)
            S = jnp.where((cidx == jj) & (rowv == j)[:, None], beta, S)
            S = jnp.where((cidx == jj) & (rowv > j)[:, None],
                          v[:, None], S)
        if trail.shape[1]:
            # compact-WY of the block: V_blk unit-lower in the strip
            Vb = jnp.where(rowv[:, None] > (j0 + cidx), S,
                           jnp.where(rowv[:, None] == (j0 + cidx),
                                     1.0, 0.0))
            G = jax.lax.dot_general(
                Vb, Vb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)   # (JB, JB)
            T = jnp.zeros((JB, JB), jnp.float32)
            for i in range(JB):
                ti = taus_blk[i]
                if i:
                    T = T.at[:i, i].set(
                        -ti * jnp.matmul(
                            T[:i, :i], G[:i, i],
                            preferred_element_type=jnp.float32))
                T = T.at[i, i].set(ti)
            # C -= V (T^T (V^T C)): one rank-JB MXU couple
            W = jax.lax.dot_general(
                Vb, trail, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)   # (JB, w)
            trail = trail - jax.lax.dot_general(
                Vb, jnp.matmul(T.T, W,
                               preferred_element_type=jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        A = jnp.concatenate(
            [A[:, :j0], S, trail] if j0 else [S, trail], axis=1) \
            if trail.shape[1] or j0 else S
    out_ref[...] = A
    tau_ref[...] = tauvec


@functools.partial(jax.jit, static_argnums=(1,))
def _geqrt_call(a, interpret: bool):
    M, nb = a.shape
    kern = functools.partial(_geqrt_kernel, nb)
    out, taus = pl.pallas_call(
        kern,
        out_shape=[
            jax.ShapeDtypeStruct((M, nb), jnp.float32),
            jax.ShapeDtypeStruct((nb,), jnp.float32),
        ],
        interpret=interpret,
    )(a)
    return out, taus


def geqrt_panel(a, interpret: bool | None = None):
    """Fused panel QR of an (M, nb) f32 panel: returns ``(packed, V,
    T)`` in the exact :func:`~dplasma_tpu.kernels.householder.geqrt`
    contract. M*nb*4 bytes must fit VMEM; nb must be a multiple of
    ``JB`` (the engine's eligibility check guards both)."""
    from dplasma_tpu.kernels import householder as hh
    a = jnp.asarray(a, jnp.float32)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    with jax.enable_x64(False):
        packed, taus = _geqrt_call(a, interpret)
    v, _ = hh.split_qr(packed)
    return packed, v, hh.larft(v, taus)


#: whole-panel VMEM residency budget of the fused panel kernels
VMEM_PANEL_BYTES = 8 * 2 ** 20


def eligible_shape(m: int, nb: int, itemsize: int = 4) -> bool:
    """The fused-panel shape gate alone (no pallas probe): f32-width
    items, JB-aligned width, whole panel within the VMEM residency
    budget. Shared with the roofline pricing, which must price the
    tree FALLBACK for exactly the shapes this gate rejects."""
    return (itemsize == 4 and nb % JB == 0
            and m * nb * itemsize <= VMEM_PANEL_BYTES)


def eligible(a) -> bool:
    """Trace-time gate for the fused panel: f32 +
    the shape gate."""
    if a.ndim != 2 or a.dtype != jnp.float32:
        return False
    return eligible_shape(a.shape[0], a.shape[1])
