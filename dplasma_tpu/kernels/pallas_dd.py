"""Pallas TPU kernel for the dd engine's level recombine + epilogue.

The blocked dd factorizations spend a profiled-dominant share of their
non-matmul time in ``base - scale * sum_l levels[l] * 2^(-w(l+2))`` —
the limb-level recombination and scaled subtraction that closes every
exact limb product (kernels/dd.py ``_level_recombine``). On the TPU
backend f64 is an f32 float-float pair (the X64 rewriter), and the
emulated chain costs ~20 rewriter ops per element; measured r5 on the
N=16384 blocked Cholesky it is ~0.22 s of the 0.45 s trailing update
and ~0.15 s of the panel IR.

This kernel computes the same quantity in ONE fused VMEM pass with
hand-written double-single (hi, lo f32) arithmetic:

* each int32 level splits EXACTLY into hi16/lo16 halves (both exact
  in f32), giving 2*nl exactly-representable terms;
* terms accumulate by Knuth two-sum into a running (hi, lo) pair
  (error ~2^-48 relative — the SAME width as the platform's
  float-float f64, so this is not a precision regression on TPU;
  true-f64 backends keep the exact _level_recombine);
* the power-of-two row/col scales multiply exactly in f32;
* the f32-pair base subtracts in double-single and renormalizes.

Role: the reference's hand-written CUDA epilogue kernels
(src/cores/dplasma_cuda_ztsmqr.c — fused composite updates beyond what
the vendor BLAS fuses); here the fusion XLA cannot do is float-float
arithmetic kept in registers across the whole chain.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl


def _two_sum(a, b):
    """Knuth exact addition: a + b = s + err with s = fl(a + b)."""
    s = a + b
    bb = s - a
    err = (a - (s - bb)) + (b - bb)
    return s, err


def _recombine_kernel(nl: int, w: int, lv_ref, bh_ref, bl_ref, sa_ref,
                      sb_ref, oh_ref, ol_ref):
    sc = sa_ref[...] * sb_ref[...]          # pow2 * pow2: exact f32
    acc_hi = jnp.zeros_like(bh_ref[...])
    acc_lo = jnp.zeros_like(acc_hi)
    two16 = jnp.float32(65536.0)
    for l in range(nl):
        v = lv_ref[l]
        h16 = jnp.right_shift(v, 16)                    # floor shift
        l16 = (v - (h16 << 16)).astype(jnp.float32)     # in [0, 2^16)
        wl = jnp.float32(2.0 ** (-w * (l + 2)))
        for t in (h16.astype(jnp.float32) * (two16 * wl), l16 * wl):
            acc_hi, e = _two_sum(acc_hi, t)
            acc_lo = acc_lo + e
    # base - scale * acc, in double-single
    r_hi = acc_hi * sc
    r_lo = acc_lo * sc
    s, e = _two_sum(bh_ref[...], -r_hi)
    lo = e + (bl_ref[...] - r_lo)
    hi = s + lo
    ol_ref[...] = lo - (hi - s)
    oh_ref[...] = hi


@functools.partial(jax.jit, static_argnums=(5, 6))
def _recombine_call(lv, bh, bl, sa, sb, w: int, interpret: bool):
    nl, M, N = lv.shape
    # Mosaic: the 2nd-minor block dim must be a multiple of 8 (callers
    # guarantee M % 8 == 0); pick the largest 8-multiple divisor of M
    # within a ~2 MB VMEM budget for the level block
    bm = max(8, min(M, (2 * 1024 * 1024) // (nl * N * 4)) // 8 * 8)
    while M % bm:
        bm -= 8
    grid = (M // bm,)
    kern = functools.partial(_recombine_kernel, nl, w)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((nl, bm, N), lambda i: (0, i, 0)),
            pl.BlockSpec((bm, N), lambda i: (i, 0)),
            pl.BlockSpec((bm, N), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, N), lambda i: (i, 0)),
            pl.BlockSpec((bm, N), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), jnp.float32),
            jax.ShapeDtypeStruct((M, N), jnp.float32),
        ],
        interpret=interpret,
    )(lv, bh, bl, sa, sb)


def recombine_base(levels, base, sa, sb, w: int,
                   interpret: bool | None = None):
    """``base - (sa * sb) * sum_l levels[l] * 2^(-w(l+2))`` as one
    fused double-single pass.

    ``levels``: list of nl int32 (M, N) level sums (unchunked dd
    products); ``base``: f64 (M, N) or None (treated as zero);
    ``sa``/``sb``: f64 power-of-two scale columns/rows (M, 1)/(1, N)
    — any sign (callers negate to ADD the product). Returns f64.

    Precision: double-single (~2^-48 relative) — bit-compatible with
    the TPU backend's float-float f64; callers on true-f64 backends
    must use the exact ``_level_recombine`` instead (kernels.dd
    gates on the backend).
    """
    f32 = jnp.float32
    M, N = levels[0].shape
    lv = jnp.stack([x.astype(jnp.int32) for x in levels])
    if base is None:
        bh = jnp.zeros((M, N), f32)
        bl = bh
    else:
        bh = base.astype(f32)
        bl = (base - bh.astype(base.dtype)).astype(f32)
    sa32 = jnp.broadcast_to(jnp.asarray(sa).astype(f32), (M, 1))
    sb32 = jnp.broadcast_to(jnp.asarray(sb).astype(f32), (1, N))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # trace the kernel with x64 OFF: every operand is 32-bit, and x64
    # mode makes index-map constants i64, which Mosaic refuses to mix
    # with the i32 grid index ("failed to legalize func.return")
    with jax.enable_x64(False):
        oh, ol = _recombine_call(lv, bh, bl, sa32, sb32, w, interpret)
    return oh.astype(jnp.float64) + ol.astype(jnp.float64)
