"""Seeded parallel matrix generators.

Reference: ``dplasma_zplrnt`` (random), ``dplasma_zplghe`` (Hermitian,
diagonally bumped → SPD), ``dplasma_zplgsy`` (symmetric), built on the map
framework over per-tile kernels with an index-jumping LCG
(ref src/zplrnt_wrapper.c, src/cores/core_zplrnt.c, SURVEY §2.2).

TPU-native design: the generator is an *elementwise counter-based hash* of
(seed, global row, global col) — every element is independent, so the
generator is one fused VPU op, deterministic under any tiling or sharding
(a stronger reproducibility guarantee than the reference's tile-jump LCG,
which we do not copy). Tests regenerate matrices from the seed instead of
storing goldens, exactly like the reference's `-x` paths
(ref tests/testing_zpotrf.c:50,92).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from dplasma_tpu.descriptors import Dist, TileDesc, TileMatrix

_C1 = 0x7feb352d
_C2 = 0x846ca68b
_R1 = 0x85ebca6b
_R2 = 0xc2b2ae35


def _mix(x):
    """lowbias32-style avalanche mix on uint32."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_C1)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(_C2)
    x = x ^ (x >> 16)
    return x


def _hash2d(seed: int, i, j):
    """Deterministic uint32 hash of (seed, i, j)."""
    h = _mix(jnp.uint32(seed & 0xFFFFFFFF) ^ jnp.uint32(0x9e3779b9))
    h = _mix(h ^ (i.astype(jnp.uint32) * jnp.uint32(_R1)))
    h = _mix(h ^ (j.astype(jnp.uint32) * jnp.uint32(_R2)))
    return h


def _uniform(seed: int, i, j, real_dtype):
    """U(-0.5, 0.5) at global element (i, j) — the reference generators'
    value range (0.5 - ran)."""
    h = _hash2d(seed, i, j)
    u = h.astype(real_dtype) * real_dtype(2.0 ** -32)
    return real_dtype(0.5) - u


def _grid(desc: TileDesc):
    r = jnp.arange(desc.Mp)[:, None]
    c = jnp.arange(desc.Np)[None, :]
    return r, c


def _value(seed: int, r, c, dtype):
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.complexfloating):
        rdt = jnp.finfo(dtype).dtype.type
        re = _uniform(seed, r, c, rdt)
        im = _uniform(seed + 1, r, c, rdt)
        return (re + 1j * im).astype(dtype)
    return _uniform(seed, r, c, dtype.type).astype(dtype)


def _mask_mn(desc: TileDesc, x):
    r, c = _grid(desc)
    return jnp.where((r < desc.M) & (c < desc.N), x, jnp.zeros((), x.dtype))


def _build(desc: TileDesc, values) -> TileMatrix:
    """``values(r, c)`` over the padded grid, masked to M x N, as one
    program: op by op, each of the hash's ~20 elementwise ops compiles
    and materializes on its own."""
    def make():
        r, c = _grid(desc)
        return _mask_mn(desc, values(r, c))
    return TileMatrix(jax.jit(make)(), desc)


def plrnt(M: int, N: int, mb: int, nb: int, seed: int = 3872,
          dtype=jnp.float32, diagdom: bool = False,
          dist: Dist = Dist()) -> TileMatrix:
    """Random matrix (dplasma_zplrnt). ``diagdom`` adds max(M,N) to the
    diagonal (the reference's diagonal-dominant mode used before
    no-pivoting LU)."""
    def values(r, c):
        v = _value(seed, r, c, dtype)
        if diagdom:
            bump = jnp.asarray(max(M, N), dtype=v.dtype)
            v = jnp.where(r == c, v + bump, v)
        return v
    return _build(TileDesc(M, N, mb, nb, dist), values)


def plghe(bump: float, N: int, nb: int, seed: int = 3872,
          dtype=jnp.float32, mb: int | None = None,
          dist: Dist = Dist()) -> TileMatrix:
    """Hermitian matrix with real diagonal + ``bump`` (dplasma_zplghe).
    ``bump >= N`` yields a positive-definite matrix (the SPD generator
    under every Cholesky test, ref tests/testing_zpotrf.c:50)."""
    def values(r, c):
        lo = jnp.maximum(r, c)
        hi = jnp.minimum(r, c)
        v = _value(seed, lo, hi, dtype)  # canonical (unordered) index pair
        if jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating):
            v = jnp.where(r < c, v.conj(), v)  # upper = conj(lower)
            v = jnp.where(r == c, v.real.astype(v.dtype), v)
        bump_a = jnp.asarray(bump, dtype=v.dtype)
        return jnp.where(r == c, v + bump_a, v)
    return _build(TileDesc(N, N, mb or nb, nb, dist), values)


def plgsy(bump: float, N: int, nb: int, seed: int = 3872,
          dtype=jnp.float32, mb: int | None = None,
          dist: Dist = Dist()) -> TileMatrix:
    """Complex-symmetric (not Hermitian) matrix + diagonal bump
    (dplasma_zplgsy)."""
    def values(r, c):
        lo = jnp.maximum(r, c)
        hi = jnp.minimum(r, c)
        v = _value(seed, lo, hi, dtype)
        bump_a = jnp.asarray(bump, dtype=v.dtype)
        return jnp.where(r == c, v + bump_a, v)
    return _build(TileDesc(N, N, mb or nb, nb, dist), values)
