"""The benchmark's own copy of the seeded matrix generators.

The value rule is that of ``dplasma_tpu/ops/generators.py`` (a
counter-based hash of seed, global row and global column, the
``plrnt``/``plghe`` value range U(-0.5, 0.5) of DPLASMA's
``dplasma_zplrnt``/``dplasma_zplghe``), copied here so that no change to
the program can change the benchmark's inputs. Each matrix is made on
the device in one jitted call, already in its sharding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_C1 = 0x7feb352d
_C2 = 0x846ca68b
_R1 = 0x85ebca6b
_R2 = 0xc2b2ae35


def _mix(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_C1)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(_C2)
    return x ^ (x >> 16)


def _uniform(seed, i, j, dtype):
    """U(-0.5, 0.5) at global element (i, j); ``seed``, ``i``, ``j``
    uint32."""
    h = _mix(seed ^ jnp.uint32(0x9e3779b9))
    h = _mix(h ^ (i * jnp.uint32(_R1)))
    h = _mix(h ^ (j * jnp.uint32(_R2)))
    u = h.astype(dtype) * jnp.asarray(2.0 ** -32, dtype)
    return jnp.asarray(0.5, dtype) - u


def _values(kind: str, seed, rows: int, cols: int, dtype, bump,
            col0=0):
    r = lax.broadcasted_iota(jnp.uint32, (rows, cols), 0)
    c = lax.broadcasted_iota(jnp.uint32, (rows, cols), 1) + col0
    if kind == "plrnt":
        return _uniform(seed, r, c, dtype)
    if kind == "plghe":
        v = _uniform(seed, jnp.maximum(r, c), jnp.minimum(r, c), dtype)
        return jnp.where(r == c, v + jnp.asarray(bump, dtype), v)
    raise ValueError(f"unknown generator {kind!r}")


def matrix(kind: str, seed: int, rows: int, cols: int, dtype,
           bump: float = 0.0, sharding=None, col0: int = 0) -> jax.Array:
    """Columns ``col0`` to ``col0 + cols`` of the ``rows``-row matrix of
    generator ``kind`` (``plrnt``, or ``plghe`` with diagonal ``bump``),
    made on the device. ``seed`` (taken modulo 2**32, as the program's
    generators take it) and ``col0`` are arguments of the compiled
    program, so every seed and block of columns shares one program."""
    dtype = jnp.dtype(dtype)
    fn = jax.jit(lambda s, c0: _values(kind, s, rows, cols, dtype, bump,
                                       c0), out_shardings=sharding)
    return fn(jnp.uint32(seed % 2 ** 32), jnp.uint32(col0))
