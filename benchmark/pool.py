"""The operands of a request-serving cell, made from the seed.

A traffic mix with a ``pool`` draws its requests from that many
operands ``(op, A, b)``. Every seed gets the same set of shapes: the
sizes are the pool's quantiles of a log-uniform law over ``n_range``,
each op takes every other size in turn (``ops``, shares of the pool),
and each op's NRHS cycle through ``nrhs_range`` in size order. The seed
decides the entries of every matrix and which index holds which shape.

The entries follow the benchmark's generators (``benchmark/gen.py``):
``posv`` gets ``plghe`` with its diagonal raised by n (symmetric and
diagonally dominant, so SPD, DPLASMA's ``bump = N``), ``gesv`` gets
``plrnt`` with its diagonal raised by n (diagonally dominated, as
``tools/servebench.py`` makes it), and ``b`` is ``plrnt``. An entry
depends only on the seed, the operand's index and its global row and
column, so each matrix is made on the device at the next power of two
and cut to ``n`` on the host: one small program per op and size class,
whatever the sizes drawn.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from benchmark import lawn41

#: LAWN-41 operations of one solve, by op
FLOPS = {"posv": lambda n, r: lawn41.potrf(n) + lawn41.potrs(n, r),
         "gesv": lambda n, r: lawn41.getrf(n, n) + lawn41.getrs(n, r)}


@dataclasses.dataclass
class Operand:
    op: str
    n: int
    nrhs: int
    a: np.ndarray
    b: np.ndarray

    @property
    def flops(self) -> float:
        return FLOPS[self.op](self.n, self.nrhs)


def shapes(traffic: dict) -> list:
    """``[(op, n, nrhs)]`` of the pool, the same for every seed."""
    size = int(traffic["pool"])
    lo, hi = traffic["n_range"]
    r_lo, r_hi = traffic["nrhs_range"]
    ns = [int(round(math.exp(math.log(lo) + (k + 0.5) / size
                             * math.log(hi / lo)))) for k in range(size)]
    # each op's places spread evenly over the sizes, in size order
    places = []
    for op, share in sorted(traffic["ops"].items()):
        count = int(round(share * size))
        places += [((j + 0.5) / count, op) for j in range(count)]
    if len(places) != size:
        raise ValueError(f"op shares {traffic['ops']} do not split a pool "
                         f"of {size}")
    out, seen = [], {}
    for (_, op), n in zip(sorted(places), ns):
        j = seen[op] = seen.get(op, -1) + 1
        out.append((op, n, r_lo + j % (r_hi - r_lo + 1)))
    return out


def _generator(op: str, m: int, r: int, dtype):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from benchmark.gen import _mix, _uniform

    def make(seed, k, bump):
        s = _mix(seed ^ _mix(k + jnp.uint32(0x2545F491)))
        i = lax.broadcasted_iota(jnp.uint32, (m, m), 0)
        j = lax.broadcasted_iota(jnp.uint32, (m, m), 1)
        if op == "posv":
            a = _uniform(s, jnp.maximum(i, j), jnp.minimum(i, j), dtype)
        else:
            a = _uniform(s, i, j, dtype)
        a = jnp.where(i == j, a + bump.astype(dtype), a)
        bi = lax.broadcasted_iota(jnp.uint32, (m, r), 0)
        bj = lax.broadcasted_iota(jnp.uint32, (m, r), 1)
        return a, _uniform(s ^ jnp.uint32(0x68E31DA4), bi, bj, dtype)
    return jax.jit(make)


def make(seed: int, traffic: dict, dtype) -> list:
    """The pool's operands as host arrays, index ``k`` at position k."""
    import jax
    import jax.numpy as jnp
    dtype = np.dtype(dtype)
    shp = shapes(traffic)
    order = np.random.default_rng([seed % 2 ** 63, 7]).permutation(len(shp))
    r_max = int(traffic["nrhs_range"][1])
    progs, out = {}, []
    for k, idx in enumerate(order):
        op, n, nrhs = shp[idx]
        m = 1 << max(n - 1, 1).bit_length()
        if (op, m) not in progs:
            progs[op, m] = _generator(op, m, r_max, dtype)
        a, b = progs[op, m](jnp.uint32(seed % 2 ** 32), jnp.uint32(k),
                            jnp.float32(n))
        a, b = jax.device_get((a, b))
        out.append(Operand(op, n, nrhs, np.ascontiguousarray(a[:n, :n]),
                           np.ascontiguousarray(b[:n, :nrhs])))
    return out
