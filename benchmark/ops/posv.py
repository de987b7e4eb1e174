"""Entry ``posv``: an SPD solve ``A x = b`` through the library's public
``dplasma_tpu.ops.potrf.posv`` (tile Cholesky, then two blocked
triangular sweeps), on one chip. Nothing of the factor is kept between
calls."""
from __future__ import annotations

import contextlib

from jax.sharding import SingleDeviceSharding

from benchmark import lawn41
from benchmark.ops import Program


def build(cfg: dict, devices) -> Program:
    from dplasma_tpu.descriptors import TileDesc, TileMatrix
    from dplasma_tpu.ops import potrf

    n, nb, nrhs = cfg["N"], cfg["nb"], cfg["nrhs"]
    adesc = TileDesc(n, n, nb, nb)
    bdesc = TileDesc(n, nrhs, nb, nrhs)

    def solve(a, b):
        _, x = potrf.posv(TileMatrix(a, adesc), TileMatrix(b, bdesc), "L")
        return x.to_dense()

    one = SingleDeviceSharding(devices[0])
    return Program(fn=solve, a_sharding=one, b_sharding=one,
                   scope=contextlib.nullcontext,
                   flops=lawn41.potrf(n) + lawn41.potrs(n, nrhs))
