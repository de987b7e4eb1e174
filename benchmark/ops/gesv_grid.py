"""Entry ``gesv_grid``: a partial-pivot LU solve on a P x Q process grid
through the library's public ``dplasma_tpu.ops.lu.getrf_ptgpanel``
(block-cyclic ``shard_map`` LU with tournament pivoting and the ICI
ring kernels) and ``getrs("N", ...)``, traced under
``parallel.mesh.use_grid``."""
from __future__ import annotations

import re

from jax.sharding import NamedSharding, PartitionSpec

from benchmark import lawn41
from benchmark.hlo import RING_MARKER
from benchmark.ops import Program


def ring_calls(text: str) -> int:
    """Mosaic custom calls of the ICI ring kernels in compiled HLO."""
    return len(re.findall(r"%" + RING_MARKER + r"[\w.]* = [^\n]*"
                          r'custom_call_target="tpu_custom_call"', text))


def _require_rings(compiled, platform: str) -> None:
    if platform == "tpu" and ring_calls(compiled.as_text()) == 0:
        raise RuntimeError("the grid program holds no dplasma_ring_* "
                           "kernel: the ICI ring path is not the one timed")


def build(cfg: dict, devices) -> Program:
    from dplasma_tpu.descriptors import TileDesc, TileMatrix
    from dplasma_tpu.ops import lu
    from dplasma_tpu.parallel import mesh as pmesh

    n, nb, nrhs = cfg["N"], cfg["nb"], cfg["nrhs"]
    p, q = cfg["grid"]
    mesh = pmesh.make_mesh(p, q, devices)
    adesc = TileDesc(n, n, nb, nb)
    bdesc = TileDesc(n, nrhs, nb, nrhs)

    def solve(a, b):
        f, perm = lu.getrf_ptgpanel(TileMatrix(a, adesc))
        return lu.getrs("N", f, perm, TileMatrix(b, bdesc)).to_dense()

    return Program(
        fn=solve,
        a_sharding=NamedSharding(mesh, PartitionSpec(pmesh.ROW_AXIS,
                                                     pmesh.COL_AXIS)),
        b_sharding=NamedSharding(mesh, PartitionSpec()),
        scope=lambda: pmesh.use_grid(mesh),
        flops=lawn41.getrf(n, n) + lawn41.getrs(n, nrhs),
        check=_require_rings)
