"""Entry ``hpl_mxp``: HPL-MxP's timed solve through the library's public
``dplasma_tpu.ops.refine.gesv_mxp`` on a P x Q process grid: the
block-cyclic LU without pivoting of an f32 copy of ``A`` with bf16
trailing updates, then GMRES-based refinement with f64-equivalent
residuals, traced under ``parallel.mesh.use_grid``. The operation count
is HPL-MxP's, ``2/3 N^3 + 3/2 N^2``, for the factorization and the
refinement together."""
from __future__ import annotations

import re

from jax.sharding import NamedSharding, PartitionSpec

from benchmark import hlo
from benchmark.ops import Program
from benchmark.ops.gesv_grid import ring_calls

#: an instruction emitted by ``jax.lax.linalg.lu``: a pivot election ran
_LU_OP = re.compile(r'op_name="[^"]*/lu["/\[]')


def bf16_products(text: str) -> int:
    """Dots and convolutions of compiled HLO whose two operands are
    bf16 (the one-pass trailing update)."""
    comps = hlo.parse(text)
    shapes = {ins["name"]: hlo._shape(ins["type"])
              for inss in comps.values() for ins in inss}
    return sum(1 for inss in comps.values() for ins in inss
               if ins["opcode"] in ("dot", "convolution")
               and len(ins["operands"]) >= 2
               and all(hlo._operand_shape(o, shapes)[0] == "bf16"
                       for o in ins["operands"][:2]))


def _check(compiled, platform: str) -> None:
    if platform != "tpu":
        return
    text = compiled.as_text()
    if ring_calls(text) == 0:
        raise RuntimeError("the grid program holds no dplasma_ring_* "
                           "kernel: the ICI ring path is not the one timed")
    if bf16_products(text) == 0:
        raise RuntimeError("the program holds no bf16 x bf16 product: the "
                           "trailing update does not run in one bf16 pass")
    if _LU_OP.search(text):
        raise RuntimeError("the program holds an lu operation: a pivot "
                           "election ran, the factor is not HPL-MxP's")


def build(cfg: dict, devices) -> Program:
    from dplasma_tpu.descriptors import TileDesc, TileMatrix
    from dplasma_tpu.ops import refine
    from dplasma_tpu.parallel import mesh as pmesh

    gesv_mxp = refine.gesv_mxp
    n, nb, nrhs = cfg["N"], cfg["nb"], cfg["nrhs"]
    p, q = cfg["grid"]
    mesh = pmesh.make_mesh(p, q, devices)
    adesc = TileDesc(n, n, nb, nb)
    bdesc = TileDesc(n, nrhs, nb, nrhs)

    def solve(a, b):
        x, _ = gesv_mxp(TileMatrix(a, adesc), TileMatrix(b, bdesc))
        return x.to_dense()

    return Program(
        fn=solve,
        a_sharding=NamedSharding(mesh, PartitionSpec(pmesh.ROW_AXIS,
                                                     pmesh.COL_AXIS)),
        b_sharding=NamedSharding(mesh, PartitionSpec()),
        scope=lambda: pmesh.use_grid(mesh),
        flops=2.0 / 3.0 * n ** 3 + 1.5 * n ** 2,
        check=_check)
