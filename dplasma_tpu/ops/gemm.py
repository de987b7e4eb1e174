"""GEMM algorithm family with runtime dispatch.

Reference surface: ``dplasma_zgemm_New_ex`` picks between three
algorithms (src/zgemm_wrapper.c:439-493):

(a) owner-computes default JDF (zgemm_NN.jdf …);
(b) SUMMA pipelined-broadcast variants when C is block-cyclic
    (zgemm_*_summa.jdf, src/zgemm_wrapper.c:79-101,488);
(c) the GPU-resident blocked GEMM with (b, c, d) block sizing and
    LOOK_AHEAD CTL-edge pacing, chosen when the active set approaches
    device memory (zgemm_NN_gpu.jdf:123-152,243-330,
    zgemm_wrapper.c:261-305,474-486), tunable via the info keys
    ``DPLASMA:GEMM:GPU:{b,c,d,look_ahead}``
    (zgemm_wrapper.c:290-334).

TPU-native design:
- (a) is one XLA dot (GSPMD partitions it under a mesh);
- (b) is an *explicit* SUMMA written with ``jax.shard_map``: the k
  dimension advances in panels, each panel broadcast along the mesh
  rows/columns with masked ``psum`` (the ICI analog of the reference's
  pipelined row/column broadcasts). Useful when you want the collective
  schedule pinned rather than left to GSPMD.
- (c) is a footprint-paced blocked GEMM: C advances in (b×c)-tile
  blocks, each accumulated by a ``lax.scan`` over d-tile k-chunks with
  ``look_ahead`` unrolling — the HBM-bounded working-set analog of the
  reference's barrier-paced GPU streaming.

``gemm_ex`` is the dispatcher (the ``_New_ex`` analog), consulting an
:class:`~dplasma_tpu.utils.config.Info` object and the MCA tier.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map

from dplasma_tpu.descriptors import TileMatrix
from dplasma_tpu.kernels import blas as k
from dplasma_tpu.ops.blas3 import _op, gemm as gemm_dot
from dplasma_tpu.parallel import mesh as pmesh
from dplasma_tpu.utils import config


# -- (c) footprint model + streaming variant ---------------------------

@dataclasses.dataclass(frozen=True)
class GemmPlan:
    """Chosen algorithm + blocking (the taskpool-constructor arguments
    the reference derives in dplasma_zgemm_gpu_new)."""

    algo: str                  # "dot" | "summa" | "stream"
    b: int = 0                 # C block rows, in tiles
    c: int = 0                 # C block cols, in tiles
    d: int = 0                 # k-chunk depth, in tiles
    look_ahead: int = 1


def device_memory_bytes(default_gb: float = 16.0) -> int:
    """Best-effort accelerator memory size; the zone-allocator size the
    reference reads from the CUDA device module."""
    try:
        stats = jax.devices()[0].memory_stats() or {}
    except Exception:
        stats = {}  # backend without memory introspection: use default
    if "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    return int(default_gb * 2**30)


def _footprint_bytes(M, N, K, dtype) -> int:
    return (M * K + K * N + M * N) * jnp.dtype(dtype).itemsize


def plan_gemm(C: TileMatrix, A: TileMatrix, B: TileMatrix,
              transa: str = "N", transb: str = "N",
              info: Optional[config.Info] = None,
              algo: str = "auto") -> GemmPlan:
    """Algorithm + blocking selection (zgemm_wrapper.c:439-493 logic,
    memory model at :261-305)."""
    info = info or config.Info()
    M, N = C.shape
    Ka = A.shape[1] if transa == "N" else A.shape[0]

    if algo == "auto":
        if pmesh.active() is not None:
            algo = "summa"
        else:
            try:
                frac = float(config.mca_get("device.hbm_fraction", "0.95"))
            except ValueError:
                frac = 0.95  # malformed MCA value: fall back (mca_get_int
                # semantics, ref PaRSEC MCA params SURVEY §5.6)
            if _footprint_bytes(M, N, Ka, C.dtype) > frac * \
                    device_memory_bytes():
                algo = "stream"
            else:
                algo = "dot"

    if algo != "stream":
        return GemmPlan(algo)

    # blocking for the paced variant: honor info overrides, else size
    # (b, c, d) so one block set fits comfortably (the reference solves
    # the same inequality against GPU memory, zgemm_wrapper.c:261-305)
    mb, nb = C.desc.mb, C.desc.nb
    MT, NT = C.desc.MT, C.desc.NT
    KT = max(1, -(-Ka // nb))
    budget = 0.25 * device_memory_bytes()
    item = jnp.dtype(C.dtype).itemsize

    def fits(b, c, d):
        return (b * mb * c * nb + b * mb * d * nb + d * nb * c * nb) \
            * item <= budget

    b = c = d = 1
    grew = True
    while grew:
        grew = False
        for attr in ("b", "c", "d"):
            nb_, nc_, nd_ = b + (attr == "b"), c + (attr == "c"), \
                d + (attr == "d")
            if nb_ <= MT and nc_ <= NT and nd_ <= KT and \
                    fits(nb_, nc_, nd_):
                b, c, d = nb_, nc_, nd_
                grew = True
    b = info.get_int("DPLASMA:GEMM:GPU:B", b)
    c = info.get_int("DPLASMA:GEMM:GPU:C", c)
    d = info.get_int("DPLASMA:GEMM:GPU:D", d)
    la = info.get_int("DPLASMA:GEMM:GPU:LOOK_AHEAD",
                      config.mca_get_int("gemm.lookahead", 2))
    return GemmPlan("stream", b=min(b, MT), c=min(c, NT), d=min(d, KT),
                    look_ahead=max(1, la))


def gemm_stream(alpha, A: TileMatrix, B: TileMatrix, beta, C: TileMatrix,
                transa: str = "N", transb: str = "N",
                plan: Optional[GemmPlan] = None,
                info: Optional[config.Info] = None) -> TileMatrix:
    """Footprint-paced blocked GEMM (the zgemm_NN_gpu analog): C block
    (bi, cj) accumulated by a k-scan of depth-d chunks, ``look_ahead``
    chunks unrolled per scan step."""
    if plan is None:
        plan = plan_gemm(C, A, B, transa, transb, info, algo="stream")
    mb, nb = C.desc.mb, C.desc.nb
    a = _op(A.zero_pad().data, transa)
    bm = _op(B.zero_pad().data, transb)
    Mp, Kp = a.shape
    Np = bm.shape[1]
    Cp = C.zero_pad()
    out = Cp.data * jnp.asarray(beta, C.dtype)  # jaxlint: ok=J010 (scalar)

    brow = plan.b * mb            # C block rows
    bcol = plan.c * nb            # C block cols
    kdep = plan.d * nb            # k chunk
    # pad k so the scan has uniform chunks (pad region is zeros)
    nk = -(-Kp // kdep)
    ktot = nk * kdep
    if ktot != Kp:
        a = jnp.pad(a, ((0, 0), (0, ktot - Kp)))
        bm = jnp.pad(bm, ((0, ktot - Kp), (0, 0)))
    al = jnp.asarray(alpha, C.dtype)  # jaxlint: ok=J010 (scalar)

    for i0 in range(0, Mp, brow):
        i1 = min(i0 + brow, Mp)
        for j0 in range(0, Np, bcol):
            j1 = min(j0 + bcol, Np)
            arow = a[i0:i1, :]
            bcol_m = bm[:, j0:j1]

            def step(acc, t, arow=arow, bcol_m=bcol_m):
                ak = lax.dynamic_slice_in_dim(arow, t * kdep, kdep, 1)
                bk = lax.dynamic_slice_in_dim(bcol_m, t * kdep, kdep, 0)
                return acc + k.dot(ak, bk), None

            acc = jnp.zeros((i1 - i0, j1 - j0), C.dtype)
            acc, _ = lax.scan(lambda s, t: step(s, t),
                              acc, jnp.arange(nk),
                              unroll=plan.look_ahead)
            out = out.at[i0:i1, j0:j1].add(al * acc)
    return TileMatrix(out, Cp.desc).zero_pad()


# -- (b) explicit SUMMA -------------------------------------------------

def gemm_summa(alpha, A: TileMatrix, B: TileMatrix, beta, C: TileMatrix,
               transa: str = "N", transb: str = "N",
               steps_per_panel: int | None = None) -> TileMatrix:
    """SUMMA over the active P×Q mesh with explicitly scheduled panel
    broadcasts (zgemm_summa JDF analog).

    k advances in panels sized so each panel is owned by exactly one
    mesh row (for B) and one mesh column (for A); masked ``psum``
    broadcasts the panel along the other axis — the ICI realization of
    the reference's pipelined ring broadcasts. ``steps_per_panel`` > 1
    splits each owner's block into that many broadcast panels, so a
    step's matmul overlaps the next panel's broadcast (the pipelined
    lookahead; MCA ``summa_steps``, default 2). Arbitrary shapes are
    edge-padded to the mesh tiling INSIDE this routine (the reference
    SUMMA handles any block-cyclic shape, zgemm_wrapper.c:79-101 —
    the r4 fallback to the GSPMD dot on non-divisible shapes is gone).
    """
    m = pmesh.active()
    if m is None:
        return gemm_dot(alpha, A, B, beta, C, transa, transb)
    if steps_per_panel is None:
        steps_per_panel = config.mca_get_int("gemm.summa_steps", 2)
    Pn = m.shape[pmesh.ROW_AXIS]
    Qn = m.shape[pmesh.COL_AXIS]

    a = _op(A.zero_pad().data, transa)
    bmat = _op(B.zero_pad().data, transb)
    cmat = C.zero_pad().data
    Mp, Kp = a.shape
    Np = bmat.shape[1]

    # panel width: must divide both the p-block (Kp/P) and q-block
    # (Kp/Q) — edge-pad every extent to the mesh quantum (zero rows/
    # cols contribute nothing; C crops after the shard_map)
    lcm = Pn * Qn // math.gcd(Pn, Qn)
    quant = lcm * max(steps_per_panel, 1)
    Mp2 = -(-Mp // Pn) * Pn
    Np2 = -(-Np // Qn) * Qn
    Kp2 = -(-Kp // quant) * quant
    if (Mp2, Np2, Kp2) != (Mp, Np, Kp):
        a = jnp.pad(a, ((0, Mp2 - Mp), (0, Kp2 - Kp)))
        bmat = jnp.pad(bmat, ((0, Kp2 - Kp), (0, Np2 - Np)))
        cmat = jnp.pad(cmat, ((0, Mp2 - Mp), (0, Np2 - Np)))
    kb = Kp2 // quant
    nsteps = Kp2 // kb
    kq, kp = Kp2 // Qn, Kp2 // Pn
    al = jnp.asarray(alpha, C.dtype)  # jaxlint: ok=J010 (scalar)
    be = jnp.asarray(beta, C.dtype)

    def local(a_loc, b_loc, c_loc):
        pid = lax.axis_index(pmesh.ROW_AXIS)
        qid = lax.axis_index(pmesh.COL_AXIS)
        acc = c_loc * be
        for t in range(nsteps):
            # A panel: global k-cols [t*kb, (t+1)*kb) live on mesh col
            owner_q = (t * kb) // kq
            off_q = (t * kb) % kq
            pa = lax.dynamic_slice_in_dim(a_loc, off_q, kb, 1)
            pa = jnp.where(qid == owner_q, pa, jnp.zeros_like(pa))
            pa = lax.psum(pa, pmesh.COL_AXIS)      # broadcast along row
            # B panel: global k-rows live on mesh row owner_p
            owner_p = (t * kb) // kp
            off_p = (t * kb) % kp
            pb = lax.dynamic_slice_in_dim(b_loc, off_p, kb, 0)
            pb = jnp.where(pid == owner_p, pb, jnp.zeros_like(pb))
            pb = lax.psum(pb, pmesh.ROW_AXIS)      # broadcast along col
            acc = acc + al * k.dot(pa, pb)
        return acc

    spec2d = P(pmesh.ROW_AXIS, pmesh.COL_AXIS)
    out = shard_map(
        local, mesh=m,
        in_specs=(spec2d, spec2d, spec2d),
        out_specs=spec2d)(a, bmat, cmat)
    if (Mp2, Np2) != (Mp, Np):
        out = out[:Mp, :Np]
    return TileMatrix(out, C.desc).zero_pad()


# -- dispatcher ---------------------------------------------------------

def gemm_ex(alpha, A: TileMatrix, B: TileMatrix, beta, C: TileMatrix,
            transa: str = "N", transb: str = "N",
            info: Optional[config.Info] = None,
            algo: str = "auto") -> TileMatrix:
    """dplasma_zgemm_New_ex analog: dispatch on mesh/footprint/info."""
    plan = plan_gemm(C, A, B, transa, transb, info, algo)
    if plan.algo == "summa":
        return gemm_summa(alpha, A, B, beta, C, transa, transb)
    if plan.algo == "stream":
        return gemm_stream(alpha, A, B, beta, C, transa, transb, plan)
    return gemm_dot(alpha, A, B, beta, C, transa, transb)


def dag(C: TileMatrix, A: TileMatrix, B: TileMatrix, recorder=None):
    """Record the tile-level owner-computes GEMM DAG (one gemm(m,n,k)
    task per C tile per k panel, chained along k — the zgemm_NN JDF
    accumulation structure) into ``recorder``."""
    from dplasma_tpu import native
    from dplasma_tpu.utils import profiling
    rec = recorder if recorder is not None else profiling.recorder
    MT, NT = C.desc.MT, C.desc.NT
    KT = A.desc.NT
    ranks = native.rank_grid(C.desc.dist, MT, NT)
    for m in range(MT):
        for n in range(NT):
            prev = None
            for kk in range(KT):
                g = rec.task("gemm", m, n, kk, priority=kk,
                             rank=int(ranks[m, n]),
                             reads=[("A", m, kk), ("B", kk, n),
                                    ("C", m, n)],
                             writes=[("C", m, n)])
                if prev is not None:
                    rec.edge(prev, g, "C")
                prev = g
    return rec
