"""Realized 2-D block-cyclic distribution.

The reference's ``parsec_matrix_block_cyclic_t`` (ref
tests/testing_zpotrf.c:100-103, tests/common.c:79-93) owns per-rank
LOCAL tile storage: rank (p,q) holds tiles {(i,j): owner(i)=p,
owner(j)=q} packed contiguously, which is what load-balances the
shrinking trailing submatrix of a factorization. Round-1 carried the
owner-map algebra (parallel/layout.py) but sharded the global array
contiguously, leaving supertiles/offsets inert (VERDICT §2.3).

TPU-native realization: :class:`CyclicMatrix` stores the matrix as a
``(P, Q, mloc, nloc)`` array whose leading axes are sharded one-slab-
per-device over the ('p','q') mesh — each device's slab IS the
reference's local tile storage, cyclic order and all. Conversions to
and from the natural-order global array are two tile-axis gathers
(trace-time index tables from parallel/layout.py).

:func:`potrf_cyclic` then runs the ScaLAPACK-shaped right-looking
Cholesky as a ``shard_map`` program: panel broadcast = masked ``psum``
along 'q', diagonal broadcast = masked ``psum`` along 'p', row-panel
formation = ``all_gather`` along 'p' + cyclic index arithmetic, local
trailing update = one local MXU matmul per step. These are exactly the
collectives the reference's comm engine derives from ``type_remote``
annotations (src/zpotrf_L.jdf:109-114), riding ICI.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from jax import shard_map

from dplasma_tpu.descriptors import Dist, TileMatrix
from dplasma_tpu.parallel import layout
from dplasma_tpu.parallel import mesh as pmesh


@dataclasses.dataclass(frozen=True)
class CyclicDesc:
    M: int
    N: int
    mb: int
    nb: int
    dist: Dist

    @property
    def MT(self):
        return -(-self.M // self.mb)

    @property
    def NT(self):
        return -(-self.N // self.nb)

    @property
    def MTL(self):
        """Local row-tile slots per rank (ceil-uniform)."""
        return max(layout.max_local_count(self.MT, self.dist.P,
                                          self.dist.kp), 1)

    @property
    def NTL(self):
        return max(layout.max_local_count(self.NT, self.dist.Q,
                                          self.dist.kq), 1)


class CyclicMatrix:
    """Block-cyclic distributed matrix: data (P, Q, MTL*mb, NTL*nb)."""

    def __init__(self, data: jax.Array, desc: CyclicDesc):
        self.data = data
        self.desc = desc

    @property
    def dtype(self):
        return self.data.dtype

    # -- conversions ---------------------------------------------------
    @staticmethod
    def from_tile(A: TileMatrix, dist: Dist | None = None,
                  mesh=None) -> "CyclicMatrix":
        """Natural-order TileMatrix -> cyclic local slabs.

        Under a mesh matching the dist grid this routes through the
        memory-bounded all_to_all exchange (:func:`from_tile_a2a` —
        peak per-device bytes O(N^2/PQ)) on accelerator backends,
        where the memory wall is real; the CPU test mesh keeps the
        trace-time gather path (two shard_map compiles per conversion
        shape cost more than they save there). MCA ``cyclic.convert``
        = a2a|gather|auto overrides."""
        d = dist or A.desc.dist
        m_ = mesh or pmesh.active()
        if (m_ is not None and d.P * d.Q > 1
                and m_.shape[pmesh.ROW_AXIS] == d.P
                and m_.shape[pmesh.COL_AXIS] == d.Q
                and _a2a_default()):
            return from_tile_a2a(A, d, m_)
        desc = CyclicDesc(A.desc.M, A.desc.N, A.desc.mb, A.desc.nb, d)
        MT, NT = desc.MT, desc.NT
        mb, nb = desc.mb, desc.nb
        X = A.zero_pad().data  # (MT*mb, NT*nb), natural order
        P, Q = d.P, d.Q
        # row tile table: gi[p, l] = global tile of local slot l on p
        gi = np.array([[layout.global_index(l, p, P, d.kp, d.ip)
                        for l in range(desc.MTL)] for p in range(P)])
        gj = np.array([[layout.global_index(l, q, Q, d.kq, d.jq)
                        for l in range(desc.NTL)] for q in range(Q)])
        rvalid = (gi < MT)
        cvalid = (gj < NT)
        Xr = X.reshape(MT, mb, NT * nb)
        Xr = jnp.where(jnp.asarray(rvalid)[:, :, None, None],
                       Xr[jnp.asarray(gi.clip(max=MT - 1))], 0)
        # (P, MTL, mb, NT*nb) -> columns
        Xc = Xr.reshape(P, desc.MTL * mb, NT, nb)
        Xc = jnp.where(jnp.asarray(cvalid)[None, :, None, :, None],
                       Xc[:, :, jnp.asarray(gj.clip(max=NT - 1))]
                       .transpose(0, 2, 1, 3, 4), 0)
        # (P, Q, MTL*mb, NTL, nb) -> (P, Q, mloc, nloc)
        data = Xc.reshape(P, Q, desc.MTL * mb, desc.NTL * nb)
        m = mesh or pmesh.active()
        if (m is not None and m.shape[pmesh.ROW_AXIS] == P
                and m.shape[pmesh.COL_AXIS] == Q):
            data = jax.lax.with_sharding_constraint(
                data, NamedSharding(m, PartitionSpec(
                    pmesh.ROW_AXIS, pmesh.COL_AXIS, None, None)))
        return CyclicMatrix(data, desc)

    def to_tile(self) -> TileMatrix:
        """Cyclic slabs -> natural-order TileMatrix (the a2a exchange
        under a matching mesh, the gather path otherwise)."""
        desc = self.desc
        d = desc.dist
        m_ = pmesh.active()
        if (m_ is not None and d.P * d.Q > 1
                and m_.shape[pmesh.ROW_AXIS] == d.P
                and m_.shape[pmesh.COL_AXIS] == d.Q
                and _a2a_default()):
            return to_tile_a2a(self, m_)
        MT, NT = desc.MT, desc.NT
        mb, nb = desc.mb, desc.nb
        own_r = np.array([layout.owner(i, d.P, d.kp, d.ip)
                          for i in range(MT)])
        loc_r = np.array([layout.local_index(i, d.P, d.kp)
                          for i in range(MT)])
        own_c = np.array([layout.owner(j, d.Q, d.kq, d.jq)
                          for j in range(NT)])
        loc_c = np.array([layout.local_index(j, d.Q, d.kq)
                          for j in range(NT)])
        Xr = self.data.reshape(d.P, d.Q, desc.MTL, mb,
                               desc.NTL, nb)
        # natural[i, j] = data[own_r[i], own_c[j], loc_r[i], :, loc_c[j], :]
        g = Xr[jnp.asarray(own_r), :, jnp.asarray(loc_r)]
        # (MT, Q, mb, NTL, nb)
        g = g[:, jnp.asarray(own_c), :, jnp.asarray(loc_c)]
        # (NT, MT, mb, nb) — leading advanced-index axes group together
        g = g.transpose(1, 2, 0, 3).reshape(MT * mb, NT * nb)
        out = TileMatrix.zeros(desc.M, desc.N, mb, nb, dist=d)
        full = g[:out.data.shape[0], :out.data.shape[1]]
        return TileMatrix(full, out.desc)


def _a2a_phase(x, axis_name, nt: int, tb: int, P: int, kp: int,
               ip: int, row_axis: bool, mesh, inverse: bool = False):
    """One redistribution phase (rows or columns) between contiguous
    and k-cyclic tile ownership along one mesh axis, as an
    ``all_to_all`` of UNIFORM pieces — peak per-device live bytes stay
    O(local block), never a replicated global array (VERDICT r2
    weak #5: the gather conversions pivot through the full dense
    array).

    ``x``: global array whose ``row_axis ? rows : cols`` are evenly
    contiguous over ``axis_name``; returns the same array with that
    axis k-cyclic (local slots ascending in global tile index).
    ``nt`` tiles of size ``tb`` must satisfy nt % (P*P*kp) == 0
    (callers pad) so every (src, dst) pair exchanges exactly
    nt/(P*P*kp) supertiles.
    """
    c = nt // (P * kp)            # supertiles per contiguous shard
    per = c // P                  # supertiles exchanged per (src,dst)
    stb = kp * tb                 # supertile rows

    def body(loc):
        if not row_axis:
            loc = loc.T
        me = jax.lax.axis_index(axis_name)
        r_eff = (me - ip) % P
        W = loc.shape[1]
        if not inverse:   # contiguous -> cyclic
            # send[d] = my supertiles owned by cyclic rank d, ascending
            d = jnp.arange(P)[:, None]                   # dst
            j = jnp.arange(per)[None, :]                 # piece slot
            t = ((d - ip) - me * c) % P + j * P          # local stile
            rows = (t[..., None] * stb + jnp.arange(stb)).reshape(-1)
            send = loc[rows].reshape(P, per * stb, W)
            recv = jax.lax.all_to_all(send, axis_name, split_axis=0,
                                      concat_axis=0, tiled=False)
            # my cyclic slot l holds global supertile l*P + r_eff,
            # from source s = sg // c at piece slot (sg - s*c) // P
            l = jnp.arange(c)
            sg = l * P + r_eff
            s_src = sg // c
            jj = (sg - s_src * c) // P
            rows2 = (jj[:, None] * stb + jnp.arange(stb)).reshape(-1)
            # one gather by (source, row): never the (c, per*stb, W)
            # stack of whole pieces that recv[s_src] would build
            out = recv[s_src.repeat(stb), rows2].reshape(c * stb, W)
        else:             # cyclic -> contiguous
            # send[d] = my slots whose global supertile lies in d's
            # contiguous range — per CONSECUTIVE slots from d*c//P
            send = loc.reshape(P, per * stb, W)
            recv = jax.lax.all_to_all(send, axis_name, split_axis=0,
                                      concat_axis=0, tiled=False)
            # my contiguous supertile t (global me*c + t) came from
            # cyclic rank ((g % P) + ip) % P at piece slot t // P
            t = jnp.arange(c)
            g = me * c + t
            s_src = (g % P + ip) % P
            jj = t // P
            rows2 = (jj[:, None] * stb + jnp.arange(stb)).reshape(-1)
            out = recv[s_src.repeat(stb), rows2].reshape(c * stb, W)
        return out if row_axis else out.T

    spec = PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS)
    f = shard_map(
        body, mesh=mesh,
        in_specs=spec,
        out_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS))
    return f(x)


def _grow(lslots: int, nb: int, rank, P: int, kp: int, ip: int):
    """Global tile index per local element row (vectorized, dynamic
    rank): g(l) = (l//kp * P + (rank - ip) % P) * kp + l % kp."""
    l = jnp.arange(lslots * nb) // nb
    return ((l // kp) * P + (rank - ip) % P) * kp + l % kp


def _a2a_default() -> bool:
    """Should conversions ride the all_to_all exchange?  MCA
    ``cyclic.convert``: ``a2a``/``gather`` force; ``auto`` = a2a on
    accelerator backends (the memory bound is what the layer exists
    for there), gather on the CPU test mesh (compile cost dominates
    at test scale)."""
    from dplasma_tpu.utils import config as _cfg
    mode = (_cfg.mca_get("cyclic.convert") or "auto").lower()
    if mode == "a2a":
        return True
    if mode == "gather":
        return False
    return jax.default_backend() != "cpu"


def _a2a_geometry(desc: CyclicDesc):
    """Padded tile counts and slab extents shared by BOTH a2a
    directions (they must stay bit-identical for round-trips):
    nt padded so every (src, dst) pair exchanges uniform pieces."""
    d = desc.dist
    MTg = -(-desc.MT // (d.P * d.P * d.kp)) * d.P * d.P * d.kp
    NTg = -(-desc.NT // (d.Q * d.Q * d.kq)) * d.Q * d.Q * d.kq
    return MTg, NTg, MTg // d.P * desc.mb, NTg // d.Q * desc.nb


def from_tile_a2a(A: TileMatrix, dist: Dist | None = None,
                  mesh=None) -> CyclicMatrix:
    """Memory-bounded conversion to cyclic local slabs: two uniform
    ``all_to_all`` phases (rows along 'p', then columns along 'q')
    instead of gathers through a replicated natural-order array —
    peak per-device live bytes stay O(N^2/(P*Q)) plus one exchange
    buffer (VERDICT r2 weak #5 / parsec_redistribute's role,
    ref scalapack_wrappers/common.c:75-83). Needs a mesh matching the
    dist grid; :meth:`CyclicMatrix.from_tile` remains the general
    (gather) path."""
    d = dist or A.desc.dist
    m = mesh or pmesh.active()
    assert m is not None and (
        m.shape[pmesh.ROW_AXIS], m.shape[pmesh.COL_AXIS]) == (d.P, d.Q)
    desc = CyclicDesc(A.desc.M, A.desc.N, A.desc.mb, A.desc.nb, d)
    mb, nb = desc.mb, desc.nb
    MTg, NTg, mloc_g, nloc_g = _a2a_geometry(desc)
    X = A.zero_pad().data
    X = jnp.pad(X, ((0, MTg * mb - X.shape[0]),
                    (0, NTg * nb - X.shape[1])))
    spec2 = NamedSharding(m, PartitionSpec(pmesh.ROW_AXIS,
                                           pmesh.COL_AXIS))
    X = jax.lax.with_sharding_constraint(X, spec2)
    X = _a2a_phase(X, pmesh.ROW_AXIS, MTg, mb, d.P, d.kp, d.ip,
                   True, m)
    X = _a2a_phase(X, pmesh.COL_AXIS, NTg, nb, d.Q, d.kq, d.jq,
                   False, m)
    data = X.reshape(d.P, mloc_g, d.Q, nloc_g).transpose(0, 2, 1, 3)
    data = data[:, :, :desc.MTL * mb, :desc.NTL * nb]
    data = jax.lax.with_sharding_constraint(
        data, NamedSharding(m, PartitionSpec(
            pmesh.ROW_AXIS, pmesh.COL_AXIS, None, None)))
    return CyclicMatrix(data, desc)


def to_tile_a2a(C: CyclicMatrix, mesh=None) -> TileMatrix:
    """Inverse of :func:`from_tile_a2a` — the same two exchange
    phases run backwards (cyclic -> contiguous), same memory bound."""
    desc = C.desc
    d = desc.dist
    m = mesh or pmesh.active()
    assert m is not None and (
        m.shape[pmesh.ROW_AXIS], m.shape[pmesh.COL_AXIS]) == (d.P, d.Q)
    mb, nb = desc.mb, desc.nb
    MTg, NTg, mloc_g, nloc_g = _a2a_geometry(desc)
    data = jnp.pad(C.data, ((0, 0), (0, 0),
                            (0, mloc_g - C.data.shape[2]),
                            (0, nloc_g - C.data.shape[3])))
    X = data.transpose(0, 2, 1, 3).reshape(d.P * mloc_g,
                                           d.Q * nloc_g)
    spec2 = NamedSharding(m, PartitionSpec(pmesh.ROW_AXIS,
                                           pmesh.COL_AXIS))
    X = jax.lax.with_sharding_constraint(X, spec2)
    X = _a2a_phase(X, pmesh.COL_AXIS, NTg, nb, d.Q, d.kq, d.jq,
                   False, m, inverse=True)
    X = _a2a_phase(X, pmesh.ROW_AXIS, MTg, mb, d.P, d.kp, d.ip,
                   True, m, inverse=True)
    out = TileMatrix.zeros(desc.M, desc.N, mb, nb, dist=d)
    return TileMatrix(X[:out.data.shape[0], :out.data.shape[1]],
                      out.desc)


def _slab_coords(desc: CyclicDesc, p, q):
    """Per-element global coordinates of a rank's local slab:
    (grow, gcol) tile ids and (gid, gcid) element ids."""
    d = desc.dist
    grow = _grow(desc.MTL, desc.mb, p, d.P, d.kp, d.ip)
    gcol = _grow(desc.NTL, desc.nb, q, d.Q, d.kq, d.jq)
    gid = grow * desc.mb + jnp.arange(desc.MTL * desc.mb) % desc.mb
    gcid = gcol * desc.nb + jnp.arange(desc.NTL * desc.nb) % desc.nb
    return grow, gcol, gid, gcid


def _seed_pad_diag(A, desc: CyclicDesc, gid, gcid):
    """Well-posed padding for factorizations: put 1.0 on the pad
    diagonal locally (conversions force-zero the pad region, so callers
    cannot pre-set it) — factor blkdiag(A, I)."""
    K = min(desc.M, desc.N)
    KT = min(desc.MT, desc.NT)
    padrow = (gid >= K) & (gid < KT * desc.mb)
    eq = (gid[:, None] == gcid[None, :]) & padrow[:, None]
    return jnp.where(eq, jnp.ones((), A.dtype), A)


def _bcast_q(val, q, qk: int, Q: int, ring: bool, P: int,
             rchunks: int = 0):
    """Panel broadcast along 'q' from owner column ``qk`` (a trace-time
    int): the explicit ICI ring when ``ring`` (wire-optimal — each
    link carries the panel once, started as early as program order
    allows), else the masked-psum emulation (an all-reduce moving 2x
    the bytes — the bit-identical ``ring.enable=off`` path). The owner
    mask is one-hot, so both paths produce IDENTICAL values.
    ``rchunks`` is the PINNED pipelining depth (the wrappers resolve
    MCA ``ring.chunks`` and thread it as a jit static, so an MCA flip
    re-traces instead of replaying a stale cached kernel; 0 = resolve
    at trace time — direct/test callers only)."""
    if ring and Q > 1:
        from dplasma_tpu.kernels import pallas_ring as _pring
        return _pring.ring_bcast(
            val, root=qk, axis=pmesh.COL_AXIS,
            axes=((pmesh.ROW_AXIS, P), (pmesh.COL_AXIS, Q)),
            chunks=rchunks if rchunks > 0 else None)
    return jax.lax.psum(
        jnp.where(q == qk, val, jnp.zeros_like(val)), pmesh.COL_AXIS)


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _potrf_cyclic_jit(data, desc: CyclicDesc, mesh, lookahead: int = 0,
                      ring: bool = False, rchunks: int = 0):
    # ``mesh`` (hashable) is part of the jit key: two same-shaped meshes
    # with different device orders must not share a trace.
    # ``lookahead`` > 0 pipelines the sweep: step k broadcasts and
    # narrowly updates the NEXT panel's block column before issuing
    # the wide trailing matmul, so step k+1's panel chain (its psum
    # collectives + potrf + trsm) is dataflow-independent of step k's
    # MXU-bound update and the compiler/runtime can overlap them —
    # the lookahead the reference gets from PaRSEC running panel
    # tasks as soon as their block-column lands.
    # ``ring`` routes the panel broadcast over the explicit ICI ring
    # (kernels.pallas_ring) instead of the masked psum: with
    # lookahead, the NEXT panel's ring transfer is issued before this
    # step's wide MXU matmul and consumed only at step k+1's panel
    # factorization — the start-early/wait-late overlap schedule.
    d = desc.dist
    P, Q = d.P, d.Q
    mb = desc.mb
    assert desc.mb == desc.nb and desc.M == desc.N
    KT = min(desc.MT, desc.NT)
    mloc = desc.MTL * mb
    nloc = desc.NTL * mb
    cplx = jnp.iscomplexobj(data)

    def ct(x):
        return x.conj().T if cplx else x.T

    def body(local):
        from dplasma_tpu.kernels import blas as kb
        A = local.reshape(mloc, nloc)
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        grow = _grow(desc.MTL, mb, p, P, d.kp, d.ip)      # (mloc,)
        gcol = _grow(desc.NTL, mb, q, Q, d.kq, d.jq)      # (nloc,)
        pan_next = None
        for k in range(KT):
            pk = layout.owner(k, P, d.kp, d.ip)
            qk = layout.owner(k, Q, d.kq, d.jq)
            lrk = layout.local_index(k, P, d.kp)
            lck = layout.local_index(k, Q, d.kq)
            # 1) broadcast block column k along 'q' (panel bcast) —
            # or take the lookahead-carried pre-updated column
            cs = jax.lax.dynamic_slice_in_dim(A, lck * mb, mb, axis=1)
            if pan_next is None:
                pan = _bcast_q(cs, q, qk, Q, ring, P, rchunks)
            else:
                pan = pan_next
            # 2) broadcast diagonal tile along 'p'
            dt = jax.lax.dynamic_slice_in_dim(pan, lrk * mb, mb, axis=0)
            ddt = jax.lax.psum(
                jnp.where(p == pk, dt, jnp.zeros_like(dt)),
                pmesh.ROW_AXIS)
            Lkk = kb.potrf(ddt, lower=True)
            # 3) local panel solve (rows strictly below k)
            sol = kb.trsm(Lkk, pan, side="R", lower=True, trans="C")
            below = (grow > k)[:, None]
            diagrow = ((grow == k) & (p == pk))[:, None]
            at_k = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros_like(pan), Lkk, lrk * mb, axis=0)
            Lpan = jnp.where(below, sol, jnp.where(diagrow, at_k, 0))
            # 4) owners write the factored panel back
            keep = (grow >= k)[:, None]
            newcs = jnp.where(keep, Lpan, cs)
            A = jnp.where(q == qk,
                          jax.lax.dynamic_update_slice_in_dim(
                              A, newcs, lck * mb, axis=1), A)
            # 5) row panel: all_gather along 'p' + cyclic row pick
            allg = jax.lax.all_gather(Lpan, pmesh.ROW_AXIS)
            allg = allg.reshape(P * mloc, mb)
            jt = gcol                                   # (nloc,) tiles
            pj = (jt // d.kp + d.ip) % P
            lj = (jt // (d.kp * P)) * d.kp + jt % d.kp
            idx = pj * mloc + lj * mb + jnp.arange(nloc) % mb
            W = jnp.where((jt > k)[:, None], allg[idx], 0)  # (nloc, mb)
            Lbelow = jnp.where(below, Lpan, 0)
            # 5b) lookahead: broadcast the STALE next panel column and
            # apply step k's rank-mb update to it narrowly (allg is
            # replicated along 'q', so the catch-up is local compute)
            # — next step's panel chain never waits for the wide matmul
            if lookahead > 0 and k + 1 < KT:
                qk1 = layout.owner(k + 1, Q, d.kq, d.jq)
                lck1 = layout.local_index(k + 1, Q, d.kq)
                pk1 = layout.owner(k + 1, P, d.kp, d.ip)
                lrk1 = layout.local_index(k + 1, P, d.kp)
                cs1 = jax.lax.dynamic_slice_in_dim(A, lck1 * mb, mb,
                                                   axis=1)
                # with ring on, this transfer STARTS here — before the
                # wide trailing matmul below — and is consumed only at
                # step k+1's panel factorization (the overlap window)
                stale = _bcast_q(cs1, q, qk1, Q, ring, P, rchunks)
                Lk1 = allg[pk1 * mloc + lrk1 * mb:
                           pk1 * mloc + (lrk1 + 1) * mb]
                pan_next = stale - kb.dot(Lbelow, ct(Lk1))
            else:
                pan_next = None
            # 6) local trailing update (one MXU matmul)
            A = A - kb.dot(Lbelow, ct(W))
        return A.reshape(1, 1, mloc, nloc)

    f = shard_map(
        body, mesh=mesh,
        in_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                               None),
        out_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None),
        # pallas_call has no replication rule: the ring path must opt
        # out of shard_map's rep check (the off path keeps it — its
        # traced program is bit-identical to the pre-ring kernels)
        **({"check_vma": False} if ring else {}))
    return f(data)


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7, 8))
def _getrf_cyclic_jit(data, desc: CyclicDesc, mesh,
                      lookahead: int = 0, panel: str = "chain",
                      ring: bool = False, rchunks: int = 0,
                      pivot: bool = True, update_dtype=None):
    """Distributed tournament-pivoting LU over cyclic local slabs —
    the reference's hand-distributed parallel panel
    (src/zgetrf_ptgpanel.jdf: per-rank panel elimination + pivot
    exchange over MPI) as a shard_map program: each row-rank elects mb
    candidate pivot rows from its local slab with one local LU, an
    all_gather along 'p' stages the playoff, a replicated LU of the
    P*mb candidates picks the winners (CALU tournament — same pivot
    quality class as the reference's distributed partial pivoting),
    and winner rows are exchanged by masked psum. Factor rows stay in
    their owners' slabs (pivoting is deferred to the returned global
    permutation, never materialized as row motion — on TPU a gather at
    the end beats KT rounds of row swaps over ICI).

    ``pivot=False`` elects nothing: the rows of tile k are step k's
    winners, their current values reach every row rank by the
    exchange's masked psum (or ring) and every rank factors the
    diagonal block without pivoting — the elimination order is the
    identity (the rest of the step is shared). ``update_dtype``
    (``jnp.bfloat16``) rounds the Schur update's and the lookahead's
    operands to it, accumulating in the slabs' dtype; the panel's
    triangular solves keep the slabs' precision.

    Returns (local factor slabs, win_gids (KT, mb) global element-row
    ids in elimination order, active_left (P, mloc) bools)."""
    d = desc.dist
    P, Q = d.P, d.Q
    mb = desc.mb
    assert desc.mb == desc.nb, "getrf_cyclic needs square tiles"
    KT = min(desc.MT, desc.NT)
    mloc = desc.MTL * mb
    nloc = desc.NTL * mb

    def body(local):
        from dplasma_tpu.kernels import blas as kb
        from dplasma_tpu.observability.phases import span
        A = local.reshape(mloc, nloc)
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        grow, gcol, gid, gcid = _slab_coords(desc, p, q)
        A = _seed_pad_diag(A, desc, gid, gcid)
        active = jnp.ones((mloc,), bool)
        wins = []
        pan_next = None
        for k in range(KT):
            qk = layout.owner(k, Q, d.kq, d.jq)
            lck = layout.local_index(k, Q, d.kq)
            with span("panel", timed=False):
                # 1) panel broadcast along 'q' — or the lookahead-carried
                # pre-updated next column from the previous step
                cs = jax.lax.dynamic_slice_in_dim(A, lck * mb, mb, axis=1)
                if pan_next is None:
                    with span("bcast", timed=False):
                        pan = _bcast_q(cs, q, qk, Q, ring, P, rchunks)
                else:
                    pan = pan_next
                panm = jnp.where(active[:, None], pan, 0)
                if pivot:
                    # 2) local candidate election (one local LU per
                    #    row-rank, concurrently across 'p' — the distributed
                    #    panel). The panel engine selects the
                    #    election/playoff kernel: rec = the
                    #    blocked-recursive fused panel (kernels.panels, no
                    #    vendor custom call), chain = lax.linalg.lu
                    #    (bit-identical pre-engine route). Local work only —
                    #    the collective schedule is IDENTICAL either way
                    #    (spmdcheck's exact-count contract holds per kernel).
                    with span("elect", timed=False):
                        if panel == "rec":
                            from dplasma_tpu.kernels import panels as _panels
                            _, cperm = _panels.lu_panel_rec(panm)
                        else:
                            _, _, cperm = jax.lax.linalg.lu(panm)
                        cand_pos = cperm[:mb]                  # (mb,) local
                        cands = panm[cand_pos]
                    # 3) playoff: all_gather candidates along 'p',
                    # replicated LU
                    with span("playoff", timed=False):
                        allc = jax.lax.all_gather(cands, pmesh.ROW_AXIS)
                        allid = jax.lax.all_gather(gid[cand_pos],
                                                   pmesh.ROW_AXIS)
                        if panel == "rec":
                            lu2, perm2 = _panels.lu_panel_rec(
                                allc.reshape(P * mb, mb))
                        else:
                            lu2, _, perm2 = jax.lax.linalg.lu(
                                allc.reshape(P * mb, mb))
                        wr = perm2[:mb]                        # stack index
                        win_gids = allid.reshape(P * mb)[wr]
                        top = lu2[:mb]               # packed L11\U11 rows
                    wins.append(win_gids)
                    # 4) my winners -> local rows; retire them from the
                    # active set
                    mine = (wr // mb) == p
                    win_lrow = jnp.where(mine, cand_pos[wr % mb], mloc)
                    elim = jnp.zeros((mloc + 1,), bool).at[win_lrow].set(
                        True, mode="drop")[:mloc]
                    # 5) winner rows' current values for MY columns (masked
                    #    psum along 'p' — the pivot-row exchange)
                    with span("exchange", timed=False):
                        sel = jnp.where(mine[:, None],
                                        A[jnp.where(mine, win_lrow, 0)], 0)
                        if ring and P > 1:
                            # winner rows ride the explicit 'p' ring: P-1
                            # shift-and-add hops (kernels.pallas_ring).
                            # Winner rows have exactly one owner, so the
                            # contributions are disjoint and the sum is
                            # bit-identical to psum's.
                            from dplasma_tpu.kernels import pallas_ring \
                                as _pring
                            wrows = _pring.ring_allreduce(
                                sel, axis=pmesh.ROW_AXIS,
                                axes=((pmesh.ROW_AXIS, P),
                                      (pmesh.COL_AXIS, Q)))
                        else:
                            wrows = jax.lax.psum(sel, pmesh.ROW_AXIS)
                else:
                    # 2-5) no election: the rows of tile k on their
                    # owner row rank are the winners; their current
                    # values (my columns, then the panel block) reach
                    # every row rank by the exchange's masked psum
                    pk = layout.owner(k, P, d.kp, d.ip)
                    lrk = layout.local_index(k, P, d.kp)
                    rows = slice(lrk * mb, (lrk + 1) * mb)
                    mine = jnp.broadcast_to(p == pk, (mb,))
                    win_lrow = jnp.where(mine, lrk * mb + jnp.arange(mb),
                                         mloc)
                    win_gids = k * mb + jnp.arange(mb, dtype=gid.dtype)
                    sel = jnp.where(p == pk, jnp.concatenate(
                        [A[rows], pan[rows]], axis=1), 0)
                    if ring and P > 1:
                        from dplasma_tpu.kernels import pallas_ring \
                            as _pring
                        both = _pring.ring_allreduce(
                            sel, axis=pmesh.ROW_AXIS,
                            axes=((pmesh.ROW_AXIS, P),
                                  (pmesh.COL_AXIS, Q)))
                    else:
                        both = jax.lax.psum(sel, pmesh.ROW_AXIS)
                    wrows = both[:, :nloc]
                    top = kb.getrf_nopiv(both[:, nloc:])
                    wins.append(win_gids)
                    elim = jnp.zeros((mloc + 1,), bool).at[win_lrow].set(
                        True, mode="drop")[:mloc]
                u12 = kb.trsm(top, wrows, side="L", lower=True, unit=True)
                trailing = (gcol > k)[None, :]
                u12 = jnp.where(trailing, u12, 0)
            with span("update", timed=False):
                # 6) local L column + Schur update of my trailing
                # columns
                l21 = kb.trsm(jnp.triu(top), panm, side="R", lower=False)
                l21 = jnp.where((active & ~elim)[:, None], l21, 0)
                # 6b) lookahead: assemble the NEXT panel column —
                # narrow Schur update + the winner-row substitution of
                # step 8, broadcast along 'q' — BEFORE the wide local
                # update, so step k+1's candidate election and playoff
                # collectives overlap this step's MXU-bound Schur matmul
                if lookahead > 0 and k + 1 < KT:
                    with span("lookahead", timed=False):
                        qk1 = layout.owner(k + 1, Q, d.kq, d.jq)
                        lck1 = layout.local_index(k + 1, Q, d.kq)
                        cs1 = jax.lax.dynamic_slice_in_dim(
                            A, lck1 * mb, mb, axis=1)
                        u12k1 = jax.lax.dynamic_slice_in_dim(
                            u12, lck1 * mb, mb, axis=1)
                        coln = cs1 - kb.dot_in(l21, u12k1,
                                               update_dtype)
                        coln = coln.at[win_lrow].set(
                            jnp.where(mine[:, None], u12k1,
                                      coln[jnp.where(mine, win_lrow, 0)]),
                            mode="drop")
                        # ring: step k+1's panel transfer starts HERE,
                        # before the wide Schur matmul below (the
                        # overlap window)
                        with span("bcast", timed=False):
                            pan_next = _bcast_q(coln, q, qk1, Q, ring, P,
                                                rchunks)
                else:
                    pan_next = None
                A = A - kb.dot_in(l21, u12, update_dtype)
                # 7) owners write the L column into the panel block
                newcs = jnp.where((active & ~elim)[:, None], l21, cs)
                A = jnp.where(q == qk,
                              jax.lax.dynamic_update_slice_in_dim(
                                  A, newcs, lck * mb, axis=1), A)
                # 8) winner rows take their factor content (U12 on
                #    trailing columns, packed L11\U11 in the panel block)
                row_new = jnp.where(trailing, u12, wrows)
                pancols = jnp.zeros((nloc,), bool).at[
                    lck * mb + jnp.arange(mb)].set(q == qk)
                paste = jnp.zeros((mb, nloc), A.dtype)
                paste = jax.lax.dynamic_update_slice_in_dim(
                    paste, top, lck * mb, axis=1)
                row_new = jnp.where(pancols[None, :], paste, row_new)
                A = A.at[win_lrow].set(
                    jnp.where(mine[:, None], row_new,
                              A[jnp.where(mine, win_lrow, 0)]),
                    mode="drop")
            active = active & ~elim
        winsA = jnp.stack(wins)                            # (KT, mb)
        return (A.reshape(1, 1, mloc, nloc),
                winsA[None, None],
                active[None, None])

    f = shard_map(
        body, mesh=mesh,
        in_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                               None),
        out_specs=(PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                 None),
                   PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                 None),
                   PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None)),
        **({"check_vma": False} if ring else {}))
    return f(data)


def getrf_cyclic(A: CyclicMatrix, pivot: bool = True,
                 update_dtype=None):
    """Distributed partial-pivoting LU on block-cyclic local storage
    (the pdgetrf / zgetrf_ptgpanel shape). Returns
    (factor CyclicMatrix — rows in place, perm) with the
    :func:`dplasma_tpu.ops.lu.getrf_1d` contract ``A[perm] = L U``
    after gathering rows by ``perm``. ``pivot=False`` factors without
    pivoting (``perm`` is the identity) and ``update_dtype`` sets the
    trailing updates' operand type (:func:`_getrf_cyclic_jit`)."""
    m = pmesh.active()
    assert m is not None, "getrf_cyclic needs an active mesh (use_grid)"
    ms = (m.shape[pmesh.ROW_AXIS], m.shape[pmesh.COL_AXIS])
    assert ms == (A.desc.dist.P, A.desc.dist.Q), (
        f"mesh {ms} != dist grid {(A.desc.dist.P, A.desc.dist.Q)}")
    from dplasma_tpu.kernels import panels as _panels
    pk = _panels.panel_kernel("lu")
    if pk == "pallas":   # no fused pallas panel inside shard_map
        pk = "rec"
    ring = _cyclic_ring(A.desc, A.dtype, m, need_row=True)
    rch = _ring_chunks(ring)
    _ring_span(A, m, ring, rch)
    out, wins, active = _getrf_cyclic_jit(A.data, A.desc, m,
                                          _cyclic_lookahead(), pk,
                                          ring, rch, pivot, update_dtype)
    desc = A.desc
    d = desc.dist
    mb = desc.mb
    Mp = desc.MT * mb
    if not pivot:
        return CyclicMatrix(out, desc), jnp.arange(Mp, dtype=wins.dtype)
    KT = min(desc.MT, desc.NT)
    win_flat = wins[0, 0].reshape(-1)
    nleft = Mp - KT * mb  # static: winners cover exactly KT*mb rows
    if nleft:
        # leftover rows (tall case), ascending global id, excluding
        # over-allocated pad slots — traced (getrf_cyclic stays
        # jit-compatible; the row-id table itself is static layout)
        P = d.P
        mloc = desc.MTL * mb
        gids = jnp.asarray(np.concatenate([
            np.asarray([layout.global_index(l // mb, p, P, d.kp, d.ip)
                        * mb + l % mb for l in range(mloc)])
            for p in range(P)]))
        act = active[:, 0].reshape(-1)
        key = jnp.where(act & (gids < Mp), gids, Mp + 1)
        left = jnp.sort(key)[:nleft].astype(win_flat.dtype)
        perm = jnp.concatenate([win_flat, left])
    else:
        perm = win_flat
    return CyclicMatrix(out, desc), perm[:Mp]


def _cqr2_panel(x, M: int, mb: int, eps: float, pdiag, ldiag, p, ct,
                axis: str = None):
    """Distributed CholeskyQR2 + TSQR-HR panel factorization (shared
    by the QR, herbt, and ge2gb sweeps; must run inside a shard_map
    body).

    ``x``: masked local panel rows (mloc, mb), distributed along
    ``axis`` (default 'p'; the ge2gb LQ half passes 'q' — the same
    panel algebra in column coordinates); ``pdiag``/``ldiag``: owner
    rank and local tile slot of the diagonal tile along that axis.
    Returns (packedtop, V1, T, Ub, q2): the packed top block
    (sign-adjusted R above, V1 below), the replicated T, the
    reconstruction's U (for V2 = q2 U^{-1}), and the distributed
    orthonormal factor q2."""
    from dplasma_tpu.kernels import blas as kb
    from dplasma_tpu.kernels import householder as hh

    ax = axis or pmesh.ROW_AXIS
    eye = jnp.eye(mb, dtype=x.dtype)

    def cqr(xx, shift):
        g = jax.lax.psum(kb.dot(xx, xx, ta=True, conj_a=True), ax)
        if shift:
            sft = 11.0 * (M * mb + mb * (mb + 1)) * eps
            g = g + (sft * jnp.trace(g).real.astype(
                g.real.dtype)) * eye
        ell = kb.potrf(g, lower=True)
        return kb.trsm(ell, xx, side="R", lower=True, trans="C"), ell

    q1, l1 = cqr(x, True)
    q2, l2 = cqr(q1, False)
    R = ct(kb.dot(l1, l2))            # R2 R1, replicated
    topq = jax.lax.psum(
        jnp.where(p == pdiag,
                  jax.lax.dynamic_slice_in_dim(q2, ldiag * mb, mb,
                                               axis=0),
                  jnp.zeros((mb, mb), x.dtype)),
        ax)
    packedtop, V1, T, Ub = hh.householder_reconstruct(
        topq, R, return_u=True)
    return packedtop, V1, T, Ub, q2


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _geqrf_cyclic_jit(data, desc: CyclicDesc, mesh,
                      lookahead: int = 0, ring: bool = False,
                      rchunks: int = 0):
    """Distributed blocked Householder QR over cyclic local slabs —
    BASELINE config #3's hierarchical QR (ref src/zgeqrf_param.jdf +
    dplasma_hqr.c high-level trees) re-designed for the mesh: each
    panel is factored by distributed CholeskyQR2 (the Gram psum along
    'p' IS the high-level reduction tree — ranks are the TS domains,
    and ICI's all-reduce replaces the reference's explicit
    FLAT/GREEDY combining trees) followed by TSQR-HR Householder
    reconstruction, so the factor comes out in the standard compact-WY
    packed layout (V below the diagonal, R on/above, T per panel —
    interchangeable with ops.qr.geqrf output). Trailing updates are
    V^H C psum along 'p' + one local MXU matmul per rank.

    Panels must be numerically full rank (pad columns are identity-
    seeded; the Gram squares the condition — same envelope as the
    cholqr panel path everywhere else in the package).

    Returns (local factor slabs, Ts (KT, mb, mb) replicated).
    """
    from dplasma_tpu.kernels import blas as kb

    d = desc.dist
    P, Q = d.P, d.Q
    mb = desc.mb
    assert desc.mb == desc.nb, "geqrf_cyclic needs square tiles"
    KT = min(desc.MT, desc.NT)
    mloc = desc.MTL * mb
    nloc = desc.NTL * mb
    cplx = jnp.iscomplexobj(data)

    def ct(x):
        return x.conj().T if cplx else x.T

    eps = float(jnp.finfo(
        jnp.zeros((), data.dtype).real.dtype).eps)

    def body(local):
        A = local.reshape(mloc, nloc)
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        grow, gcol, gid, gcid = _slab_coords(desc, p, q)
        # identity-seed pad columns (zero pad panels break the Gram)
        A = _seed_pad_diag(A, desc, gid, gcid)
        Ts = []
        pan_next = None
        for k in range(KT):
            pk = layout.owner(k, P, d.kp, d.ip)
            qk = layout.owner(k, Q, d.kq, d.jq)
            lrk = layout.local_index(k, P, d.kp)
            lck = layout.local_index(k, Q, d.kq)
            cs = jax.lax.dynamic_slice_in_dim(A, lck * mb, mb, axis=1)
            if pan_next is None:
                pan = _bcast_q(cs, q, qk, Q, ring, P, rchunks)
            else:
                pan = pan_next
            act = (gid >= k * mb)[:, None]
            x = jnp.where(act, pan, 0)
            # distributed CholeskyQR2 + TSQR-HR (shared helper), U
            # exposed for the distributed rows' V2 = q2 U^{-1}
            packedtop, V1, T, Ub, q2 = _cqr2_panel(
                x, desc.M, mb, eps, pk, lrk, p, ct)
            Ts.append(T)
            # local V: V1 rows on the diag owner, q2 Ub^{-1} below
            below = (gid >= (k + 1) * mb)[:, None]
            V2 = kb.trsm(Ub, q2, side="R", lower=False)
            v1slab = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros_like(q2), V1, lrk * mb, axis=0)
            diagrow = ((grow == k) & (p == pk))[:, None]
            Vloc = jnp.where(below, V2, jnp.where(diagrow, v1slab, 0))
            # trailing + R12 update: C <- C - V (T^H (V^H C))
            W = jax.lax.psum(kb.dot(Vloc, A, ta=True, conj_a=True),
                             pmesh.ROW_AXIS)
            # lookahead: assemble + broadcast the NEXT panel column
            # with a narrow compact-WY apply before the wide trailing
            # update — step k+1's distributed CholeskyQR2 (its Gram
            # psums) overlaps this step's MXU-bound apply
            if lookahead > 0 and k + 1 < KT:
                qk1 = layout.owner(k + 1, Q, d.kq, d.jq)
                lck1 = layout.local_index(k + 1, Q, d.kq)
                cs1 = jax.lax.dynamic_slice_in_dim(A, lck1 * mb, mb,
                                                   axis=1)
                Wk1 = jax.lax.dynamic_slice_in_dim(W, lck1 * mb, mb,
                                                   axis=1)
                updn = kb.dot(Vloc, kb.dot(T, Wk1, ta=True,
                                           conj_a=True))
                # ring: step k+1's panel transfer starts HERE, before
                # the wide compact-WY apply below (the overlap window)
                pan_next = _bcast_q(cs1 - updn, q, qk1, Q, ring, P,
                                    rchunks)
            else:
                pan_next = None
            upd = kb.dot(Vloc, kb.dot(T, W, ta=True, conj_a=True))
            trail = (gcid >= (k + 1) * mb)[None, :]
            A = A - jnp.where(trail, upd, 0)
            # owners write the packed panel column
            at_k = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros_like(cs), packedtop, lrk * mb, axis=0)
            newcs = jnp.where(below, V2,
                              jnp.where(diagrow, at_k, cs))
            A = jnp.where(q == qk,
                          jax.lax.dynamic_update_slice_in_dim(
                              A, newcs, lck * mb, axis=1), A)
        TsA = jnp.stack(Ts)                       # (KT, mb, mb)
        return A.reshape(1, 1, mloc, nloc), TsA[None, None]

    f = shard_map(
        body, mesh=mesh,
        in_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                               None),
        out_specs=(PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                 None),
                   PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                 None, None)),
        **({"check_vma": False} if ring else {}))
    return f(data)


@partial(jax.jit, static_argnums=(1, 2))
def _herbt_cyclic_jit(data, desc: CyclicDesc, mesh):
    """Distributed Hermitian dense -> band reduction over cyclic local
    slabs (the dplasma_zherbt role, ref src/zherbt_L.jdf, composed by
    zheev_wrapper.c:96-103 — BASELINE config #5's stage 1). Panel k
    QR-factors block column k below the first subdiagonal block by
    distributed CholeskyQR2 + TSQR-HR (the geqrf_cyclic panel, shifted
    one tile down), then applies the TWO-SIDED compact-WY update
    A <- Q^H A Q with four collectives per panel:

      S  = psum_p(V^H A)            row-space inner products
      Vc = all_gather_p + cyclic pick   V in column coordinates
      Y  = psum_q(A Vc), Z = psum_q(P1 Vc)
      A -= V (T^H S)  +  mask((Y - V Z) T) Vc^H

    — every heavy op a local MXU matmul. Requires BOTH triangles
    stored (full Hermitian slabs); leaves the bandwidth-mb band, both
    triangles, V/T discarded (jobz=N — eigenvalues only)."""
    from dplasma_tpu.kernels import blas as kb

    d = desc.dist
    P, Q = d.P, d.Q
    mb = desc.mb
    assert desc.mb == desc.nb and desc.M == desc.N
    KT = desc.MT
    mloc = desc.MTL * mb
    nloc = desc.NTL * mb
    cplx = jnp.iscomplexobj(data)

    def ct(x):
        return x.conj().T if cplx else x.T

    eps = float(jnp.finfo(
        jnp.zeros((), data.dtype).real.dtype).eps)

    def body(local):
        A = local.reshape(mloc, nloc)
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        grow, gcol, gid, gcid = _slab_coords(desc, p, q)
        A = _seed_pad_diag(A, desc, gid, gcid)
        # column-space pick tables (the herk/potrf row formation).
        # Unused ceil-uniform slots (gcol >= MT on uneven supertile
        # splits) MUST pick zero: the clipped gather would hand them
        # real V rows, the update would write garbage into the unused
        # columns, and the next panel's Y = A @ Vc contraction reads
        # every local column (r4 debug, kp=kq=2 N=96 case)
        jt = gcol
        pj = (jt // d.kp + d.ip) % P
        lj = (jt // (d.kp * P)) * d.kp + jt % d.kp
        colidx = jnp.clip(pj * mloc + lj * mb + jnp.arange(nloc) % mb,
                          0, P * mloc - 1)
        colvalid = (jt < desc.MT)[:, None]
        for k in range(KT - 1):
            qk = layout.owner(k, Q, d.kq, d.jq)
            lck = layout.local_index(k, Q, d.kq)
            pk = layout.owner(k, P, d.kp, d.ip)
            lrk = layout.local_index(k, P, d.kp)
            pk1 = layout.owner(k + 1, P, d.kp, d.ip)
            lrk1 = layout.local_index(k + 1, P, d.kp)
            e = (k + 1) * mb
            # 1) panel broadcast along 'q', masked below the band
            cs = jax.lax.dynamic_slice_in_dim(A, lck * mb, mb, axis=1)
            pan = jax.lax.psum(
                jnp.where(q == qk, cs, jnp.zeros_like(cs)),
                pmesh.COL_AXIS)
            below = (gid >= e)[:, None]
            x = jnp.where(below, pan, 0)
            # 2) distributed CholeskyQR2 + TSQR-HR (diag tile = k+1).
            # The applied Q produces the sign-adjusted R of the
            # reconstruction (packedtop's upper triangle), NOT the raw
            # cholqr R — writing raw R breaks the similarity (r4)
            packedtop, V1, T, Ub, q2 = _cqr2_panel(
                x, desc.M, mb, eps, pk1, lrk1, p, ct)
            Rw = jnp.triu(packedtop)
            strict = (gid >= e + mb)[:, None]
            V2 = kb.trsm(Ub, q2, side="R", lower=False)
            v1slab = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros_like(q2), V1, lrk1 * mb, axis=0)
            diagrow1 = ((grow == k + 1) & (p == pk1))[:, None]
            Vloc = jnp.where(strict, V2,
                             jnp.where(diagrow1, v1slab, 0))
            # 3) two-sided update, all local MXU matmuls + psums
            S = jax.lax.psum(kb.dot(Vloc, A, ta=True, conj_a=True),
                             pmesh.ROW_AXIS)          # (mb, nloc)
            P1 = kb.dot(T, S, ta=True, conj_a=True)   # T^H S
            allv = jax.lax.all_gather(Vloc, pmesh.ROW_AXIS)
            Vc = jnp.where(colvalid,
                           allv.reshape(P * mloc, mb)[colidx], 0)
            Y = jax.lax.psum(kb.dot(A, Vc), pmesh.COL_AXIS)
            Z = jax.lax.psum(kb.dot(P1, Vc), pmesh.COL_AXIS)
            W2 = kb.dot(Y - kb.dot(Vloc, Z), T)
            W2 = jnp.where(below, W2, 0)
            A = A - kb.dot(Vloc, P1) - kb.dot(W2, ct(Vc))
            # 4) owners write the reduced panel column (R at tile k+1,
            #    zeros below) and its mirror row strip
            at_k1 = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros_like(cs), Rw, lrk1 * mb, axis=0)
            newcs = jnp.where(below,
                              jnp.where(diagrow1, at_k1, 0), cs)
            A = jnp.where(q == qk,
                          jax.lax.dynamic_update_slice_in_dim(
                              A, newcs, lck * mb, axis=1), A)
            rows = jax.lax.dynamic_slice_in_dim(A, lrk * mb, mb,
                                                axis=0)
            keep = (gcid < e)[None, :]
            strip = jnp.where(keep, rows, 0)
            at_c1 = jnp.zeros_like(rows)
            qk1 = layout.owner(k + 1, Q, d.kq, d.jq)
            lck1 = layout.local_index(k + 1, Q, d.kq)
            at_c1 = jax.lax.dynamic_update_slice_in_dim(
                at_c1, ct(Rw), lck1 * mb, axis=1)
            strip = jnp.where((q == qk1) & ~keep, at_c1, strip)
            A = jnp.where(p == pk,
                          jax.lax.dynamic_update_slice_in_dim(
                              A, strip, lrk * mb, axis=0), A)
        return A.reshape(1, 1, mloc, nloc)

    f = shard_map(
        body, mesh=mesh,
        in_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                               None),
        out_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None))
    return f(data)


def herbt_cyclic(A: CyclicMatrix) -> CyclicMatrix:
    """Distributed dense Hermitian -> band (bandwidth mb) reduction on
    block-cyclic local storage (dplasma_zherbt over
    parsec_matrix_block_cyclic; stage 1 of the zheev chain). ``A``
    must store BOTH triangles (full Hermitian slabs)."""
    m = _mesh_of(A)
    assert A.desc.mb == A.desc.nb and A.desc.M == A.desc.N
    # the last panel must have a full mb real rows below the band —
    # with N % mb != 0 its CholeskyQR Gram would be singular (there
    # are no pad rows to identity-seed: panel columns are all real)
    assert A.desc.M % A.desc.mb == 0, "herbt_cyclic: need N % mb == 0"
    return CyclicMatrix(_herbt_cyclic_jit(A.data, A.desc, m), A.desc)


@partial(jax.jit, static_argnums=(1, 2))
def _band_extract_cyclic_jit(data, desc: CyclicDesc, mesh):
    """Lower band (bandwidth mb) of a Hermitian cyclic matrix as
    per-row diagonal storage: out[global row i, d] = A(i, i-d),
    d = 0..mb. One masked psum along 'q' (each rank contributes the
    band entries whose COLUMNS it owns) + an all_gather along 'p' —
    total bytes moved O(N*mb), not the O(N^2) full-matrix exchange
    (ADVICE r4 item 3)."""
    d = desc.dist
    P, Q = d.P, d.Q
    mb = desc.mb
    mloc = desc.MTL * mb
    nloc = desc.NTL * mb

    def body(loc):
        A = loc.reshape(mloc, nloc)
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        _, _, gid, gcid = _slab_coords(desc, p, q)
        offs = jnp.arange(mb + 1)
        # my contribution: band[r, d] = A_local[r, c] where
        # gcid[c] == gid[r] - d (only if I own that column)
        tgt = gid[:, None] - offs[None, :]              # (mloc, mb+1)
        # column position lookup: local col of global id g (if mine)
        t = jnp.clip(tgt, 0, desc.N - 1)
        ct_ = t // mb
        qj = (ct_ // d.kq + d.jq) % Q
        lj = (ct_ // (d.kq * Q)) * d.kq + ct_ % d.kq
        colpos = jnp.clip(lj * mb + t % mb, 0, nloc - 1)
        mine = (qj == q) & (tgt >= 0)
        vals = jnp.take_along_axis(A, colpos, axis=1)
        band = jnp.where(mine, vals, 0)
        band = jax.lax.psum(band, pmesh.COL_AXIS)       # (mloc, mb+1)
        allb = jax.lax.all_gather(band, pmesh.ROW_AXIS)
        return allb.reshape(1, 1, P * mloc, mb + 1)

    f = shard_map(
        body, mesh=mesh,
        in_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                               None),
        out_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None))
    out = f(data)
    # every (p, q) holds the same replicated gather; take rank (0, 0)
    # and reorder the cyclic row slots to natural order
    stacked = out[0, 0]                                  # (P*mloc, mb+1)
    # natural[i] = stacked[owner(i)*mloc + local_slot(i)]
    MT = desc.MT
    own = np.array([layout.owner(i, P, d.kp, d.ip) for i in range(MT)])
    locr = np.array([layout.local_index(i, P, d.kp) for i in range(MT)])
    idx = (own[:, None] * desc.MTL + locr[:, None]) * mb + \
        np.arange(mb)[None, :]
    return stacked[jnp.asarray(idx.reshape(-1))][:desc.M]


def heev_cyclic(A: CyclicMatrix):
    """Distributed Hermitian eigenvalues (BASELINE config #5; the
    dplasma_zheev composition, ref src/zheev_wrapper.c:96-103):
    distributed herbt on the cyclic slabs, a BAND-ONLY extraction off
    the slabs (O(N*mb) moved, not the r4 full to_tile — ADVICE r4
    item 3), and the pipelined-SBR chase finishes per-rank, the way
    the reference ships its tridiagonal to rank-0 LAPACK. Requires
    N % mb == 0 (herbt's contract, see PARITY.md). Returns ascending
    eigenvalues (N,)."""
    import jax.scipy.linalg as jsl

    from dplasma_tpu.descriptors import TileMatrix
    from dplasma_tpu.ops import eig as eig_mod

    B = herbt_cyclic(A)
    band = _band_extract_cyclic_jit(B.data, B.desc, _mesh_of(B))
    # rebuild the (local, dense) band matrix the SBR chase consumes:
    # B[i, i-d] = band[i, d] and its Hermitian mirror
    N, mb = B.desc.M, B.desc.mb
    i = jnp.arange(N)
    dense = jnp.zeros((N, N), band.dtype)
    for off in range(mb + 1):
        v = band[off:, off]
        dense = dense.at[i[off:], i[off:] - off].set(v)
        if off:
            dense = dense.at[i[off:] - off, i[off:]].set(
                v.conj() if jnp.iscomplexobj(band) else v)
    Bt = TileMatrix.from_dense(dense, mb, mb)
    d_, e_ = eig_mod.hbrdt(Bt, mb)
    if d_.shape[0] == 1:
        return d_
    return jsl.eigh_tridiagonal(d_, e_, eigvals_only=True)


@partial(jax.jit, static_argnums=(1, 2))
def _ge2gb_cyclic_jit(data, desc: CyclicDesc, mesh):
    """Distributed general dense -> upper band-bidiagonal reduction
    over cyclic slabs (the dplasma_zgebrd_ge2gb stage 1, ref
    src/zgebrd_ge2gb.jdf:1-1191; composed into the SVD chain by
    zgesvd_wrapper.c). Panel k alternates:

      * a QR half on column block k (rows >= k) — the geqrf_cyclic
        step: distributed CholeskyQR2 + TSQR-HR along 'p', trailing
        A <- Q^H A via psum_p(V^H A);
      * an LQ half on row block k (columns >= k+1) — the SAME panel
        algebra run along 'q' on the conjugate-transposed row strip,
        trailing A <- A Q2^H via psum_q(A conj(V)).

    Leaves R_k on diagonal tiles and L_k^H = ct(Rtilde) on the first
    superdiagonal tiles: an upper block-bidiagonal band of bandwidth
    mb whose singular values equal A's. V/T are discarded (values-only
    jobz=N, as the reference CI drives it)."""
    from dplasma_tpu.kernels import blas as kb

    d = desc.dist
    P, Q = d.P, d.Q
    mb = desc.mb
    assert desc.mb == desc.nb and desc.M == desc.N
    KT = desc.MT
    mloc = desc.MTL * mb
    nloc = desc.NTL * mb
    cplx = jnp.iscomplexobj(data)

    def ct(x):
        return x.conj().T if cplx else x.T

    def cj(x):
        return x.conj() if cplx else x

    eps = float(jnp.finfo(
        jnp.zeros((), data.dtype).real.dtype).eps)

    def body(local):
        A = local.reshape(mloc, nloc)
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        grow, gcol, gid, gcid = _slab_coords(desc, p, q)
        A = _seed_pad_diag(A, desc, gid, gcid)
        for k in range(KT):
            pk = layout.owner(k, P, d.kp, d.ip)
            qk = layout.owner(k, Q, d.kq, d.jq)
            lrk = layout.local_index(k, P, d.kp)
            lck = layout.local_index(k, Q, d.kq)
            e = k * mb
            # ---- QR half: column block k, rows >= k ----
            cs = jax.lax.dynamic_slice_in_dim(A, lck * mb, mb, axis=1)
            pan = jax.lax.psum(
                jnp.where(q == qk, cs, jnp.zeros_like(cs)),
                pmesh.COL_AXIS)
            act = (gid >= e)[:, None]
            x = jnp.where(act, pan, 0)
            packedtop, V1, T, Ub, q2 = _cqr2_panel(
                x, desc.M, mb, eps, pk, lrk, p, ct)
            below = (gid >= e + mb)[:, None]
            V2 = kb.trsm(Ub, q2, side="R", lower=False)
            v1slab = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros_like(q2), V1, lrk * mb, axis=0)
            diagrow = ((grow == k) & (p == pk))[:, None]
            Vloc = jnp.where(below, V2, jnp.where(diagrow, v1slab, 0))
            # trailing cols > k: A <- A - V (T^H (V^H A))
            S = jax.lax.psum(kb.dot(Vloc, A, ta=True, conj_a=True),
                             pmesh.ROW_AXIS)
            upd = kb.dot(Vloc, kb.dot(T, S, ta=True, conj_a=True))
            trail = (gcid >= e + mb)[None, :]
            A = A - jnp.where(trail, upd, 0)
            # write column k: R on the diagonal tile, zeros below
            Rw = jnp.triu(packedtop)
            at_k = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros_like(cs), Rw, lrk * mb, axis=0)
            newcs = jnp.where(act, jnp.where(diagrow, at_k, 0), cs)
            A = jnp.where(q == qk,
                          jax.lax.dynamic_update_slice_in_dim(
                              A, newcs, lck * mb, axis=1), A)
            if k == KT - 1:
                break
            # ---- LQ half: row block k, columns >= k+1 ----
            qk1 = layout.owner(k + 1, Q, d.kq, d.jq)
            lck1 = layout.local_index(k + 1, Q, d.kq)
            rs = jax.lax.dynamic_slice_in_dim(A, lrk * mb, mb, axis=0)
            strip = jax.lax.psum(
                jnp.where(p == pk, rs, jnp.zeros_like(rs)),
                pmesh.ROW_AXIS)
            actq = (gcid >= e + mb)[:, None]
            xq = jnp.where(actq, ct(strip), 0)
            packedq, V1q, Tq, Ubq, q2q = _cqr2_panel(
                xq, desc.N, mb, eps, qk1, lck1, q, ct,
                axis=pmesh.COL_AXIS)
            beyond = (gcid >= e + 2 * mb)[:, None]
            V2q = kb.trsm(Ubq, q2q, side="R", lower=False)
            v1slabq = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros_like(q2q), V1q, lck1 * mb, axis=0)
            diagcol = ((gcol == k + 1) & (q == qk1))[:, None]
            Vq = jnp.where(beyond, V2q,
                           jnp.where(diagcol, v1slabq, 0))
            # trailing rows > k: A <- A - (A conj(Vq)) conj(Tq) Vq^T
            Y = jax.lax.psum(kb.dot(A, cj(Vq)), pmesh.COL_AXIS)
            updr = kb.dot(kb.dot(Y, cj(Tq)), Vq.T)
            rtrail = (gid >= e + mb)[:, None]
            A = A - jnp.where(rtrail, updr, 0)
            # write row k: ct(Rtilde) on the superdiagonal tile,
            # zeros to its right
            Lw = ct(jnp.triu(packedq))
            at_c1 = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros_like(rs), Lw, lck1 * mb, axis=1)
            # only the owner rank-column of tile k+1 holds Lw; on any
            # other rank local slot lck1 is a DIFFERENT global block
            at_c1 = jnp.where(q == qk1, at_c1, jnp.zeros_like(at_c1))
            rows = jax.lax.dynamic_slice_in_dim(A, lrk * mb, mb,
                                                axis=0)
            keepleft = (gcid < e + mb)[None, :]
            newrow = jnp.where(keepleft, rows, at_c1)
            A = jnp.where(p == pk,
                          jax.lax.dynamic_update_slice_in_dim(
                              A, newrow, lrk * mb, axis=0), A)
        return A.reshape(1, 1, mloc, nloc)

    f = shard_map(
        body, mesh=mesh,
        in_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                               None),
        out_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None))
    return f(data)


def gebrd_ge2gb_cyclic(A: CyclicMatrix) -> CyclicMatrix:
    """Distributed dense -> band-bidiagonal reduction (SVD stage 1) on
    block-cyclic local storage (ref src/zgebrd_ge2gb.jdf). Square with
    N % mb == 0 (the LQ panels need full real blocks, as herbt)."""
    m = _mesh_of(A)
    assert A.desc.mb == A.desc.nb and A.desc.M == A.desc.N
    assert A.desc.M % A.desc.mb == 0, "ge2gb_cyclic: need N % mb == 0"
    return CyclicMatrix(_ge2gb_cyclic_jit(A.data, A.desc, m), A.desc)


def gesvd_cyclic(A: CyclicMatrix):
    """Distributed singular values (the dplasma_zgesvd composition,
    ref src/zgesvd_wrapper.c): ge2gb on the cyclic slabs, then the
    band finishes per-rank through the existing band-bidiagonal
    stage 2 (ops.eig), the way the reference ships its bidiagonal to
    rank-0 LAPACK. Returns descending singular values (N,)."""
    from dplasma_tpu.ops import eig as eig_mod

    Bt = gebrd_ge2gb_cyclic(A).to_tile()
    return eig_mod.gesvd(Bt)


def qr_t_factor(Ts, A: TileMatrix) -> TileMatrix:
    """Convert a geqrf_cyclic T-factor stack (KT, mb, mb) into the
    ops.qr T TileMatrix (unmqr/ormqr-ready), padded to the T
    descriptor of ``A``."""
    from dplasma_tpu.ops import qr as qr_mod
    Td = jnp.concatenate([Ts[i] for i in range(Ts.shape[0])], axis=1)
    Tm = qr_mod.t_desc(A)
    if Td.shape[1] < Tm.desc.Np:
        Td = jnp.pad(Td, ((0, 0), (0, Tm.desc.Np - Td.shape[1])))
    return TileMatrix(Td, Tm.desc)


def geqrf_cyclic(A: CyclicMatrix):
    """Distributed blocked QR on block-cyclic local storage (the
    pdgeqrf / zgeqrf_param shape). Returns (factor CyclicMatrix in the
    ops.qr packed layout, Ts (KT, mb, mb) T-factor stack —
    :func:`qr_t_factor` converts it to the ops.qr T TileMatrix)."""
    m = pmesh.active()
    assert m is not None, "geqrf_cyclic needs an active mesh (use_grid)"
    ms = (m.shape[pmesh.ROW_AXIS], m.shape[pmesh.COL_AXIS])
    assert ms == (A.desc.dist.P, A.desc.dist.Q), (
        f"mesh {ms} != dist grid {(A.desc.dist.P, A.desc.dist.Q)}")
    ring = _cyclic_ring(A.desc, A.dtype, m)
    rch = _ring_chunks(ring)
    _ring_span(A, m, ring, rch)
    out, Ts = _geqrf_cyclic_jit(A.data, A.desc, m,
                                _cyclic_lookahead(), ring, rch)
    return CyclicMatrix(out, A.desc), Ts[0, 0]


def _cyclic_ring(desc: CyclicDesc, dtype, mesh,
                 need_row: bool = False) -> bool:
    """Resolve MCA ``ring.enable`` for one cyclic factorization: the
    panel-broadcast ring rides the 'q' axis, the LU winner-row
    exchange (``need_row``) the 'p' axis. The kernels take ONE ring
    flag and fall back per size-1 axis internally, so the resolution
    is: every RINGABLE axis (size > 1) the kernel would use must pass
    its gate — a Px1 LU grid rings the row exchange alone, and a
    geometry failure on either live axis keeps the whole kernel on
    the psum path (conservative: the single flag cannot express a
    per-axis mix beyond the size-1 fallback). ``off`` keeps the
    masked-psum kernels bit-identical; ``auto`` activates only where
    the runtime probe and mesh-geometry gate pass (CPU always falls
    back — see kernels.pallas_ring)."""
    from dplasma_tpu.kernels import pallas_ring as _pring
    d = desc.dist
    gates = []
    if d.Q > 1:
        gates.append(_pring.ring_active(d.Q, dtype, mesh,
                                        pmesh.COL_AXIS))
    if need_row and d.P > 1:
        gates.append(_pring.ring_active(d.P, dtype, mesh,
                                        pmesh.ROW_AXIS))
    return bool(gates) and all(gates)


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _panel_bcast_probe_jit(data, desc: CyclicDesc, mesh,
                           ring: bool = False, rchunks: int = 0):
    """The factorizations' panel-broadcast schedule ALONE — KT
    owner-column transfers along 'q' (ring or masked psum) with a
    trivial reduction to keep the dataflow live. This is the comm
    microprogram the ``ring`` phase span times: its measured seconds
    are (nearly) pure ICI transfer, which the roofline joins against
    the ``ici`` bound priced from :func:`spmd_comm_model`'s
    panel-broadcast bytes (the satellite closing the never-validated
    ``ici`` roofline component)."""
    d = desc.dist
    P, Q = d.P, d.Q
    mb = desc.mb
    KT = min(desc.MT, desc.NT)
    mloc = desc.MTL * mb

    def body(local):
        A = local.reshape(mloc, desc.NTL * desc.nb)
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        s = jnp.zeros((mloc, mb), A.dtype)
        for k in range(KT):
            qk = layout.owner(k, Q, d.kq, d.jq)
            lck = layout.local_index(k, Q, d.kq)
            cs = jax.lax.dynamic_slice_in_dim(A, lck * mb, mb, axis=1)
            s = s + _bcast_q(cs, q, qk, Q, ring, P, rchunks)
        return s[None, None]

    f = shard_map(
        body, mesh=mesh,
        in_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                               None),
        out_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None),
        **({"check_vma": False} if ring else {}))
    return f(data)


def _ring_chunks(ring: bool) -> int:
    """Resolve MCA ``ring.chunks`` ONCE at the wrapper (pinned into
    the jit key as a static, so a knob flip re-traces instead of
    replaying a stale cached kernel); 0 on the psum path."""
    from dplasma_tpu.utils import config as _cfg
    return _cfg.mca_get_int("ring.chunks", 4) if ring else 0


def _ring_span(A: CyclicMatrix, mesh, ring: bool,
               rchunks: int = 0) -> None:
    """Emit the ``ring`` phase span (active ledger only — the default
    path never runs the probe, keeping the timed loop untouched): one
    fenced pass of the panel-broadcast microprogram, so the ledger's
    measured ICI seconds can be validated against the roofline
    ``ici`` bound."""
    from dplasma_tpu.observability import phases as _phases
    if _phases.active() is None:
        return
    with _phases.span("ring") as fence:
        fence(_panel_bcast_probe_jit(A.data, A.desc, mesh, ring,
                                     rchunks))


def _cyclic_lookahead() -> int:
    """Pipeline depth for the cyclic factorization kernels: MCA
    ``sweep.lookahead`` > 0 enables the one-column pan_next carry
    (the shard_map bodies pipeline exactly one panel ahead — deeper
    windows would carry multiple pre-updated columns for no extra
    overlap on a single in-order core per rank)."""
    from dplasma_tpu.ops._sweep import sweep_params
    la, _ = sweep_params()
    return 1 if la > 0 else 0


def _mesh_of(A: CyclicMatrix):
    m = pmesh.active()
    assert m is not None, "cyclic ops need an active mesh (use_grid)"
    ms = (m.shape[pmesh.ROW_AXIS], m.shape[pmesh.COL_AXIS])
    assert ms == (A.desc.dist.P, A.desc.dist.Q), (
        f"mesh {ms} != dist grid {(A.desc.dist.P, A.desc.dist.Q)}")
    return m


@partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _trsm_cyclic_jit(adata, bdata, desc, bdesc, mesh, uplo, trans,
                     unit):
    """Distributed left triangular solve over cyclic local slabs (the
    role of the reference's ztrsm_LL* JDFs on
    parsec_matrix_block_cyclic, ref src/ztrsm_LLN.jdf:1-60): op(T) X =
    B for T the named stored triangle, all trans (N/T/C) on either
    uplo. The per-step collectives are the POTRF set —
    masked-psum panel broadcast along 'q', diagonal tile along 'p',
    and for trans=C a partial-sum psum along 'p' — so a solve after
    :func:`potrf_cyclic`/:func:`getrf_cyclic` never leaves the slabs
    (VERDICT r3 missing #1)."""
    from dplasma_tpu.kernels import blas as kb

    lower = uplo == "L"
    d = desc.dist
    P, Q = d.P, d.Q
    mb = desc.mb
    KT = min(desc.MT, desc.NT)
    mloc = desc.MTL * mb
    nlocB = bdesc.NTL * bdesc.nb
    cplx = jnp.iscomplexobj(adata)

    def ct(x):
        return x.conj().T if cplx else x.T

    # op(T) is effectively lower-triangular (forward substitution) for
    # (lower, N) and (upper, C/T); backward otherwise — the masked
    # partial-sum structure below is uplo-general (``off`` keeps only
    # the already-solved rows' couplings)
    forward = lower == (trans == "N")

    def body(aloc, bloc):
        A = aloc.reshape(mloc, desc.NTL * mb)
        B = bloc.reshape(mloc, nlocB)
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        grow = _grow(desc.MTL, mb, p, P, d.kp, d.ip)
        steps = range(KT) if forward else range(KT - 1, -1, -1)
        for k in steps:
            pk = layout.owner(k, P, d.kp, d.ip)
            qk = layout.owner(k, Q, d.kq, d.jq)
            lrk = layout.local_index(k, P, d.kp)
            lck = layout.local_index(k, Q, d.kq)
            # T's block column k -> everyone in the row (panel bcast)
            cs = jax.lax.dynamic_slice_in_dim(A, lck * mb, mb, axis=1)
            pan = jax.lax.psum(
                jnp.where(q == qk, cs, jnp.zeros_like(cs)),
                pmesh.COL_AXIS)
            dt = jax.lax.dynamic_slice_in_dim(pan, lrk * mb, mb, axis=0)
            Tkk = jax.lax.psum(
                jnp.where(p == pk, dt, jnp.zeros_like(dt)),
                pmesh.ROW_AXIS)
            if not lower:
                Tkk = jnp.triu(Tkk)
            # off-diagonal rows of the panel that couple with X_k
            off = (grow > k) if lower else (grow < k)
            Tb = jnp.where(off[:, None], pan, 0)
            bk = jax.lax.dynamic_slice_in_dim(B, lrk * mb, mb, axis=0)
            if trans == "N":
                rhs = bk
            else:
                # X_k = op(T)_kk^{-1} (B_k - sum_i op(T)_ik X_i): the
                # partial sums ride one masked psum along 'p'; the
                # coupling blocks must match the solve's op — plain
                # transpose for trans=T, conjugate for C (review r5)
                Tbt = Tb.T if trans == "T" else ct(Tb)
                s = jax.lax.psum(kb.dot(Tbt, B), pmesh.ROW_AXIS)
                rhs = bk - s
            xk = kb.trsm(Tkk, jnp.where(p == pk, rhs, 0), side="L",
                         lower=lower, trans=trans, unit=unit)
            xk = jax.lax.psum(xk, pmesh.ROW_AXIS)
            B = jnp.where((grow == k)[:, None] & (p == pk),
                          jax.lax.dynamic_update_slice_in_dim(
                              B, xk, lrk * mb, axis=0), B)
            if trans == "N":
                # B_off -= T_ik X_k (local MXU matmul per rank)
                B = B - kb.dot(Tb, xk)
        return B.reshape(1, 1, mloc, nlocB)

    f = shard_map(
        body, mesh=mesh,
        in_specs=(PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None),) * 2,
        out_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None))
    return f(adata, bdata)


def trsm_cyclic(A: CyclicMatrix, B: CyclicMatrix, trans: str = "N",
                unit: bool = False, uplo: str = "L") -> CyclicMatrix:
    """Distributed op(T) X = B on block-cyclic local storage (left
    side; every (uplo, trans) corner — the POTRS/GETRS building
    block, ref src/ztrsm_LLN.jdf). A and B share the grid; B keeps
    its own column blocking."""
    m = _mesh_of(A)
    assert (A.desc.dist == B.desc.dist and A.desc.mb == B.desc.mb
            and A.desc.M == B.desc.M), "trsm_cyclic: mismatched descs"
    out = _trsm_cyclic_jit(A.data, B.data, A.desc, B.desc, m,
                           uplo.upper(), trans.upper(), unit)
    return CyclicMatrix(out, B.desc)


def potrs_cyclic(L: CyclicMatrix, B: CyclicMatrix,
                 uplo: str = "L") -> CyclicMatrix:
    """Solve A X = B from the distributed Cholesky factor without
    leaving the slabs (the pdpotrs / zpotrs_wrapper.c composition of
    two distributed TRSMs). ``uplo`` names the factor's storage:
    A = L L^H (L) or A = U^H U (U)."""
    assert uplo.upper() in ("L", "U"), uplo
    if uplo.upper() == "U":
        return trsm_cyclic(L, trsm_cyclic(L, B, "C", uplo="U"), "N",
                           uplo="U")
    return trsm_cyclic(L, trsm_cyclic(L, B, "N"), "C")


@partial(jax.jit, static_argnums=(2, 3, 4))
def _gemm_cyclic_jit(adata, bdata, adesc, bdesc, mesh):
    """Distributed C = A @ B over cyclic slabs: the SUMMA loop on
    block-cyclic storage (ref src/zsumma_NN.jdf) — per k-step one
    masked-psum broadcast of A's block column along 'q', one of B's
    block row along 'p', one local MXU matmul."""
    from dplasma_tpu.kernels import blas as kb

    d = adesc.dist
    P, Q = d.P, d.Q
    mb, nb = adesc.mb, adesc.nb
    KT = adesc.NT                       # contraction tiles
    mloc = adesc.MTL * mb
    nlocB = bdesc.NTL * bdesc.nb

    def body(aloc, bloc):
        A = aloc.reshape(mloc, adesc.NTL * nb)
        B = bloc.reshape(bdesc.MTL * bdesc.mb, nlocB)
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        C = jnp.zeros((mloc, nlocB), A.dtype)
        for k in range(KT):
            qk = layout.owner(k, Q, d.kq, d.jq)
            pk = layout.owner(k, P, d.kp, d.ip)
            lck = layout.local_index(k, Q, d.kq)
            lrk = layout.local_index(k, P, d.kp)
            acol = jax.lax.dynamic_slice_in_dim(A, lck * nb, nb, axis=1)
            acol = jax.lax.psum(
                jnp.where(q == qk, acol, jnp.zeros_like(acol)),
                pmesh.COL_AXIS)
            brow = jax.lax.dynamic_slice_in_dim(
                B, lrk * bdesc.mb, bdesc.mb, axis=0)
            brow = jax.lax.psum(
                jnp.where(p == pk, brow, jnp.zeros_like(brow)),
                pmesh.ROW_AXIS)
            C = C + kb.dot(acol, brow)
        return C.reshape(1, 1, mloc, nlocB)

    f = shard_map(
        body, mesh=mesh,
        in_specs=(PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None),) * 2,
        out_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None))
    return f(adata, bdata)


def gemm_cyclic(A: CyclicMatrix, B: CyclicMatrix) -> CyclicMatrix:
    """Distributed C = A @ B on block-cyclic local storage (the SUMMA
    shape over slabs). A's column tiling must match B's row tiling."""
    m = _mesh_of(A)
    assert (A.desc.dist == B.desc.dist and A.desc.nb == B.desc.mb
            and A.desc.N == B.desc.M), "gemm_cyclic: mismatched descs"
    out = _gemm_cyclic_jit(A.data, B.data, A.desc, B.desc, m)
    return CyclicMatrix(out, CyclicDesc(A.desc.M, B.desc.N, A.desc.mb,
                                        B.desc.nb, A.desc.dist))


@partial(jax.jit, static_argnums=(1, 2, 3))
def _herk_cyclic_jit(adata, desc, cdesc, mesh):
    """Distributed C = A A^H (lower triangle, C M x M) over cyclic
    slabs — the POTRF trailing-update collectives (panel bcast along
    'q', all_gather row formation along 'p') as a standalone rank-k
    sweep (ref src/zherk_LN.jdf). ``A`` may be rectangular: C's
    columns follow the M x M descriptor ``cdesc``, not A's column
    tiling (review r4)."""
    from dplasma_tpu.kernels import blas as kb

    d = desc.dist
    P, Q = d.P, d.Q
    mb = desc.mb
    mloc = desc.MTL * mb
    nloc = desc.NTL * desc.nb
    ncloc = cdesc.NTL * cdesc.nb
    cplx = jnp.iscomplexobj(adata)

    def ct(x):
        return x.conj().T if cplx else x.T

    def body(aloc):
        A = aloc.reshape(mloc, nloc)
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        grow, _, gid, _ = _slab_coords(desc, p, q)
        # C's column coordinates ride the M x M descriptor
        gcol_c = _grow(cdesc.NTL, cdesc.nb, q, Q, d.kq, d.jq)
        gcid_c = (gcol_c * cdesc.nb
                  + jnp.arange(ncloc) % cdesc.nb)
        C = jnp.zeros((mloc, ncloc), A.dtype)
        for k in range(desc.NT):
            qk = layout.owner(k, Q, d.kq, d.jq)
            lck = layout.local_index(k, Q, d.kq)
            acol = jax.lax.dynamic_slice_in_dim(
                A, lck * desc.nb, desc.nb, axis=1)
            acol = jax.lax.psum(
                jnp.where(q == qk, acol, jnp.zeros_like(acol)),
                pmesh.COL_AXIS)
            # row formation: A(j, k)^H for my local C columns j — the
            # all_gather + cyclic pick of the POTRF trailing update
            allg = jax.lax.all_gather(acol, pmesh.ROW_AXIS)
            allg = allg.reshape(P * mloc, desc.nb)
            jt = gcol_c
            pj = (jt // d.kp + d.ip) % P
            lj = (jt // (d.kp * P)) * d.kp + jt % d.kp
            idx = pj * mloc + lj * mb + jnp.arange(ncloc) % cdesc.nb
            valid = (jt < desc.MT)[:, None]
            W = jnp.where(valid, allg[jnp.clip(idx, 0, P * mloc - 1)],
                          0)                           # (ncloc, nb)
            C = C + kb.dot(acol, ct(W))
        lower = (gid[:, None] >= gcid_c[None, :])
        return jnp.where(lower, C, 0).reshape(1, 1, mloc, ncloc)

    f = shard_map(
        body, mesh=mesh,
        in_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                               None),
        out_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None))
    return f(adata)


def herk_cyclic(A: CyclicMatrix) -> CyclicMatrix:
    """Distributed C = A A^H (lower stored, M x M) on block-cyclic
    local storage. Square tiles; A may be rectangular."""
    m = _mesh_of(A)
    assert A.desc.mb == A.desc.nb, "herk_cyclic needs square tiles"
    cdesc = CyclicDesc(A.desc.M, A.desc.M, A.desc.mb, A.desc.mb,
                       A.desc.dist)
    out = _herk_cyclic_jit(A.data, A.desc, cdesc, m)
    return CyclicMatrix(out, cdesc)


def _row_pick(desc, grow_like, nloc_src: int):
    """Index table mapping my local ROW ids (global column coordinate
    ``grow_like`` per element) into a 'q'-axis all_gather of a row
    slab reshaped (mb, Q*nloc_src): entry for element with global id g
    is q_owner(g)*nloc_src + local_col(g). The column-coordinate twin
    of the herk/potrf row-formation pick."""
    d = desc.dist
    gid = grow_like
    t = gid // desc.nb
    qj = (t // d.kq + d.jq) % d.Q
    lj = (t // (d.kq * d.Q)) * d.kq + t % d.kq
    idx = qj * nloc_src + lj * desc.nb + gid % desc.nb
    return jnp.clip(idx, 0, d.Q * nloc_src - 1), (t < desc.NT)


@partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _trmm_cyclic_jit(adata, bdata, desc, bdesc, mesh, opts):
    """Distributed left triangular MULTIPLY over cyclic slabs — B <-
    op(T) B (the role of ref src/ztrmm_LLN.jdf on
    parsec_matrix_block_cyclic). trans=N is the SUMMA loop with the T
    column element-masked to its triangle; trans=C forms the lhs
    conj(T(k, r)) by the 'q'-axis gather + column-coordinate pick."""
    from dplasma_tpu.kernels import blas as kb

    uplo, trans, unit = opts
    lower = uplo == "L"
    d = desc.dist
    P, Q = d.P, d.Q
    mb = desc.mb
    KT = min(desc.MT, desc.NT)
    mloc = desc.MTL * mb
    nloc = desc.NTL * mb
    nlocB = bdesc.NTL * bdesc.nb
    cplx = jnp.iscomplexobj(adata)

    def cj(x):
        return x.conj() if cplx else x

    def body(aloc, bloc):
        A = aloc.reshape(mloc, nloc)
        B = bloc.reshape(mloc, nlocB)
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        grow, gcol, gid, gcid = _slab_coords(desc, p, q)
        C = jnp.zeros((mloc, nlocB), A.dtype)
        for k in range(KT):
            pk = layout.owner(k, P, d.kp, d.ip)
            qk = layout.owner(k, Q, d.kq, d.jq)
            lrk = layout.local_index(k, P, d.kp)
            lck = layout.local_index(k, Q, d.kq)
            ke = k * mb + jnp.arange(mb)              # block-k elem ids
            # B block row k -> everyone in the column ('p' bcast)
            br = jax.lax.dynamic_slice_in_dim(B, lrk * mb, mb, axis=0)
            brow = jax.lax.psum(
                jnp.where(p == pk, br, jnp.zeros_like(br)),
                pmesh.ROW_AXIS)
            if trans == "N":
                # T's block column k ('q' bcast), element-masked
                cs = jax.lax.dynamic_slice_in_dim(A, lck * mb, mb,
                                                  axis=1)
                acol = jax.lax.psum(
                    jnp.where(q == qk, cs, jnp.zeros_like(cs)),
                    pmesh.COL_AXIS)
                if lower:
                    keep = gid[:, None] > ke[None, :]
                else:
                    keep = gid[:, None] < ke[None, :]
                dg = (gid[:, None] == ke[None, :])
                one = jnp.ones((), A.dtype)
                acol = jnp.where(keep, acol,
                                 jnp.where(dg, one if unit else acol,
                                           0))
                C = C + kb.dot(acol, brow)
            else:
                # lhs = conj(T(k, gid_r)): T row slab k ('p' bcast),
                # gathered along 'q', column-coordinate pick
                rs = jax.lax.dynamic_slice_in_dim(A, lrk * mb, mb,
                                                  axis=0)
                rowk = jax.lax.psum(
                    jnp.where(p == pk, rs, jnp.zeros_like(rs)),
                    pmesh.ROW_AXIS)
                allr = jax.lax.all_gather(rowk, pmesh.COL_AXIS)
                flat = allr.transpose(1, 0, 2).reshape(mb, Q * nloc)
                idx, valid = _row_pick(desc, gid, nloc)
                Wl = jnp.where(valid[:, None], cj(flat[:, idx].T), 0)
                # Wl[r, t] = conj(T(ke_t, gid_r)): lower T has
                # T(ke, r) nonzero for ke >= r, upper for ke <= r
                if lower:
                    keep = gid[:, None] < ke[None, :]
                else:
                    keep = gid[:, None] > ke[None, :]
                dg = (gid[:, None] == ke[None, :])
                one = jnp.ones((), A.dtype)
                Wl = jnp.where(keep, Wl,
                               jnp.where(dg, one if unit else Wl, 0))
                C = C + kb.dot(Wl, brow)
        return C.reshape(1, 1, mloc, nlocB)

    f = shard_map(
        body, mesh=mesh,
        in_specs=(PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None),) * 2,
        out_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None))
    return f(adata, bdata)


def trmm_cyclic(A: CyclicMatrix, B: CyclicMatrix, trans: str = "N",
                unit: bool = False, uplo: str = "L") -> CyclicMatrix:
    """Distributed B <- op(T) B on block-cyclic local storage (left
    side; ref src/ztrmm_LLN.jdf family). A and B share the grid and
    row tiling."""
    m = _mesh_of(A)
    assert (A.desc.dist == B.desc.dist and A.desc.mb == B.desc.mb
            and A.desc.M == B.desc.M), "trmm_cyclic: mismatched descs"
    assert A.desc.mb == A.desc.nb, "trmm_cyclic needs square tiles"
    t = trans.upper()
    # 'T' aliases 'C' only for real data: the non-N branch conjugates
    assert t in ("N", "C") or not jnp.iscomplexobj(A.data), \
        "trmm_cyclic: complex plain-transpose not implemented"
    out = _trmm_cyclic_jit(A.data, B.data, A.desc, B.desc, m,
                           (uplo.upper(), t, unit))
    return CyclicMatrix(out, B.desc)


@partial(jax.jit, static_argnums=(2, 3, 4))
def _hemm_cyclic_jit(adata, bdata, desc, bdesc, mesh):
    """Distributed C = A B with A Hermitian stored LOWER, over cyclic
    slabs (the zhemm/zsymm left-side role, ref src/zhemm.jdf): per
    k-step the stored column block serves rows >= k directly and rows
    < k through its conjugate-transposed row strip (the 'q'-gather +
    column-coordinate pick)."""
    from dplasma_tpu.kernels import blas as kb

    d = desc.dist
    P, Q = d.P, d.Q
    mb = desc.mb
    KT = desc.MT
    mloc = desc.MTL * mb
    nloc = desc.NTL * mb
    nlocB = bdesc.NTL * bdesc.nb
    cplx = jnp.iscomplexobj(adata)

    def cj(x):
        return x.conj() if cplx else x

    def body(aloc, bloc):
        A = aloc.reshape(mloc, nloc)
        B = bloc.reshape(mloc, nlocB)
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        grow, gcol, gid, gcid = _slab_coords(desc, p, q)
        C = jnp.zeros((mloc, nlocB), A.dtype)
        for k in range(KT):
            pk = layout.owner(k, P, d.kp, d.ip)
            qk = layout.owner(k, Q, d.kq, d.jq)
            lrk = layout.local_index(k, P, d.kp)
            lck = layout.local_index(k, Q, d.kq)
            ke = k * mb + jnp.arange(mb)
            br = jax.lax.dynamic_slice_in_dim(B, lrk * mb, mb, axis=0)
            brow = jax.lax.psum(
                jnp.where(p == pk, br, jnp.zeros_like(br)),
                pmesh.ROW_AXIS)
            # stored lower column block k: rows >= k (incl. diagonal)
            cs = jax.lax.dynamic_slice_in_dim(A, lck * mb, mb, axis=1)
            acol = jax.lax.psum(
                jnp.where(q == qk, cs, jnp.zeros_like(cs)),
                pmesh.COL_AXIS)
            acol = jnp.where(gid[:, None] >= ke[None, :], acol, 0)
            # rows < k: A(r, ke) = conj(A_stored(ke, r)) — row slab k
            # gathered along 'q', picked at my rows' global columns
            rs = jax.lax.dynamic_slice_in_dim(A, lrk * mb, mb, axis=0)
            rowk = jax.lax.psum(
                jnp.where(p == pk, rs, jnp.zeros_like(rs)),
                pmesh.ROW_AXIS)
            allr = jax.lax.all_gather(rowk, pmesh.COL_AXIS)
            flat = allr.transpose(1, 0, 2).reshape(mb, Q * nloc)
            idx, valid = _row_pick(desc, gid, nloc)
            Wl = jnp.where(valid[:, None], cj(flat[:, idx].T), 0)
            Wl = jnp.where(gid[:, None] < ke[None, :], Wl, 0)
            C = C + kb.dot(acol + Wl, brow)
        return C.reshape(1, 1, mloc, nlocB)

    f = shard_map(
        body, mesh=mesh,
        in_specs=(PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None),) * 2,
        out_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None))
    return f(adata, bdata)


def hemm_cyclic(A: CyclicMatrix, B: CyclicMatrix) -> CyclicMatrix:
    """Distributed C = A B with A Hermitian stored lower (left side;
    ref src/zhemm.jdf on parsec_matrix_block_cyclic)."""
    m = _mesh_of(A)
    assert (A.desc.dist == B.desc.dist and A.desc.mb == B.desc.mb
            and A.desc.M == B.desc.M and A.desc.M == A.desc.N), \
        "hemm_cyclic: mismatched descs"
    assert A.desc.mb == A.desc.nb, "hemm_cyclic needs square tiles"
    out = _hemm_cyclic_jit(A.data, B.data, A.desc, B.desc, m)
    return CyclicMatrix(out, B.desc)


@partial(jax.jit, static_argnums=(2, 3, 4))
def _her2k_cyclic_jit(adata, bdata, desc, cdesc, mesh):
    """Distributed C = A B^H + B A^H (lower stored) over cyclic slabs
    (ref src/zher2k_LN.jdf): the herk_cyclic collectives doubled —
    per column block one 'q'-bcast of each operand and one 'p'-gather
    row formation of each, two local MXU matmuls."""
    from dplasma_tpu.kernels import blas as kb

    d = desc.dist
    P, Q = d.P, d.Q
    mb = desc.mb
    mloc = desc.MTL * mb
    nloc = desc.NTL * desc.nb
    cplx = jnp.iscomplexobj(adata)

    def ct(x):
        return x.conj().T if cplx else x.T

    def body(aloc, bloc):
        A = aloc.reshape(mloc, nloc)
        Bm = bloc.reshape(mloc, nloc)
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        grow, _, gid, _ = _slab_coords(desc, p, q)
        ncloc = cdesc.NTL * cdesc.nb
        gcol_c = _grow(cdesc.NTL, cdesc.nb, q, Q, d.kq, d.jq)
        gcid_c = gcol_c * cdesc.nb + jnp.arange(ncloc) % cdesc.nb
        C = jnp.zeros((mloc, ncloc), A.dtype)
        jt = gcol_c
        pj = (jt // d.kp + d.ip) % P
        lj = (jt // (d.kp * P)) * d.kp + jt % d.kp
        idx = jnp.clip(pj * mloc + lj * mb
                       + jnp.arange(ncloc) % mb, 0, P * mloc - 1)
        valid = (jt < desc.MT)[:, None]
        for k in range(desc.NT):
            qk = layout.owner(k, Q, d.kq, d.jq)
            lck = layout.local_index(k, Q, d.kq)

            def colof(X):
                c = jax.lax.dynamic_slice_in_dim(
                    X, lck * desc.nb, desc.nb, axis=1)
                c = jax.lax.psum(
                    jnp.where(q == qk, c, jnp.zeros_like(c)),
                    pmesh.COL_AXIS)
                allg = jax.lax.all_gather(c, pmesh.ROW_AXIS)
                W = jnp.where(valid,
                              allg.reshape(P * mloc, desc.nb)[idx], 0)
                return c, W
            acol, Wa = colof(A)
            bcol, Wb = colof(Bm)
            C = C + kb.dot(acol, ct(Wb)) + kb.dot(bcol, ct(Wa))
        lower = (gid[:, None] >= gcid_c[None, :])
        return jnp.where(lower, C, 0).reshape(1, 1, mloc, ncloc)

    f = shard_map(
        body, mesh=mesh,
        in_specs=(PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None),) * 2,
        out_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None))
    return f(adata, bdata)


def her2k_cyclic(A: CyclicMatrix, B: CyclicMatrix) -> CyclicMatrix:
    """Distributed C = A B^H + B A^H (lower stored, M x M) on
    block-cyclic local storage (ref src/zher2k_LN.jdf). Square tiles;
    A and B share shape and grid."""
    m = _mesh_of(A)
    assert (A.desc.dist == B.desc.dist and A.desc.mb == B.desc.mb
            and A.desc.M == B.desc.M and A.desc.N == B.desc.N), \
        "her2k_cyclic: mismatched descs"
    assert A.desc.mb == A.desc.nb, "her2k_cyclic needs square tiles"
    cdesc = CyclicDesc(A.desc.M, A.desc.M, A.desc.mb, A.desc.mb,
                       A.desc.dist)
    out = _her2k_cyclic_jit(A.data, B.data, A.desc, cdesc, m)
    return CyclicMatrix(out, cdesc)


@partial(jax.jit, static_argnums=(1, 2))
def _lauum_cyclic_jit(adata, desc, mesh):
    """Distributed LAUUM (lower): C = L^H L restricted to the lower
    triangle, over cyclic slabs (ref src/zlauum_L.jdf) — a Gram sweep
    over row blocks: lhs conj(L(k, r)) via the 'q'-gather pick, rhs
    the broadcast row slab, one local MXU matmul per block row."""
    from dplasma_tpu.kernels import blas as kb

    d = desc.dist
    P, Q = d.P, d.Q
    mb = desc.mb
    KT = desc.MT
    mloc = desc.MTL * mb
    nloc = desc.NTL * mb
    cplx = jnp.iscomplexobj(adata)

    def cj(x):
        return x.conj() if cplx else x

    def body(aloc):
        A = aloc.reshape(mloc, nloc)
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        grow, gcol, gid, gcid = _slab_coords(desc, p, q)
        C = jnp.zeros((mloc, nloc), A.dtype)
        for k in range(KT):
            pk = layout.owner(k, P, d.kp, d.ip)
            lrk = layout.local_index(k, P, d.kp)
            ke = k * mb + jnp.arange(mb)
            rs = jax.lax.dynamic_slice_in_dim(A, lrk * mb, mb, axis=0)
            rowk = jax.lax.psum(
                jnp.where(p == pk, rs, jnp.zeros_like(rs)),
                pmesh.ROW_AXIS)
            # stored lower: row k holds columns <= k
            rowk = jnp.where(ke[:, None] >= gcid[None, :], rowk, 0)
            allr = jax.lax.all_gather(rowk, pmesh.COL_AXIS)
            flat = allr.transpose(1, 0, 2).reshape(mb, Q * nloc)
            idx, valid = _row_pick(desc, gid, nloc)
            Wl = jnp.where(valid[:, None], cj(flat[:, idx].T), 0)
            Wl = jnp.where(ke[None, :] >= gid[:, None], Wl, 0)
            C = C + kb.dot(Wl, rowk)
        lower = (gid[:, None] >= gcid[None, :])
        return jnp.where(lower, C, 0).reshape(1, 1, mloc, nloc)

    f = shard_map(
        body, mesh=mesh,
        in_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                               None),
        out_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None))
    return f(adata)


def lauum_cyclic(A: CyclicMatrix) -> CyclicMatrix:
    """Distributed L^H L (lower stored) on block-cyclic local storage
    (ref src/zlauum_L.jdf)."""
    m = _mesh_of(A)
    assert A.desc.mb == A.desc.nb and A.desc.M == A.desc.N
    return CyclicMatrix(_lauum_cyclic_jit(A.data, A.desc, m), A.desc)


@partial(jax.jit, static_argnums=(1, 2))
def _identity_cyclic_jit(data, desc, mesh):
    def body(loc):
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        _, _, gid, gcid = _slab_coords(desc, p, q)
        K = min(desc.M, desc.N)
        eye = ((gid[:, None] == gcid[None, :])
               & (gid < K)[:, None]).astype(loc.dtype)
        return eye[None, None]
    return shard_map(
        body, mesh=mesh,
        in_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                               None),
        out_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None))(data)


@partial(jax.jit, static_argnums=(1, 2, 3))
def _tri_mask_cyclic_jit(data, desc, mesh, lower):
    def body(loc):
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        _, _, gid, gcid = _slab_coords(desc, p, q)
        keep = (gid[:, None] >= gcid[None, :]) if lower else \
            (gid[:, None] <= gcid[None, :])
        return jnp.where(keep, loc[0, 0], 0)[None, None]
    return shard_map(
        body, mesh=mesh,
        in_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                               None),
        out_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None))(data)


def trtri_cyclic(A: CyclicMatrix, unit: bool = False,
                 uplo: str = "L") -> CyclicMatrix:
    """Distributed triangular inverse on block-cyclic local storage
    (ref src/ztrtri_L.jdf): the solve-shaped sweep op(T) X = I over
    the trsm_cyclic collectives (flops 3x the triangular-aware n^3/3
    — the rhs's own triangularity is not exploited; an honest trade
    for reusing the one battle-tested distributed solve)."""
    m = _mesh_of(A)
    eye = CyclicMatrix(_identity_cyclic_jit(A.data, A.desc, m),
                       A.desc)
    X = trsm_cyclic(A, eye, "N", unit=unit, uplo=uplo.upper())
    out = _tri_mask_cyclic_jit(X.data, X.desc, m,
                               uplo.upper() == "L")
    return CyclicMatrix(out, X.desc)


def potri_cyclic(L: CyclicMatrix) -> CyclicMatrix:
    """Distributed POTRI from the cyclic Cholesky factor: A^{-1} =
    L^{-H} L^{-1} = lauum(trtri(L)) without leaving the slabs (ref
    src/zpotri_wrapper.c composing ztrtri + zlauum)."""
    return lauum_cyclic(trtri_cyclic(L))


@partial(jax.jit, static_argnums=(3, 4))
def _getrs_cyclic_jit(data, perm, b, desc: CyclicDesc, mesh):
    """Pivoted LU solve on :func:`getrf_cyclic`'s slabs, as they lie.

    The factor rows stay at their original places; ``perm`` gives each
    local row its elimination position ``pos`` and step ``pos // mb``.
    In column block k, the rows of step k hold the packed L11\\U11, the
    rows of a later step hold L, those of an earlier step hold U. The
    right-hand sides arrive replicated in natural row order and the
    solution leaves replicated. Per step, forward then backward: the
    mb right-hand-side entries of block k by masked psum along 'p',
    the diagonal tile by masked psum over the grid (forward sweep
    only; the backward sweep reuses it), one tile solve, and on the
    ranks of column k the local product of the block column with the
    solved block, psummed along 'q' into the rows still to solve. No
    collective of the trace moves more than one mb x mb tile (the TPU
    compiler merges the KT diagonal gathers, which wait on nothing of
    the sweep, into one all-reduce)."""
    from dplasma_tpu.kernels import blas as kb

    d = desc.dist
    P, Q = d.P, d.Q
    mb = desc.mb
    assert desc.mb == desc.nb and desc.M == desc.N, \
        "getrs_cyclic needs a square factor with square tiles"
    KT = desc.MT
    Mp = KT * mb
    mloc = desc.MTL * mb
    nloc = desc.NTL * mb
    grid = (pmesh.ROW_AXIS, pmesh.COL_AXIS)

    def body(local, perm_, b_):
        A = local.reshape(mloc, nloc)
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        grow = _grow(desc.MTL, mb, p, P, d.kp, d.ip)
        gid = grow * mb + jnp.arange(mloc) % mb
        valid = gid < Mp
        gsafe = jnp.where(valid, gid, 0)
        inv = jnp.zeros((Mp,), jnp.int32).at[perm_].set(
            jnp.arange(Mp, dtype=jnp.int32))
        pos = inv[gsafe]
        # over-allocated slots (no global row) take step KT: never
        # gathered, never updated from a later block
        step = jnp.where(valid, pos // mb, KT)
        slot = pos % mb

        def gather(x, k, axes):
            # rows of step k, in elimination order, summed over ``axes``
            tgt = jnp.where(step == k, slot, mb)
            g = jnp.zeros((mb,) + x.shape[1:], x.dtype).at[tgt].set(
                x, mode="drop")
            return jax.lax.psum(g, axes)

        def block_col(k):
            qk = layout.owner(k, Q, d.kq, d.jq)
            lck = layout.local_index(k, Q, d.kq)
            return qk, jax.lax.dynamic_slice_in_dim(A, lck * mb, mb,
                                                    axis=1)

        def update(r, k, qk, cs, xk, rows):
            prod = jnp.where((q == qk) & rows[:, None], kb.dot(cs, xk), 0)
            return r - jax.lax.psum(prod, pmesh.COL_AXIS)

        r = jnp.where(valid[:, None], b_[gsafe], 0)
        ys, diag = [], []
        for k in range(KT):                  # L y = b[perm]
            qk, cs = block_col(k)
            dk = gather(jnp.where(q == qk, cs, 0), k, grid)
            yk = kb.trsm(dk, gather(r, k, pmesh.ROW_AXIS), side="L",
                         lower=True, unit=True)
            r = update(r, k, qk, cs, yk, step > k)
            ys.append(yk)
            diag.append(dk)
        y = jnp.concatenate(ys, axis=0)
        z = jnp.where(valid[:, None], y[jnp.where(valid, pos, 0)], 0)
        xs = [None] * KT
        for k in range(KT - 1, -1, -1):      # U x = y
            qk, cs = block_col(k)
            xk = kb.trsm(diag[k], gather(z, k, pmesh.ROW_AXIS),
                         side="L", lower=False)
            z = update(z, k, qk, cs, xk, step < k)
            xs[k] = xk
        return jnp.concatenate(xs, axis=0)

    f = shard_map(
        body, mesh=mesh,
        in_specs=(PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None),
                  PartitionSpec(), PartitionSpec()),
        out_specs=PartitionSpec(),
        # x is replicated by construction: every rank solves the same
        # blocks from psummed operands
        check_vma=False)
    return f(data, perm, b)


def getrs_cyclic(LU: CyclicMatrix, perm, B):
    """Solve A X = B from :func:`getrf_cyclic`'s output without leaving
    the slabs (pdgetrs): the factor rows stay where the factorization
    left them, ``perm`` names their elimination order
    (:func:`_getrs_cyclic_jit`). ``B`` is either a CyclicMatrix on the
    factor's grid and row tiling (the answer comes back as one) or a
    replicated natural-order (Mp, nrhs) array (the answer comes back
    replicated, in the same shape)."""
    m = _mesh_of(LU)
    if not isinstance(B, CyclicMatrix):
        return _getrs_cyclic_jit(LU.data, perm, B, LU.desc, m)
    assert (LU.desc.dist == B.desc.dist and LU.desc.mb == B.desc.mb
            and LU.desc.M == B.desc.M), "getrs_cyclic: mismatched descs"
    Bt = B.to_tile()
    x = _getrs_cyclic_jit(LU.data, perm, Bt.data, LU.desc, m)
    return CyclicMatrix.from_tile(Bt.like(x), B.desc.dist)


@partial(jax.jit, static_argnums=(1, 2))
def _potrf_cyclic_upper_jit(data, desc: CyclicDesc, mesh):
    """Upper-storage right-looking Cholesky (A = U^H U) — the lower
    sweep with the mesh axes' roles mirrored: row-panel broadcast
    along 'p', diagonal along 'q', column formation by all_gather
    along 'q' + cyclic pick (ref src/zpotrf_U.jdf)."""
    d = desc.dist
    P, Q = d.P, d.Q
    mb = desc.mb
    assert desc.mb == desc.nb and desc.M == desc.N
    KT = min(desc.MT, desc.NT)
    mloc = desc.MTL * mb
    nloc = desc.NTL * mb
    cplx = jnp.iscomplexobj(data)

    def body(local):
        from dplasma_tpu.kernels import blas as kb
        A = local.reshape(mloc, nloc)
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        grow = _grow(desc.MTL, mb, p, P, d.kp, d.ip)
        gcol = _grow(desc.NTL, mb, q, Q, d.kq, d.jq)
        for k in range(KT):
            pk = layout.owner(k, P, d.kp, d.ip)
            qk = layout.owner(k, Q, d.kq, d.jq)
            lrk = layout.local_index(k, P, d.kp)
            lck = layout.local_index(k, Q, d.kq)
            # 1) broadcast block row k along 'p' (row-panel bcast)
            rs = jax.lax.dynamic_slice_in_dim(A, lrk * mb, mb, axis=0)
            pan = jax.lax.psum(
                jnp.where(p == pk, rs, jnp.zeros_like(rs)),
                pmesh.ROW_AXIS)
            # 2) broadcast diagonal tile along 'q'
            dt = jax.lax.dynamic_slice_in_dim(pan, lck * mb, mb, axis=1)
            ddt = jax.lax.psum(
                jnp.where(q == qk, dt, jnp.zeros_like(dt)),
                pmesh.COL_AXIS)
            Ukk = kb.potrf(ddt, lower=False)
            # 3) local row-panel solve (cols strictly right of k)
            sol = kb.trsm(Ukk, pan, side="L", lower=False, trans="C")
            right = (gcol > k)[None, :]
            diagcol = ((gcol == k) & (q == qk))[None, :]
            at_k = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros_like(pan), Ukk, lck * mb, axis=1)
            Upan = jnp.where(right, sol, jnp.where(diagcol, at_k, 0))
            # 4) owners write the factored row panel back
            keep = (gcol >= k)[None, :]
            newrs = jnp.where(keep, Upan, rs)
            A = jnp.where(p == pk,
                          jax.lax.dynamic_update_slice_in_dim(
                              A, newrs, lrk * mb, axis=0), A)
            # 5) column formation: all_gather along 'q' + cyclic pick
            allg = jax.lax.all_gather(Upan, pmesh.COL_AXIS)
            flat = allg.transpose(1, 0, 2).reshape(mb, Q * nloc)
            it = grow                                    # row tiles
            qi = (it // d.kq + d.jq) % Q
            li = (it // (d.kq * Q)) * d.kq + it % d.kq
            idx = jnp.clip(qi * nloc + li * mb
                           + jnp.arange(mloc) % mb, 0, Q * nloc - 1)
            W = jnp.where((it > k)[:, None], flat[:, idx].T, 0)
            # W[i, t] = U[k*mb+t, gid_i]; trailing A_ij -= conj(W_i) U_j
            Uright = jnp.where(right, Upan, 0)
            A = A - kb.dot(W.conj() if cplx else W, Uright)
        return A.reshape(1, 1, mloc, nloc)

    f = shard_map(
        body, mesh=mesh,
        in_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                               None),
        out_specs=PartitionSpec(pmesh.ROW_AXIS, pmesh.COL_AXIS, None,
                                None))
    return f(data)


def potrf_cyclic(A: CyclicMatrix, uplo: str = "L") -> CyclicMatrix:
    """Distributed right-looking Cholesky on block-cyclic local storage
    (the pdpotrf shape; ref src/zpotrf_L.jdf / zpotrf_U.jdf over
    parsec_matrix_block_cyclic). Both uplo storages; the global-array
    left-looking :func:`dplasma_tpu.ops.potrf.potrf` remains the
    single-chip path."""
    assert uplo.upper() in ("L", "U"), uplo
    m = pmesh.active()
    assert m is not None, "potrf_cyclic needs an active mesh (use_grid)"
    ms = (m.shape[pmesh.ROW_AXIS], m.shape[pmesh.COL_AXIS])
    assert ms == (A.desc.dist.P, A.desc.dist.Q), (
        f"mesh {ms} != dist grid {(A.desc.dist.P, A.desc.dist.Q)}")
    if uplo.upper() == "U":
        # the U storage is the compat variant; the lookahead pipeline
        # and the ICI ring live on the L path (and the single-chip
        # sweep)
        out = _potrf_cyclic_upper_jit(A.data, A.desc, m)
    else:
        ring = _cyclic_ring(A.desc, A.dtype, m)
        rch = _ring_chunks(ring)
        _ring_span(A, m, ring, rch)
        out = _potrf_cyclic_jit(A.data, A.desc, m,
                                _cyclic_lookahead(), ring, rch)
    return CyclicMatrix(out, A.desc)


# ---------------------------------------------------------------------
# Analytic SPMD comm-volume model (observability)
# ---------------------------------------------------------------------

def spmd_comm_model(desc: CyclicDesc, op: str, itemsize: int,
                    kt: int | None = None, ring: bool = False) -> dict:
    """Per-collective wire-byte model of the cyclic shard_map programs.

    Mirrors the collective structure the algorithms above actually
    emit — per panel step: a masked ``psum`` along 'q' (panel
    broadcast), a masked ``psum`` along 'p' (diagonal/top-block
    broadcast), and an ``all_gather`` along 'p'/'q' (row/column panel
    formation) — priced with the standard ring costs (all-reduce
    moves ``2(n-1)/n`` of the payload per rank, all-gather ``(n-1)/n``
    of the gathered output). Returned bytes are TOTAL wire bytes
    across all ranks and steps; a 1x1 grid prices to zero.

    ``ring=True`` prices the explicit ICI-ring schedule the kernels
    emit under MCA ``ring.enable`` (kernels.pallas_ring): the panel
    broadcast becomes a store-and-forward ring (each link carries the
    panel ONCE — half the masked psum's all-reduce bytes), and the LU
    winner-row exchange becomes n-1 shift-and-add hops (``(n-1)``
    payloads per rank — latency-optimized; more wire than the
    reduce-scatter psum on large axes, fewer synchronization rounds
    on the small ones the factorizations run). A size-1 axis keeps
    its psum class (the kernels fall back per axis).

    Known ``op`` values: potrf, getrf, geqrf, gemm, herbt, ge2gb (the
    cyclic kernels in this module). Raises KeyError otherwise —
    callers surface an explicit null in the run-report rather than a
    guess.
    """
    d = desc.dist
    P, Q, R = d.P, d.Q, d.P * d.Q
    mb = desc.mb
    mloc = desc.MTL * mb
    nloc = desc.NTL * desc.nb
    KT = min(desc.MT, desc.NT)

    def psum(payload_elems: float, n: int) -> float:
        return R * 2.0 * (n - 1) / max(n, 1) * payload_elems * itemsize

    def agather(payload_elems: float, n: int) -> float:
        # per-rank output is n*payload; ring moves (n-1)*payload/rank
        return R * (n - 1) * payload_elems * itemsize

    def rbcast(payload_elems: float, n: int) -> float:
        # store-and-forward ring: each of the n-1 links in a ring row
        # carries the payload exactly once
        return R * (n - 1) / max(n, 1) * payload_elems * itemsize

    def rshift_sum(payload_elems: float, n: int) -> float:
        # n-1 shift-and-add hops, every rank sends the payload per hop
        return R * (n - 1) * payload_elems * itemsize

    ring_q = ring and Q > 1
    ring_p = ring and P > 1

    def bcast_q_entry(payload_elems: float) -> tuple:
        if ring_q:
            return "panel_ring_bcast_q", KT * rbcast(payload_elems, Q)
        return "panel_bcast_psum_q", KT * psum(payload_elems, Q)

    if op == "potrf":
        key, val = bcast_q_entry(mloc * mb)
        by = {
            key: val,
            "diag_bcast_psum_p": KT * psum(mb * mb, P),
            "row_panel_allgather_p": KT * agather(mloc * mb, P),
        }
    elif op == "getrf":
        key, val = bcast_q_entry(mloc * mb)
        by = {
            key: val,
            "candidate_allgather_p": KT * (
                agather(mb * mb, P) + agather(mb, P)),
        }
        if ring_p:
            by["pivot_row_ring_shift_p"] = \
                KT * rshift_sum(mb * nloc, P)
        else:
            by["pivot_row_exchange_psum_p"] = KT * psum(mb * nloc, P)
    elif op == "geqrf":
        key, val = bcast_q_entry(mloc * mb)
        by = {
            key: val,
            # CholeskyQR2: two Gram psums + the top-block psum along 'p'
            "gram_psum_p": KT * 3 * psum(mb * mb, P),
            "trailing_vhc_psum_p": KT * psum(mb * nloc, P),
        }
    elif op == "gemm":
        # SUMMA over slabs: per contraction step one A-column bcast
        # along 'q' and one B-row bcast along 'p' (ref zsumma_NN.jdf);
        # ``kt`` carries the contraction tile count (defaults to the
        # square case)
        KT = kt if kt is not None else KT
        by = {
            "a_col_bcast_psum_q": KT * psum(mloc * desc.nb, Q),
            "b_row_bcast_psum_p": KT * psum(desc.nb * nloc, P),
        }
    elif op == "herbt":
        by = {
            "panel_bcast_psum_q": (KT - 1) * psum(mloc * mb, Q),
            "gram_psum_p": (KT - 1) * 3 * psum(mb * mb, P),
            "inner_products_psum_p": (KT - 1) * psum(mb * nloc, P),
            "v_allgather_p": (KT - 1) * agather(mloc * mb, P),
            "two_sided_psum_q": (KT - 1) * 2 * psum(mloc * mb, Q),
        }
    elif op == "ge2gb":
        by = {
            "qr_panel_bcast_psum_q": KT * psum(mloc * mb, Q),
            "qr_gram_psum_p": KT * 3 * psum(mb * mb, P),
            "qr_trailing_psum_p": KT * psum(mb * nloc, P),
            "lq_row_bcast_psum_p": KT * psum(mb * nloc, P),
            "lq_gram_psum_q": KT * 3 * psum(mb * mb, Q),
            "lq_trailing_psum_q": KT * psum(mloc * mb, Q),
        }
    else:
        raise KeyError(f"no spmd comm model for op {op!r}")
    by = {k: float(v) for k, v in by.items()}
    return {"model": "spmd_ring", "steps": KT,
            "bytes_total": float(sum(by.values())),
            "bytes_by_collective": by}
