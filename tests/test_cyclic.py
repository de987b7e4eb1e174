"""Realized block-cyclic distribution (parallel/cyclic): placement must
match the layout owner map on a real device mesh, conversions must
round-trip, and the shard_map distributed POTRF must agree with the
reference-checked global algorithm. Ref: parsec_matrix_block_cyclic_init
(tests/testing_zpotrf.c:100-103, tests/common.c:79-93)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dplasma_tpu.descriptors import Dist, TileMatrix
from dplasma_tpu.ops import generators, potrf as potrf_mod
from dplasma_tpu.parallel import cyclic, layout, mesh


DISTS = [
    Dist(P=2, Q=4),
    Dist(P=2, Q=4, kp=2, kq=1),
    Dist(P=2, Q=4, kp=2, kq=3),
    Dist(P=2, Q=4, kp=1, kq=2, ip=1, jq=2),
    Dist(P=4, Q=2, kp=3, kq=2, ip=2),
]


@pytest.mark.parametrize("dist", DISTS)
@pytest.mark.parametrize("MN", [(8, 8), (11, 7), (5, 13)])
def test_roundtrip(devices8, dist, MN):
    MT, NT = MN
    mb = 4
    M, N = MT * mb - 1, NT * mb - 2  # ragged edges
    rng = np.random.default_rng(5)
    A = TileMatrix.from_dense(
        jnp.asarray(rng.standard_normal((M, N))), mb, mb, dist)
    m = mesh.make_mesh(dist.P, dist.Q, devices8 * ((dist.P * dist.Q) //
                                                   len(devices8) or 1))
    with mesh.use_grid(m):
        C = cyclic.CyclicMatrix.from_tile(A)
        back = C.to_tile()
    np.testing.assert_allclose(np.asarray(back.data),
                               np.asarray(A.zero_pad().data))


def test_placement_matches_rank_of(devices8):
    """Tile (i,j) must physically live on the device at mesh position
    layout.rank_of(i,j) — the round-1 gap: --kp/--kq were parsed but
    placement was contiguous."""
    dist = Dist(P=2, Q=4, kp=2, kq=1, ip=1)
    mb = 4
    MT, NT = 9, 6
    rng = np.random.default_rng(0)
    A = TileMatrix.from_dense(
        jnp.asarray(rng.standard_normal((MT * mb, NT * mb))), mb, mb,
        dist)
    m = mesh.make_mesh(2, 4)
    with mesh.use_grid(m):
        C = cyclic.CyclicMatrix.from_tile(A)
        C = cyclic.CyclicMatrix(
            jax.device_put(C.data, jax.sharding.NamedSharding(
                m, jax.sharding.PartitionSpec("p", "q", None, None))),
            C.desc)
    # map each device slab back to the tiles it holds
    full = np.asarray(A.zero_pad().data)
    for shard in C.data.addressable_shards:
        p, q = shard.index[0].start, shard.index[1].start
        slab = np.asarray(shard.data)[0, 0]
        for l in range(C.desc.MTL):
            i = layout.global_index(l, p, dist.P, dist.kp, dist.ip)
            for c in range(C.desc.NTL):
                j = layout.global_index(c, q, dist.Q, dist.kq, dist.jq)
                tile = slab[l * mb:(l + 1) * mb, c * mb:(c + 1) * mb]
                if i < MT and j < NT:
                    assert layout.rank_of(
                        i, j, P=dist.P, Q=dist.Q, kp=dist.kp,
                        kq=dist.kq, ip=dist.ip, jq=dist.jq) == (p, q)
                    ref = full[i * mb:(i + 1) * mb, j * mb:(j + 1) * mb]
                    np.testing.assert_array_equal(tile, ref)
                else:
                    np.testing.assert_array_equal(tile, 0)


@pytest.mark.parametrize("dist", [
    Dist(P=2, Q=4),
    Dist(P=2, Q=4, kp=2, kq=2),
    Dist(P=4, Q=2, kp=1, kq=3, ip=1, jq=1),
])
@pytest.mark.parametrize("MT", [4, 7])
def test_potrf_cyclic_matches_global(devices8, dist, MT):
    mb = 8
    N = MT * mb
    A = generators.plghe(float(N), N, mb, seed=3872, dtype=jnp.float64)
    A = TileMatrix(A.data, A.desc.with_shape(N, N))
    ref = potrf_mod.potrf(A, "L").to_dense()
    m = mesh.make_mesh(dist.P, dist.Q)
    with mesh.use_grid(m):
        C = cyclic.CyclicMatrix.from_tile(A, dist)
        L = cyclic.potrf_cyclic(C, "L").to_tile().to_dense()
    np.testing.assert_allclose(np.asarray(jnp.tril(L)),
                               np.asarray(jnp.tril(ref)),
                               rtol=1e-10, atol=1e-10)


@pytest.mark.slow
def test_potrf_cyclic_complex(devices8):
    dist = Dist(P=2, Q=4, kp=2)
    mb, MT = 6, 5
    N = MT * mb
    A = generators.plghe(float(N), N, mb, seed=77, dtype=jnp.complex128)
    ref = potrf_mod.potrf(A, "L").to_dense()
    m = mesh.make_mesh(2, 4)
    with mesh.use_grid(m):
        C = cyclic.CyclicMatrix.from_tile(A, dist)
        L = cyclic.potrf_cyclic(C, "L").to_tile().to_dense()
    np.testing.assert_allclose(np.asarray(jnp.tril(L)),
                               np.asarray(jnp.tril(ref)),
                               rtol=1e-10, atol=1e-10)


@pytest.mark.parametrize("dist", [
    Dist(P=2, Q=4),
    Dist(P=2, Q=4, kp=2, kq=2),
    Dist(P=4, Q=2, kp=1, kq=3, ip=1, jq=1),
])
@pytest.mark.parametrize("MT", [4, 7])
def test_getrf_cyclic_factorizes(devices8, dist, MT):
    """Distributed tournament LU: A[perm] = L U on the padded matrix
    (pivots may differ from the single-stream getrf_1d — tournament vs
    direct partial pivoting — so the factorization contract is checked,
    not pivot equality). Ref: src/zgetrf_ptgpanel.jdf."""
    mb = 8
    N = MT * mb - 3  # ragged edge tiles
    A = generators.plrnt(N, N, mb, mb, seed=3872, dtype=jnp.float64)
    base = TileMatrix(A.pad_diag().data, A.desc)
    m = mesh.make_mesh(dist.P, dist.Q)
    with mesh.use_grid(m):
        C = cyclic.CyclicMatrix.from_tile(base, dist)
        F, perm = cyclic.getrf_cyclic(C)
        full = np.asarray(F.to_tile().data)[np.asarray(perm)]
    ap = np.asarray(base.data)[np.asarray(perm)]
    n = full.shape[0]
    L = np.tril(full, -1) + np.eye(n)
    r = np.abs(ap - L @ np.triu(full)).max()
    assert r < 1e-10 * N, r
    assert np.abs(np.tril(full, -1)).max() <= 8.0  # CALU growth bound


def test_getrf_ptgpanel_routes_distributed(devices8):
    """ops.lu.getrf_ptgpanel under a mesh runs the cyclic distributed
    panel (grid taken from the active mesh, even when the matrix's Dist
    doesn't name it), stays jit-traceable, and keeps the getrf_1d
    (LU, perm) solve contract."""
    from dplasma_tpu.ops import checks, lu as lu_mod
    N, mb = 52, 8
    # default Dist(1,1) — the driver-generated shape; mesh supplies grid
    A = generators.plrnt(N, N, mb, mb, seed=11, dtype=jnp.float64)
    B = generators.plrnt(N, 5, mb, mb, seed=12, dtype=jnp.float64)
    m = mesh.make_mesh(2, 4)
    with mesh.use_grid(m):
        LU, perm = jax.jit(lu_mod.getrf_ptgpanel)(A)
    X = lu_mod.getrs("N", LU, perm, B)
    r, ok = checks.check_axmb(A, B, X)
    assert ok, r


def test_geqrf_cyclic_residual(devices8):
    """Distributed blocked QR on cyclic storage: residual and
    orthogonality through the standard compact-WY apply (BASELINE
    config #3 — the zgeqrf_param role; the Gram psum along 'p' is the
    HQR high-level combining tree)."""
    from dplasma_tpu.ops import qr as qr_mod

    P, Q = 2, 4
    m = mesh.make_mesh(P, Q, devices8)
    N, nb = 48, 8
    dist = Dist(P=P, Q=Q, kp=2, kq=2)
    with mesh.use_grid(m):
        A0 = generators.plrnt(N, N, nb, nb, seed=5, dtype=jnp.float32)
        C = cyclic.CyclicMatrix.from_tile(A0, dist)
        F, Ts = cyclic.geqrf_cyclic(C)
        packed = F.to_tile()
        Tf = cyclic.qr_t_factor(Ts, A0)
        R = jnp.triu(packed.to_dense())
        Rm = TileMatrix.from_dense(R, nb, nb)
        QR = np.asarray(qr_mod.unmqr("L", "N", packed, Tf, Rm)
                        .to_dense())
        a = np.asarray(A0.to_dense())
        eps = np.finfo(np.float32).eps
        resid = np.abs(QR - a).max() / (np.abs(a).max() * N * eps)
        assert resid < 100, resid
        eye = jnp.eye(N, dtype=jnp.float32)
        Qm = np.asarray(qr_mod.unmqr(
            "L", "N", packed, Tf,
            TileMatrix.from_dense(eye, nb, nb)).to_dense())
        orth = np.abs(Qm.T @ Qm - np.eye(N)).max() / (N * eps)
        assert orth < 100, orth


@pytest.mark.parametrize(
    "dist",
    # one representative fast; the full supertile/offset sweep is a
    # compile-heavy ~40-60s each and rides the slow tier (VERDICT r4
    # item 8 — coverage of the component stays per-PR via dist0)
    [DISTS[0]] + [pytest.param(d, marks=pytest.mark.slow)
                  for d in DISTS[1:]])
def test_a2a_conversion_matches_gather(devices8, dist):
    """Memory-bounded all_to_all conversions (VERDICT r2 weak #5 /
    the parsec_redistribute role): must reproduce the gather path
    exactly and round-trip, with only O(local)-sized exchange
    buffers."""
    MT, NT = 7, 5
    mb = 4
    M, N = MT * mb - 1, NT * mb - 2
    rng = np.random.default_rng(5)
    A = TileMatrix.from_dense(
        jnp.asarray(rng.standard_normal((M, N))), mb, mb, dist)
    # reference slabs from the trace-time gather path (no active mesh)
    ref = cyclic.CyclicMatrix.from_tile(A, dist)
    m = mesh.make_mesh(dist.P, dist.Q, devices8)
    with mesh.use_grid(m):
        got = cyclic.from_tile_a2a(A, dist)
        np.testing.assert_allclose(np.asarray(got.data),
                                   np.asarray(ref.data))
        back = cyclic.to_tile_a2a(got)
        np.testing.assert_allclose(np.asarray(back.data),
                                   np.asarray(A.zero_pad().data))



def test_a2a_conversion_memory_bounded(devices8):
    """The a2a path's compiled temp footprint must stay well under a
    replicated global array (asymptotically O(N^2/PQ); measured at a
    size where padding constants don't dominate)."""
    dist = Dist(P=2, Q=4, kp=2, kq=2)
    mb, MT = 8, 64
    M = N = MT * mb
    rng = np.random.default_rng(5)
    A = TileMatrix.from_dense(
        jnp.asarray(rng.standard_normal((M, N))), mb, mb, dist)
    m = mesh.make_mesh(dist.P, dist.Q, devices8)
    with mesh.use_grid(m):
        f = jax.jit(lambda a: cyclic.from_tile_a2a(
            TileMatrix(a, A.desc), dist).data)
        compiled = f.lower(A.zero_pad().data).compile()
        try:
            stats = compiled.memory_analysis()
        except Exception:
            stats = None
        if stats is None or not hasattr(stats, "temp_size_in_bytes"):
            pytest.skip("backend reports no memory analysis")
        full = M * N * 8
        assert stats.temp_size_in_bytes < full // 2, (
            stats.temp_size_in_bytes, full)


@pytest.mark.slow
def test_a2a_dispatch_via_mca(devices8):
    """MCA cyclic.convert=a2a routes the standard from_tile/to_tile
    through the exchange path (the accelerator default)."""
    from dplasma_tpu.utils import config as cfg

    dist = Dist(P=2, Q=4, kp=2, kq=1)
    mb, MT, NT = 4, 11, 7
    rng = np.random.default_rng(5)
    A = TileMatrix.from_dense(
        jnp.asarray(rng.standard_normal((MT * mb - 1, NT * mb - 2))),
        mb, mb, dist)
    ref = cyclic.CyclicMatrix.from_tile(A, dist)   # gather (no mesh)
    m = mesh.make_mesh(dist.P, dist.Q, devices8)
    cfg.mca_set("cyclic.convert", "a2a")
    try:
        with mesh.use_grid(m):
            got = cyclic.CyclicMatrix.from_tile(A, dist)
            np.testing.assert_allclose(np.asarray(got.data),
                                       np.asarray(ref.data))
            back = got.to_tile()
            np.testing.assert_allclose(
                np.asarray(back.data), np.asarray(A.zero_pad().data))
    finally:
        cfg._MCA_OVERRIDES.pop("cyclic.convert", None)

@pytest.mark.parametrize("dist", [
    Dist(P=2, Q=4),
    Dist(P=2, Q=4, kp=2, kq=2),
])
def test_potrs_cyclic_solves_in_slabs(devices8, dist):
    """Distributed POTRS: factor + solve never leave the cyclic slabs
    (VERDICT r3 missing #1 — the ztrsm_LLN/zpotrs_wrapper role)."""
    from dplasma_tpu.ops import checks
    mb, MT = 8, 5
    N, nrhs = MT * mb, 16
    A = generators.plghe(float(N), N, mb, seed=3872, dtype=jnp.float64)
    A = TileMatrix(A.data, A.desc.with_shape(N, N))
    rng = np.random.default_rng(7)
    B = TileMatrix.from_dense(
        jnp.asarray(rng.standard_normal((N, nrhs))), mb, mb, dist)
    m = mesh.make_mesh(dist.P, dist.Q)
    with mesh.use_grid(m):
        C = cyclic.CyclicMatrix.from_tile(A, dist)
        Bc = cyclic.CyclicMatrix.from_tile(B, dist)
        L = cyclic.potrf_cyclic(C, "L")
        Xc = cyclic.potrs_cyclic(L, Bc)
        X = Xc.to_tile()
    r, ok = checks.check_axmb(A, B, TileMatrix(
        X.data[:, :B.data.shape[1]], B.desc))
    assert ok, r


def test_trsm_cyclic_matches_blas3(devices8):
    from dplasma_tpu.ops import blas3
    dist = Dist(P=2, Q=4, kp=2, kq=1)
    mb, MT = 8, 4
    N, nrhs = MT * mb, 24
    rng = np.random.default_rng(3)
    Lf = np.tril(rng.standard_normal((N, N))) + N * np.eye(N)
    B = rng.standard_normal((N, nrhs))
    Lt = TileMatrix.from_dense(jnp.asarray(Lf), mb, mb, dist)
    Bt = TileMatrix.from_dense(jnp.asarray(B), mb, mb, dist)
    m = mesh.make_mesh(dist.P, dist.Q)
    with mesh.use_grid(m):
        Lc = cyclic.CyclicMatrix.from_tile(Lt, dist)
        Bc = cyclic.CyclicMatrix.from_tile(Bt, dist)
        for trans in ("N", "C"):
            Xc = cyclic.trsm_cyclic(Lc, Bc, trans)
            X = np.asarray(Xc.to_tile().data)[:N, :nrhs]
            ref = np.asarray(blas3.trsm(
                1.0, Lt, Bt, side="L", uplo="L",
                trans=trans).data)[:N, :nrhs]
            np.testing.assert_allclose(X, ref, rtol=1e-9, atol=1e-9)


def test_gemm_herk_cyclic(devices8):
    dist = Dist(P=2, Q=4, kp=1, kq=2)
    mb, MT = 8, 4
    N = MT * mb
    rng = np.random.default_rng(9)
    a = rng.standard_normal((N, N))
    b = rng.standard_normal((N, N))
    At = TileMatrix.from_dense(jnp.asarray(a), mb, mb, dist)
    Bt = TileMatrix.from_dense(jnp.asarray(b), mb, mb, dist)
    m = mesh.make_mesh(dist.P, dist.Q)
    with mesh.use_grid(m):
        Ac = cyclic.CyclicMatrix.from_tile(At, dist)
        Bc = cyclic.CyclicMatrix.from_tile(Bt, dist)
        Cc = cyclic.gemm_cyclic(Ac, Bc)
        got = np.asarray(Cc.to_tile().data)[:N, :N]
        np.testing.assert_allclose(got, a @ b, rtol=1e-10, atol=1e-8)
        Hc = cyclic.herk_cyclic(Ac)
        goth = np.asarray(Hc.to_tile().data)[:N, :N]
        np.testing.assert_allclose(np.tril(goth), np.tril(a @ a.T),
                                   rtol=1e-10, atol=1e-8)


def test_getrs_cyclic_solves_in_slabs(devices8):
    """Distributed LU solve from the in-place tournament factor: row
    gather to elimination order + two slab TRSM sweeps (pdgetrs)."""
    from dplasma_tpu.ops import checks
    dist = Dist(P=2, Q=4, kp=2, kq=2)
    mb, MT = 8, 4
    N, nrhs = MT * mb, 8
    A = generators.plrnt(N, N, mb, mb, seed=3872, dtype=jnp.float64)
    A = TileMatrix(A.pad_diag().data, A.desc)
    rng = np.random.default_rng(4)
    B = TileMatrix.from_dense(
        jnp.asarray(rng.standard_normal((N, nrhs))), mb, mb, dist)
    m = mesh.make_mesh(dist.P, dist.Q)
    with mesh.use_grid(m):
        Ac = cyclic.CyclicMatrix.from_tile(A, dist)
        Bc = cyclic.CyclicMatrix.from_tile(B, dist)
        F, perm = cyclic.getrf_cyclic(Ac)
        Xc = cyclic.getrs_cyclic(F, perm, Bc)
        X = Xc.to_tile()
    r, ok = checks.check_axmb(A, B, TileMatrix(
        X.data[:, :B.data.shape[1]], B.desc))
    assert ok, r


def _routes():
    from dplasma_tpu.ops import lu as lu_mod
    return dict(lu_mod.GETRS_ROUTES)


def _routes_since(before):
    return {k: v - before[k] for k, v in _routes().items()}


@pytest.mark.parametrize("grid,kp,kq,nrhs,dtype", [
    ((2, 2), 1, 1, 1, jnp.float32),
    ((2, 2), 2, 2, 5, jnp.float64),
    ((2, 2), 1, 2, 5, jnp.float32),
    ((2, 4), 1, 2, 1, jnp.float64),
    ((2, 4), 2, 1, 5, jnp.float32),
    ((2, 4), 2, 2, 1, jnp.float64),
])
def test_getrf_getrs_one_program_on_slabs(devices8, grid, kp, kq, nrhs,
                                          dtype):
    """getrf_ptgpanel -> getrs("N") inside one jit solves on the cyclic
    slabs (ragged N, every kp/kq in {1, 2}) and agrees with the dense
    route on the same factor."""
    from dplasma_tpu.ops import checks, lu as lu_mod
    N, mb = 52, 8
    dist = Dist(P=grid[0], Q=grid[1], kp=kp, kq=kq)
    A = generators.plrnt(N, N, mb, mb, seed=21, dist=dist, dtype=dtype)
    B = generators.plrnt(N, nrhs, mb, mb, seed=22, dtype=dtype)

    def prog(a, b):
        LU, perm = lu_mod.getrf_ptgpanel(A.like(a))
        x = lu_mod.getrs("N", LU, perm, B.like(b))
        # .like drops the cyclic factor: the dense route, same factor
        xd = lu_mod.getrs("N", LU.like(LU.data), perm, B.like(b))
        return x.data, xd.data

    before = _routes()
    with mesh.use_grid(mesh.make_mesh(*grid, devices8)):
        x, xd = jax.jit(prog)(A.data, B.data)
    assert _routes_since(before) == {"cyclic": 1, "dense": 1}
    assert x.shape == B.data.shape and x.dtype == B.dtype
    tol = 1e-4 if dtype == jnp.float32 else 1e-10
    np.testing.assert_allclose(np.asarray(x), np.asarray(xd), rtol=tol,
                               atol=tol)
    r, ok = checks.check_axmb(A, B, TileMatrix(x, B.desc))
    assert ok, r


@pytest.mark.parametrize("case", ["jit_boundary", "trans_T", "trans_C",
                                  "foreign_perm", "getrf_1d"])
def test_getrs_falls_back_to_the_dense_route(devices8, case):
    """Whatever getrs cannot see the slabs through solves on the dense
    factor as before: a factor that crossed a jit boundary, trans T/C,
    a perm other than the one returned with the factor, getrf_1d's
    factor. Each is counted as ``dense`` and solves op(A) X = B."""
    from dplasma_tpu.ops import lu as lu_mod
    N, mb, nrhs = 52, 8, 3
    A = generators.plrnt(N, N, mb, mb, seed=31, dtype=jnp.float64)
    B = generators.plrnt(N, nrhs, mb, mb, seed=32, dtype=jnp.float64)
    trans = case[-1] if case.startswith("trans") else "N"

    def prog(a, b):
        At = A.like(a)
        LU, perm = (lu_mod.getrf_1d(At) if case == "getrf_1d"
                    else lu_mod.getrf_ptgpanel(At))
        if case == "foreign_perm":
            perm = perm + 0
        return lu_mod.getrs(trans, LU, perm, B.like(b)).data

    before = _routes()
    m = mesh.make_mesh(2, 2, devices8)
    with mesh.use_grid(m):
        if case == "jit_boundary":
            # a fresh function: jit's cache does not key on the mesh
            LU, perm = jax.jit(lambda a: lu_mod.getrf_ptgpanel(a))(A)
            rep = jax.sharding.NamedSharding(m, jax.sharding.PartitionSpec())
            perm, b = jax.device_put((perm, B.data), rep)
            x = lu_mod.getrs("N", LU, perm, B.like(b)).data
        else:
            x = jax.jit(prog)(A.data, B.data)
    assert _routes_since(before) == {"cyclic": 0, "dense": 1}
    a = np.asarray(A.to_dense())
    opa = a if trans == "N" else a.T
    xs = np.asarray(x)[:N, :nrhs]
    b = np.asarray(B.to_dense())
    r = np.abs(opa @ xs - b).max() / (np.abs(a).max() * np.abs(xs).max()
                                       * N * np.finfo(np.float64).eps)
    assert r < 60, r


def test_getrs_route_count_loses_no_update():
    """Threads that trace getrs at once (serving's workers) all count."""
    import sys
    import threading
    from dplasma_tpu.ops import lu as lu_mod
    before = _routes()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=lambda: [
            lu_mod._count_route("cyclic") for _ in range(2000)])
            for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    assert _routes_since(before) == {"cyclic": 16000, "dense": 0}


@pytest.mark.parametrize("prog,grid,route", [
    ("testing_sgetrf_1d", ["-p", "1", "-q", "1", "-x"], "dense"),
    ("testing_dgesv_ir", ["-p", "2", "-q", "2"], "cyclic"),
])
def test_driver_report_counts_getrs_routes(tmp_path, capsys, prog, grid,
                                           route):
    """The run report exports lu_getrs_route_total{route}: getrf_1d's
    check solves on the dense factor, the grid's IR solves on the
    cyclic one."""
    import json
    from dplasma_tpu.drivers import main
    rj = str(tmp_path / "r.json")
    rc = main(["-N", "64", "-t", "16", *grid, f"--report={rj}"],
              prog=prog)
    capsys.readouterr()
    assert rc == 0
    doc = json.load(open(rj))
    got = {m["labels"]["route"]: m["value"] for m in doc["metrics"]
           if m["name"] == "lu_getrs_route_total"}
    assert got.get(route, 0) >= 1, got
    assert set(got) <= {"cyclic", "dense"}


def test_getrs_on_slabs_drops_the_dense_factor(devices8):
    """With MCA cyclic.convert=a2a (the accelerator route) on a 2x2
    mesh, a factor -> solve program that returns only X keeps the way
    into the slabs and drops the way back: half the redistribute
    all_to_all ops, and no gather of the dense factor by perm. The same
    program returning (F, perm, X) still builds the dense factor, equal
    to the factorization's own."""
    import re
    from tests.conftest import mca_overrides
    from dplasma_tpu.ops import lu as lu_mod
    n, nb = 128, 32
    A = generators.plrnt(n, n, nb, nb, seed=3, dtype=jnp.float32)
    B = generators.plrnt(n, 1, nb, 1, seed=4, dtype=jnp.float32)

    def solve(a, b):
        LU, perm = lu_mod.getrf_ptgpanel(A.like(a))
        return LU.data, perm, lu_mod.getrs("N", LU, perm, B.like(b)).data

    def redistribute_ops(text, kind, scope="dplasma.redistribute/"):
        return [ln for ln in text.splitlines()
                if re.search(rf" {kind}(-start)?\(", ln) and scope in ln]

    with mca_overrides({"cyclic.convert": "a2a"}), \
            mesh.use_grid(mesh.make_mesh(2, 2, devices8)):
        x_only = jax.jit(lambda a, b: solve(a, b)[2])
        full = jax.jit(solve)
        texts = [f.lower(A.data, B.data).compile().as_text()
                 for f in (x_only, full)]
        x = x_only(A.data, B.data)
        F, perm, x_full = full(A.data, B.data)
        F0, perm0 = jax.jit(lambda a: lu_mod.getrf_ptgpanel(a))(A)
    a2a = [len(redistribute_ops(t, "all-to-all")) for t in texts]
    assert a2a[1] == 4 and a2a[0] == a2a[1] // 2, a2a
    # the row gather by perm (GSPMD makes it an all-gather on the CPU)
    by_perm = [len(redistribute_ops(t, "(all-)?gather",
                                    "dplasma.redistribute/gather"))
               for t in texts]
    assert by_perm[0] == 0 and by_perm[1] > 0, by_perm
    np.testing.assert_array_equal(np.asarray(F), np.asarray(F0.data))
    np.testing.assert_array_equal(np.asarray(perm), np.asarray(perm0))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(x_full))


def test_herk_cyclic_rectangular(devices8):
    """C = A A^H for rectangular A: C follows the M x M descriptor,
    not A's column tiling (review r4)."""
    dist = Dist(P=2, Q=4, kp=1, kq=2)
    mb = 8
    M, K = 48, 16
    rng = np.random.default_rng(11)
    a = rng.standard_normal((M, K))
    At = TileMatrix.from_dense(jnp.asarray(a), mb, mb, dist)
    m = mesh.make_mesh(dist.P, dist.Q)
    with mesh.use_grid(m):
        Ac = cyclic.CyclicMatrix.from_tile(At, dist)
        Hc = cyclic.herk_cyclic(Ac)
        assert Hc.desc.M == Hc.desc.N == M
        goth = np.asarray(Hc.to_tile().data)[:M, :M]
        np.testing.assert_allclose(np.tril(goth), np.tril(a @ a.T),
                                   rtol=1e-10, atol=1e-8)


def test_herbt_heev_cyclic(devices8):
    """Distributed heev chain (BASELINE config #5): herbt on cyclic
    slabs preserves eigenvalues and leaves the mb-band; heev_cyclic
    matches the dense eigensolver (ref src/zheev_wrapper.c:96-103)."""
    from dplasma_tpu.ops.norms import _sym_full
    dist = Dist(P=2, Q=4, kp=2, kq=2)
    N, mb = 64, 8
    A0 = generators.plghe(float(N), N, mb, seed=17, dtype=jnp.float64,
                          dist=dist)
    full = _sym_full(A0, "L", conj=True)
    At = TileMatrix.from_dense(full, mb, mb, dist)
    m = mesh.make_mesh(dist.P, dist.Q)
    with mesh.use_grid(m):
        Ac = cyclic.CyclicMatrix.from_tile(At, dist)
        Bc = cyclic.herbt_cyclic(Ac)
        B = np.asarray(Bc.to_tile().to_dense())
        w_ref = np.linalg.eigvalsh(np.asarray(full))
        for dd_ in range(mb + 1, N):
            assert np.abs(np.diagonal(B, -dd_)).max() < 1e-10
        assert np.max(np.abs(np.linalg.eigvalsh(B) - w_ref)) < 1e-10 * N
        w = np.asarray(cyclic.heev_cyclic(Ac))
        assert np.max(np.abs(w - w_ref)) / np.max(np.abs(w_ref)) \
            < 1e-12 * N


@pytest.mark.parametrize("dist", [
    Dist(P=2, Q=4, kp=1, kq=2),
    Dist(P=4, Q=2, kp=2, kq=1, ip=1),
])
def test_trmm_cyclic_matches_dense(devices8, dist):
    """Distributed triangular multiply (ref src/ztrmm_LLN.jdf family):
    all four (uplo, trans) corners plus unit diagonal."""
    mb, MT = 8, 4
    N, nrhs = MT * mb, 24
    rng = np.random.default_rng(6)
    T = rng.standard_normal((N, N))
    B = rng.standard_normal((N, nrhs))
    Tt = TileMatrix.from_dense(jnp.asarray(T), mb, mb, dist)
    Bt = TileMatrix.from_dense(jnp.asarray(B), mb, mb, dist)
    m = mesh.make_mesh(dist.P, dist.Q)
    with mesh.use_grid(m):
        Tc = cyclic.CyclicMatrix.from_tile(Tt, dist)
        Bc = cyclic.CyclicMatrix.from_tile(Bt, dist)
        for uplo in ("L", "U"):
            Tm = np.tril(T) if uplo == "L" else np.triu(T)
            for trans in ("N", "C"):
                op = Tm if trans == "N" else Tm.T
                got = cyclic.trmm_cyclic(Tc, Bc, trans, uplo=uplo)
                gd = np.asarray(got.to_tile().data)[:N, :nrhs]
                np.testing.assert_allclose(gd, op @ B, rtol=1e-10,
                                           atol=1e-8)
        Tu = np.tril(T, -1) + np.eye(N)
        got = cyclic.trmm_cyclic(Tc, Bc, "N", unit=True, uplo="L")
        gd = np.asarray(got.to_tile().data)[:N, :nrhs]
        np.testing.assert_allclose(gd, Tu @ B, rtol=1e-10, atol=1e-8)


@pytest.mark.parametrize("dist", [
    Dist(P=2, Q=4, kp=2, kq=2),
    Dist(P=4, Q=2, kp=1, kq=1, jq=1),
])
def test_hemm_her2k_cyclic(devices8, dist):
    """Distributed hemm (stored-lower Hermitian multiply, ref
    src/zhemm.jdf) and her2k (ref src/zher2k_LN.jdf)."""
    mb, MT = 8, 4
    N, nrhs = MT * mb, 16
    rng = np.random.default_rng(8)
    a0 = rng.standard_normal((N, N))
    H = a0 + a0.T
    B = rng.standard_normal((N, nrhs))
    # stored-lower input: upper triangle holds scratch that must not leak
    stored = np.tril(H) + np.triu(rng.standard_normal((N, N)), 1)
    Ht = TileMatrix.from_dense(jnp.asarray(stored), mb, mb, dist)
    Bt = TileMatrix.from_dense(jnp.asarray(B), mb, mb, dist)
    m = mesh.make_mesh(dist.P, dist.Q)
    with mesh.use_grid(m):
        Hc = cyclic.CyclicMatrix.from_tile(Ht, dist)
        Bc = cyclic.CyclicMatrix.from_tile(Bt, dist)
        got = cyclic.hemm_cyclic(Hc, Bc)
        gd = np.asarray(got.to_tile().data)[:N, :nrhs]
        np.testing.assert_allclose(gd, H @ B, rtol=1e-10, atol=1e-8)
        # her2k on rectangular A, B
        K = 16
        A2 = rng.standard_normal((N, K))
        B2 = rng.standard_normal((N, K))
        At2 = TileMatrix.from_dense(jnp.asarray(A2), mb, mb, dist)
        Bt2 = TileMatrix.from_dense(jnp.asarray(B2), mb, mb, dist)
        Ac2 = cyclic.CyclicMatrix.from_tile(At2, dist)
        Bc2 = cyclic.CyclicMatrix.from_tile(Bt2, dist)
        got2 = cyclic.her2k_cyclic(Ac2, Bc2)
        gd2 = np.asarray(got2.to_tile().data)[:N, :N]
        ref2 = A2 @ B2.T + B2 @ A2.T
        np.testing.assert_allclose(np.tril(gd2), np.tril(ref2),
                                   rtol=1e-10, atol=1e-8)


@pytest.mark.parametrize("dist", [
    Dist(P=2, Q=4, kp=2, kq=1),
    Dist(P=4, Q=2, kp=1, kq=2),
])
def test_trtri_lauum_potri_cyclic(devices8, dist):
    """Distributed trtri/lauum/potri chain (ref src/ztrtri_L.jdf,
    src/zlauum_L.jdf, zpotri_wrapper.c): inverse, Gram, and the
    composed SPD inverse all verified against dense references."""
    mb, MT = 8, 4
    N = MT * mb
    rng = np.random.default_rng(12)
    a0 = rng.standard_normal((N, N))
    spd = a0 @ a0.T + N * np.eye(N)
    Lf = np.linalg.cholesky(spd)
    Lt = TileMatrix.from_dense(jnp.asarray(Lf), mb, mb, dist)
    m = mesh.make_mesh(dist.P, dist.Q)
    with mesh.use_grid(m):
        Lc = cyclic.CyclicMatrix.from_tile(Lt, dist)
        Xi = cyclic.trtri_cyclic(Lc)
        gd = np.asarray(Xi.to_tile().data)[:N, :N]
        np.testing.assert_allclose(gd, np.linalg.inv(Lf), rtol=1e-8,
                                   atol=1e-8)
        La = cyclic.lauum_cyclic(Lc)
        ga = np.asarray(La.to_tile().data)[:N, :N]
        np.testing.assert_allclose(np.tril(ga), np.tril(Lf.T @ Lf),
                                   rtol=1e-9, atol=1e-8)
        Pi = cyclic.potri_cyclic(Lc)
        gp = np.asarray(Pi.to_tile().data)[:N, :N]
        np.testing.assert_allclose(np.tril(gp),
                                   np.tril(np.linalg.inv(spd)),
                                   rtol=1e-7, atol=1e-7)


@pytest.mark.parametrize("dist", [
    Dist(P=2, Q=4, kp=2, kq=2),
    pytest.param(Dist(P=4, Q=2, kp=1, kq=2),
                 marks=pytest.mark.slow),
])
def test_ge2gb_gesvd_cyclic(devices8, dist):
    """Distributed SVD stage 1 (ref src/zgebrd_ge2gb.jdf): the QR/LQ
    alternation on cyclic slabs leaves an upper band of bandwidth mb
    with A's singular values; gesvd_cyclic finishes the chain."""
    N, mb = 64, 8
    rng = np.random.default_rng(21)
    a = rng.standard_normal((N, N))
    At = TileMatrix.from_dense(jnp.asarray(a), mb, mb, dist)
    m = mesh.make_mesh(dist.P, dist.Q)
    with mesh.use_grid(m):
        Ac = cyclic.CyclicMatrix.from_tile(At, dist)
        Bc = cyclic.gebrd_ge2gb_cyclic(Ac)
        B = np.asarray(Bc.to_tile().data)[:N, :N]
        # band structure: zero below the diagonal block row and right
        # of the first superdiagonal block
        for off in range(1, N):
            assert np.abs(np.diagonal(B, -off)).max() < 1e-9, off
        for off in range(2 * mb, N):
            assert np.abs(np.diagonal(B, off)).max() < 1e-9, off
        s_ref = np.linalg.svd(a, compute_uv=False)
        s_band = np.linalg.svd(B, compute_uv=False)
        assert np.abs(s_band - s_ref).max() / s_ref[0] < 1e-10
        s_got = np.sort(np.asarray(cyclic.gesvd_cyclic(Ac)))[::-1]
        assert np.abs(s_got - s_ref).max() / s_ref[0] < 1e-8


@pytest.mark.parametrize("dist", [
    Dist(P=2, Q=4, kp=2, kq=1),
    Dist(P=4, Q=2, kp=1, kq=2, jq=1),
])
def test_potrf_potrs_cyclic_upper(devices8, dist):
    """Upper-storage distributed Cholesky + solve (ref
    src/zpotrf_U.jdf): A = U^H U factored and solved on slabs —
    the r4 lower-only contract widened."""
    mb, MT = 8, 4
    N, nrhs = MT * mb, 16
    rng = np.random.default_rng(14)
    a0 = rng.standard_normal((N, N))
    spd = a0 @ a0.T + N * np.eye(N)
    X0 = rng.standard_normal((N, nrhs))
    B0 = spd @ X0
    At = TileMatrix.from_dense(jnp.asarray(np.triu(spd)), mb, mb, dist)
    Bt = TileMatrix.from_dense(jnp.asarray(B0), mb, mb, dist)
    m = mesh.make_mesh(dist.P, dist.Q)
    with mesh.use_grid(m):
        Ac = cyclic.CyclicMatrix.from_tile(At, dist)
        Bc = cyclic.CyclicMatrix.from_tile(Bt, dist)
        Uc = cyclic.potrf_cyclic(Ac, "U")
        U = np.triu(np.asarray(Uc.to_tile().data))[:N, :N]
        ref = np.linalg.cholesky(spd).T
        np.testing.assert_allclose(U, ref, rtol=1e-8, atol=1e-8)
        Xc = cyclic.potrs_cyclic(Uc, Bc, uplo="U")
        X = np.asarray(Xc.to_tile().data)[:N, :nrhs]
        np.testing.assert_allclose(X, X0, rtol=1e-6, atol=1e-6)


def test_trsm_cyclic_all_corners(devices8):
    """All four (uplo, trans) trsm corners on slabs (the r4 contract
    allowed upper only with trans=N)."""
    dist = Dist(P=2, Q=4, kp=1, kq=2)
    mb, MT = 8, 4
    N, nrhs = MT * mb, 24
    rng = np.random.default_rng(15)
    T = rng.standard_normal((N, N)) + N * np.eye(N)
    B = rng.standard_normal((N, nrhs))
    Tt = TileMatrix.from_dense(jnp.asarray(T), mb, mb, dist)
    Bt = TileMatrix.from_dense(jnp.asarray(B), mb, mb, dist)
    m = mesh.make_mesh(dist.P, dist.Q)
    with mesh.use_grid(m):
        Tc = cyclic.CyclicMatrix.from_tile(Tt, dist)
        Bc = cyclic.CyclicMatrix.from_tile(Bt, dist)
        for uplo in ("L", "U"):
            Tm = np.tril(T) if uplo == "L" else np.triu(T)
            for trans in ("N", "C"):
                op = Tm if trans == "N" else Tm.T
                Xc = cyclic.trsm_cyclic(Tc, Bc, trans, uplo=uplo)
                X = np.asarray(Xc.to_tile().data)[:N, :nrhs]
                np.testing.assert_allclose(X, np.linalg.solve(op, B),
                                           rtol=1e-8, atol=1e-8)


def test_trsm_cyclic_complex_T_vs_C(devices8):
    """Complex plain-transpose vs conjugate-transpose must both be
    right: the partial-sum coupling blocks follow the solve's op
    (review r5 — a mixed conj/no-conj gave silently wrong T)."""
    dist = Dist(P=2, Q=4)
    mb, MT = 8, 3
    N, nrhs = MT * mb, 8
    rng = np.random.default_rng(16)
    T = (rng.standard_normal((N, N)) + 1j * rng.standard_normal((N, N))
         + 2 * N * np.eye(N))
    B = rng.standard_normal((N, nrhs)) + 1j * rng.standard_normal(
        (N, nrhs))
    Tt = TileMatrix.from_dense(jnp.asarray(np.triu(T)), mb, mb, dist)
    Bt = TileMatrix.from_dense(jnp.asarray(B), mb, mb, dist)
    m = mesh.make_mesh(dist.P, dist.Q)
    with mesh.use_grid(m):
        Tc = cyclic.CyclicMatrix.from_tile(Tt, dist)
        Bc = cyclic.CyclicMatrix.from_tile(Bt, dist)
        for trans, op in (("T", np.triu(T).T),
                          ("C", np.triu(T).conj().T)):
            Xc = cyclic.trsm_cyclic(Tc, Bc, trans, uplo="U")
            X = np.asarray(Xc.to_tile().data)[:N, :nrhs]
            np.testing.assert_allclose(X, np.linalg.solve(op, B),
                                       rtol=1e-9, atol=1e-9)
