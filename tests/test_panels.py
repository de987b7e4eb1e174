"""Panel-factorization engine (kernels/panels.py, MCA panel.kernel).

Covers the engine's selection contract (chain bit-identical, auto
per-backend, pallas fallback), the TSQR tree QR panel and blocked-
recursive LU panel against the pre-engine routes across dtypes and
grids, the panel building blocks' edge cases (zero/tiny-norm columns,
sign handling, rank-deficient panels, tied pivot magnitudes), the
tree-panel DAG structure, and the roofline panel pricing.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import mca_overrides
from dplasma_tpu.descriptors import Dist, TileMatrix
from dplasma_tpu.kernels import householder as hh
from dplasma_tpu.kernels import panels
from dplasma_tpu.ops import generators, lu, qr


mca = mca_overrides


def _qr_resid(a, packed, v, t):
    m, n = a.shape
    Q = hh.apply_q(v, t, jnp.eye(m, dtype=a.dtype), trans="N")
    R = jnp.triu(packed[:n])
    resid = np.abs(np.asarray(Q[:, :n] @ R) - np.asarray(a)).max()
    orth = np.abs(np.asarray(Q.T.conj() @ Q) - np.eye(m)).max()
    return resid, orth


def _lu_resid(a, packed, perm=None):
    m, n = a.shape
    L = np.tril(np.asarray(packed), -1)[:, :n]
    L[:n] += np.eye(n, dtype=L.dtype)
    U = np.triu(np.asarray(packed)[:n])
    ref = np.asarray(a)
    if perm is not None:
        ref = ref[np.asarray(perm)]
    return np.abs(ref - L @ U).max()


# ------------------------------------------------- kernel resolution

def test_panel_kernel_resolution():
    # auto on CPU resolves to chain on every route
    with mca({"panel.kernel": "auto"}):
        for route in ("qr", "lu", "nopiv"):
            assert panels.panel_kernel(route) == "chain"
    # explicit values stick; cross-family names map to the route's own
    with mca({"panel.kernel": "tree"}):
        assert panels.panel_kernel("qr") == "tree"
        assert panels.panel_kernel("lu") == "rec"
        assert panels.panel_kernel("nopiv") == "rec"
    with mca({"panel.kernel": "rec"}):
        assert panels.panel_kernel("qr") == "tree"
        assert panels.panel_kernel("lu") == "rec"
    # nopiv has no fused pallas kernel: always the rec fallback
    with mca({"panel.kernel": "pallas"}):
        assert panels.panel_kernel("nopiv") == "rec"
    # garbage falls back to auto
    with mca({"panel.kernel": "bogus"}):
        assert panels.panel_kernel("lu") == "chain"


def test_panel_kernel_pallas_explicit():
    """panel.kernel=pallas selects the fused kernels of the two routes
    that have one, on any backend (interpret mode off the chip)."""
    with mca({"panel.kernel": "pallas"}):
        assert panels.panel_kernel("qr") == "pallas"
        assert panels.panel_kernel("lu") == "pallas"


# ------------------------------------------------------- TSQR tree

@pytest.mark.parametrize("m,n", [(96, 16), (100, 16), (33, 16),
                                 (16, 16), (256, 32)])
def test_tsqr_thin_qr(m, n, rng):
    a = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    q, r = panels.tsqr(a)
    assert q.shape == (m, n) and r.shape == (n, n)
    tol = 50 * np.finfo(np.float32).eps * max(m, n)
    assert np.abs(np.asarray(q @ r) - np.asarray(a)).max() <= \
        tol * np.abs(np.asarray(a)).max()
    assert np.abs(np.asarray(q.T @ q) - np.eye(n)).max() <= tol


def test_geqrt_tree_contract(rng):
    """(packed, V, T) from the tree panel obeys the geqrt contract:
    V unit lower-trapezoidal, T upper-triangular, H[S R;0] = A."""
    a = jnp.asarray(rng.standard_normal((96, 16)), jnp.float32)
    packed, v, t = panels.geqrt_tree(a)
    vd = np.asarray(v)
    assert np.allclose(np.diag(vd[:16]), 1.0)
    assert np.abs(np.triu(vd[:16], 1)).max() == 0.0
    assert np.abs(np.tril(np.asarray(t), -1)).max() == 0.0
    resid, orth = _qr_resid(a, packed, v, t)
    assert resid < 1e-4 and orth < 1e-5


def test_geqrt_tree_leaf_knob(rng):
    a = jnp.asarray(rng.standard_normal((128, 16)), jnp.float32)
    for leaf in ("1", "4"):
        with mca({"panel.tree_leaf": leaf}):
            resid, orth = _qr_resid(a, *panels.geqrt_tree(a))
            assert resid < 1e-4 and orth < 1e-5, leaf


# ------------------------------- building-block edge cases (issue #9)

def test_tree_zero_column_panel(rng):
    """A panel with an exactly-zero column (no row padding needed):
    leaf QRs complete the basis, the tree Q stays orthonormal, and the
    reconstruction reproduces the zero column in R."""
    a = np.asarray(rng.standard_normal((64, 16)), np.float32)
    a[:, 7] = 0.0
    packed, v, t = panels.geqrt_tree(jnp.asarray(a))
    resid, orth = _qr_resid(jnp.asarray(a), packed, v, t)
    assert resid < 1e-4 and orth < 1e-5
    assert np.isfinite(np.asarray(packed)).all()


def test_tree_tiny_norm_columns(rng):
    """Tiny-norm columns (1e-18 scale) must not overflow/flush the
    tree or the reconstruction's unpivoted LU."""
    a = np.asarray(rng.standard_normal((64, 16)), np.float32)
    a[:, 3] *= 1e-18
    a[:, 11] *= 1e-12
    packed, v, t = panels.geqrt_tree(jnp.asarray(a))
    resid, orth = _qr_resid(jnp.asarray(a), packed, v, t)
    assert orth < 1e-5
    assert resid < 1e-4 * max(1.0, np.abs(a).max())


def test_tree_rank_deficient_panel(rng):
    """Rank-deficient panel, block-aligned height (no zero-row
    padding): the leaf/stacked QRs keep Q orthonormal regardless of
    rank, and TSQR-HR's unpivoted LU of Q1 - S is provably stable for
    ANY orthonormal Q (Ballard et al.) — unlike CholeskyQR2, whose
    Gram breaks down (this is the tree's stability edge)."""
    base = np.asarray(rng.standard_normal((64, 8)), np.float32)
    a = np.concatenate([base, base @ np.asarray(
        rng.standard_normal((8, 8)), np.float32)], axis=1)  # rank 8
    packed, v, t = panels.geqrt_tree(jnp.asarray(a))
    resid, orth = _qr_resid(jnp.asarray(a), packed, v, t)
    assert orth < 1e-4
    assert resid < 1e-3 * np.abs(a).max()


def test_reconstruct_sign_vector_handling(rng):
    """reconstruct_sign_shift: s = -sign(diag Q1) with the zero-diag
    tie broken to +1 (so s = -1 there), and householder_reconstruct
    reproduces Q = H [S; 0] for mixed-sign diagonals."""
    q_np = np.linalg.qr(rng.standard_normal((32, 8)))[0].astype(
        np.float32)
    q_np[:, 2] *= -1.0            # force a negative diagonal entry
    q = jnp.asarray(q_np)
    s, b = hh.reconstruct_sign_shift(q)
    sd = np.asarray(s)
    assert np.allclose(np.abs(sd), 1.0)
    assert np.allclose(sd, -np.sign(np.where(
        np.diag(q_np[:8]) == 0, 1.0, np.diag(q_np[:8]))))
    r = jnp.eye(8, dtype=jnp.float32)   # any R works for the identity
    packed, v, t = hh.householder_reconstruct(q, r)
    # H [S; 0] = Q  =>  applying H to [S; 0] recovers Q
    s0 = jnp.concatenate([jnp.diag(s), jnp.zeros((24, 8), q.dtype)])
    qrec = hh.apply_q(v, t, s0, trans="N")
    assert np.abs(np.asarray(qrec) - q_np).max() < 1e-5
    # the zero-diagonal branch of the sign helper itself
    z = hh._unimodular_sign(jnp.asarray([0.0, -2.0, 3.0]))
    assert np.allclose(np.asarray(z), [1.0, -1.0, 1.0])


def test_cholqr2_tiny_norm_panel(rng):
    """cholqr2's shifted first pass must survive a panel whose columns
    differ by ~1e6 in scale (the shift bounds the Gram's breakdown)."""
    a = np.asarray(rng.standard_normal((64, 8)), np.float32)
    a[:, 5] *= 1e-6
    q, r = hh.cholqr2(jnp.asarray(a))
    tol = 1e-4
    assert np.abs(np.asarray(q @ r) - a).max() <= tol * np.abs(a).max()
    assert np.abs(np.asarray(q.T @ q) - np.eye(8)).max() <= tol


def test_lu_rec_tied_pivot_magnitudes():
    """Tied/duplicate pivot magnitudes: the rec panel's masked argmax
    must elect the LOWEST row index — exact perm equality with the
    vendor column-loop panel on integer-valued (exactly representable)
    panels full of ties."""
    rng = np.random.default_rng(11)
    for trial in range(2):
        a = rng.integers(-3, 4, (48, 16)).astype(np.float32)
        with mca({"panel.kernel": "chain"}):
            _, p0 = lu._base_lu(jnp.asarray(a))
        pk, p1 = panels.lu_panel_rec(jnp.asarray(a))
        assert np.array_equal(np.asarray(p0), np.asarray(p1)), trial
        assert _lu_resid(jnp.asarray(a), pk, p1) < 1e-4


def test_lu_rec_zero_column():
    """An all-zero pivot column: degrades like the chain (zero L
    column, no NaNs) and keeps electing lowest-index rows."""
    rng = np.random.default_rng(12)
    a = rng.standard_normal((32, 8)).astype(np.float32)
    a[:, 4] = 0.0
    pk, perm = panels.lu_panel_rec(jnp.asarray(a))
    assert np.isfinite(np.asarray(pk)).all()


@pytest.mark.parametrize("m,n", [(64, 16), (40, 8)])
def test_lu_rec_matches_vendor(m, n, rng):
    a = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    pk, perm = panels.lu_panel_rec(a)
    with mca({"panel.kernel": "chain"}):
        _, p0 = lu._base_lu(a)
    assert np.array_equal(np.asarray(perm), np.asarray(p0))
    assert _lu_resid(a, pk, perm) < 1e-4


def test_lu_rec_nopiv_contract(rng):
    a = jnp.asarray(rng.standard_normal((48, 16))
                    + 6 * np.eye(48)[:, :16], jnp.float32)
    pk = panels.lu_panel_rec_nopiv(a)
    assert _lu_resid(a, pk) < 1e-4


# ------------------------------------------- sweep route equivalence

def test_getrf_chain_bit_identical():
    """panel.kernel=chain IS today's route, bit-identical to the
    auto default on this (CPU) backend."""
    A = generators.plrnt(64, 64, 16, 16, seed=2, dtype=jnp.float32)
    with mca({"panel.kernel": "chain"}):
        Fc, pc = lu.getrf_1d(A)
    with mca({}):
        Fd, pd = lu.getrf_1d(A)
    assert np.array_equal(np.asarray(Fc.data), np.asarray(Fd.data))
    assert np.array_equal(np.asarray(pc), np.asarray(pd))


@pytest.mark.parametrize("kind", ["rec", "pallas"])
def test_getrf_1d_engine_kernels(kind):
    A = generators.plrnt(48, 48, 16, 16, seed=3, dtype=jnp.float32)
    a = np.asarray(A.to_dense())
    with mca({"panel.kernel": "chain"}):
        _, pc = lu.getrf_1d(A)
    with mca({"panel.kernel": kind}):
        F, p = lu.getrf_1d(A)
    L = np.tril(np.asarray(F.to_dense()), -1) + np.eye(48)
    U = np.triu(np.asarray(F.to_dense()))
    tol = 100 * np.finfo(np.float32).eps * 48
    assert np.abs(a[np.asarray(p)] - L @ U).max() <= \
        tol * np.abs(a).max()
    assert np.array_equal(np.asarray(p), np.asarray(pc))


def test_getrf_nopiv_rec_equivalent():
    A = generators.plghe(64.0, 64, 16, seed=1, dtype=jnp.float32)
    with mca({"panel.kernel": "chain"}):
        b0 = np.asarray(lu.getrf_nopiv(A).to_dense())
    with mca({"panel.kernel": "rec"}):
        b1 = np.asarray(lu.getrf_nopiv(A).to_dense())
    assert np.abs(b1 - b0).max() <= 200 * np.finfo(np.float32).eps \
        * np.abs(b0).max()


@pytest.mark.parametrize("kind", ["tree", "pallas"])
def test_geqrf_engine_kernels(kind):
    M = N = 64
    A = generators.plrnt(M, N, 16, 16, seed=4, dtype=jnp.float32)
    with mca({"panel.kernel": kind}):
        Af, Tf = qr.geqrf(A)
        Q = qr.ungqr(Af, Tf).to_dense()
    R = jnp.triu(Af.to_dense()[:N])
    a = np.asarray(A.to_dense())
    tol = 100 * np.finfo(np.float32).eps * N
    assert np.abs(np.asarray(Q @ R) - a).max() <= tol * np.abs(a).max()
    assert np.abs(np.asarray(Q.T @ Q) - np.eye(M)).max() <= tol


def test_geqrf_tree_rectangular():
    """Tall and wide shapes through the tree panel (edge tiles are
    identity-padded by geqrf — the tree's full-rank envelope)."""
    for M, N in ((96, 48), (48, 64)):
        A = generators.plrnt(M, N, 16, 16, seed=5, dtype=jnp.float32)
        with mca({"panel.kernel": "tree"}):
            Af, Tf = qr.geqrf(A)
            Q = qr.ungqr(Af, Tf).to_dense()
        K = min(M, N)
        R = jnp.triu(Af.to_dense()[:K, :N])
        a = np.asarray(A.to_dense())
        tol = 200 * np.finfo(np.float32).eps * max(M, N)
        assert np.abs(np.asarray(Q @ R) - a).max() <= \
            tol * max(1.0, np.abs(a).max()), (M, N)


@pytest.mark.parametrize("kind,op", [("tree", "qr"), ("rec", "lu")])
def test_dd_f64_engine_kernels(kind, op):
    """The dd-f64 routes under the engine kernels: f64-equivalent
    residuals (the tree panel's f32-TSQR seed + limb IR pass, the rec
    panel seeding the f32 stage of _panel_lu_dd)."""
    N = 32 if op == "qr" else 48
    A = generators.plrnt(N, N, 16, 16, seed=6, dtype=jnp.float64)
    a = np.asarray(A.to_dense())
    tol = 500 * np.finfo(np.float64).eps * N
    with mca({"panel.kernel": kind, "dd_gemm": "always"}):
        if op == "qr":
            Af, Tf = qr.geqrf(A)
            Q = qr.ungqr(Af, Tf).to_dense()
            R = jnp.triu(Af.to_dense()[:N])
            assert np.abs(np.asarray(Q @ R) - a).max() <= \
                tol * np.abs(a).max()
        else:
            F, p = lu.getrf_1d(A)
            fd = np.asarray(F.to_dense())
            L = np.tril(fd, -1) + np.eye(N)
            U = np.triu(fd)
            assert np.abs(a[np.asarray(p)] - L @ U).max() <= \
                tol * np.abs(a).max()


def test_eager_jit_cache_not_stale():
    """The jitted eager callbacks thread the panel kernel as a STATIC
    arg: flipping MCA panel.kernel between same-shape calls must
    re-route, not replay the cached kernel choice."""
    rng = np.random.default_rng(13)
    a = jnp.asarray(rng.standard_normal((48, 16)), jnp.float32)
    with mca({"panel.kernel": "chain"}):
        p0 = lu._jit_lu_panel(a, panels.panel_kernel("lu"))[0]
    with mca({"panel.kernel": "rec"}):
        p1 = lu._jit_lu_panel(a, panels.panel_kernel("lu"))[0]
    # same math, different op order: allclose but not (necessarily)
    # the same executable — the static key difference is what's tested
    assert np.allclose(np.asarray(p0), np.asarray(p1), atol=1e-4)


# ------------------------------------------------------- cyclic grid

def test_cyclic_getrf_rec_panel(devices8):
    from dplasma_tpu.parallel import cyclic
    from dplasma_tpu.parallel import mesh as pmesh
    A = generators.plrnt(32, 32, 16, 16, seed=7, dtype=jnp.float32)
    a = np.asarray(A.to_dense())
    d = Dist(P=2, Q=2)
    m = pmesh.make_mesh(2, 2)
    with pmesh.use_grid(m):
        with mca({"panel.kernel": "chain"}):
            F0, p0 = cyclic.getrf_cyclic(
                cyclic.CyclicMatrix.from_tile(A, d))
        with mca({"panel.kernel": "rec"}):
            F1, p1 = cyclic.getrf_cyclic(
                cyclic.CyclicMatrix.from_tile(A, d))
        assert np.array_equal(np.asarray(p0), np.asarray(p1))
        fd = np.asarray(F1.to_tile().data)[np.asarray(p1)][:32, :32]
    L = np.tril(fd, -1) + np.eye(32)
    U = np.triu(fd)
    tol = 100 * np.finfo(np.float32).eps * 32
    assert np.abs(a[np.asarray(p1)][:32, :32] - L @ U).max() <= \
        tol * np.abs(a).max()


# ----------------------------------------------------- DAG structure

def test_tree_panel_dag_structure():
    from dplasma_tpu.analysis.dagcheck import check_dag, rank_of_dist
    from dplasma_tpu.utils.profiling import DagRecorder
    nb, nt = 4, 5
    for dist in (Dist(), Dist(P=2, Q=2)):
        A = TileMatrix.zeros(nt * nb, nt * nb, nb, nb, dist=dist)
        rec = DagRecorder(enabled=True)
        qr.dag(A, rec, lookahead=1, agg_depth=2, panel_kernel="tree")
        res = check_dag(rec, rank_of=rank_of_dist(dist))
        assert res.ok, res.format("tree")
        classes = {}
        for t in rec.tasks:
            classes[t.cls] = classes.get(t.cls, 0) + 1
        # column k has nt-k leaves (k < nt-1 expands; the last single-
        # tile column stays a flat panel task)
        assert classes["panel_leaf"] == sum(
            nt - k for k in range(nt - 1))
        assert classes["panel_comb"] == sum(
            (nt - k) - 1 for k in range(nt - 1))
        assert classes["panel"] == nt
        assert rec.meta["pipeline"]["panel.kernel"] == "tree"


def test_tree_panel_dag_follows_mca():
    """With no explicit panel_kernel the DAG builder resolves the live
    MCA config — the recorded DAG is what the sweep will run."""
    from dplasma_tpu.utils.profiling import DagRecorder
    A = TileMatrix.zeros(16, 16, 4, 4, dist=Dist())
    with mca({"panel.kernel": "tree"}):
        rec = DagRecorder(enabled=True)
        qr.dag(A, rec, lookahead=1)
        assert any(t.cls == "panel_leaf" for t in rec.tasks)
    with mca({"panel.kernel": "chain"}):
        rec = DagRecorder(enabled=True)
        qr.dag(A, rec, lookahead=1)
        assert not any(t.cls == "panel_leaf" for t in rec.tasks)


# ------------------------------------------------- roofline pricing

def test_phase_model_prices_tree_panel():
    from dplasma_tpu.observability import roofline
    kw = dict(M=256, N=256, nb=32, itemsize=4, lookahead=1,
              agg_depth=2)
    chain = roofline.phase_model("geqrf", **kw, panel_kernel="chain")
    tree = roofline.phase_model("geqrf", **kw, panel_kernel="tree")
    assert tree["panel"][0] == pytest.approx(3.0 * chain["panel"][0])
    assert tree["panel"][2] == chain["panel"][2]
    # non-panel phases identical; rec LU prices like chain (same math)
    assert tree["far_flush"] == chain["far_flush"]
    lu_c = roofline.phase_model("getrf", **kw, panel_kernel="chain")
    lu_r = roofline.phase_model("getrf", **kw, panel_kernel="rec")
    assert lu_c == lu_r
    # None resolves from the live MCA config
    with mca({"panel.kernel": "tree"}):
        auto = roofline.phase_model("geqrf", **kw)
    assert auto["panel"] == tree["panel"]


# ------------------------------------------------ pallas panel (qr)

def test_pallas_geqrt_panel_matches_vendor(rng):
    from dplasma_tpu.kernels import pallas_qr
    for m, n in ((48, 16), (64, 8), (32, 32)):
        a = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
        packed, v, t = pallas_qr.geqrt_panel(a)
        resid, orth = _qr_resid(a, packed, v, t)
        assert resid < 1e-4 and orth < 1e-5, (m, n)
        # R agrees with the vendor panel's in magnitude (per-row
        # reflector signs are not stable to roundoff: a near-zero
        # alpha flips beta's sign between implementations)
        R = np.triu(np.asarray(packed)[:n])
        R0 = np.triu(np.asarray(hh.geqrt(a)[0])[:n])
        assert np.abs(np.abs(R) - np.abs(R0)).max() < 1e-4 * max(
            1.0, np.abs(R0).max()), (m, n)


def test_pallas_geqrt_zero_column(rng):
    from dplasma_tpu.kernels import pallas_qr
    a = np.asarray(rng.standard_normal((32, 8)), np.float32)
    a[:, 3] = 0.0
    packed, v, t = pallas_qr.geqrt_panel(jnp.asarray(a))
    resid, _ = _qr_resid(jnp.asarray(a), packed, v, t)
    assert resid < 1e-4
    assert np.isfinite(np.asarray(packed)).all()


def test_pallas_qr_eligibility_gate(rng):
    from dplasma_tpu.kernels import pallas_qr
    ok = jnp.zeros((64, 16), jnp.float32)
    assert pallas_qr.eligible(ok)
    assert not pallas_qr.eligible(jnp.zeros((64, 10), jnp.float32))
    assert not pallas_qr.eligible(jnp.zeros((64, 16), jnp.float64))
    assert not pallas_qr.eligible(
        jnp.zeros((1 << 18, 16), jnp.float32))
