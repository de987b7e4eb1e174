"""Lookahead-pipelined factorization sweeps (ops._sweep engine,
CLI --lookahead / MCA sweep.lookahead + qr.agg_depth).

Numerical-equivalence fixtures: pipelining is a SCHEDULE change, so
lookahead on/off and every aggregation depth must produce the same
factors — bit-exact where the op order is unchanged (the column-split
applies are the same reductions), check_*-tolerance otherwise (the
compact-WY block-T aggregation and the potrf wide-vs-skinny
accumulation reassociate sums) — for potrf/getrf/geqrf across f32 and
the dd-f64 route, on one device and the 2x2 cyclic grid.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import mca_overrides
from dplasma_tpu.descriptors import Dist, TileMatrix
from dplasma_tpu.ops import checks, generators, lu, potrf as potrf_mod
from dplasma_tpu.ops import qr
from dplasma_tpu.utils import config


mca = mca_overrides


def _tol(dtype):
    return 200 * float(jnp.finfo(dtype).eps)


# ------------------------------------------------------- single device

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("la", [1, 2, 3])
def test_getrf_nopiv_lookahead_equivalent(dtype, la):
    A = generators.plghe(96.0, 96, 16, seed=1, dtype=dtype)
    with mca({"sweep.lookahead": "0"}):
        base = np.asarray(lu.getrf_nopiv(A).to_dense())
    with mca({"sweep.lookahead": str(la)}):
        out = np.asarray(lu.getrf_nopiv(A).to_dense())
    assert np.abs(out - base).max() <= _tol(dtype) * np.abs(base).max()


@pytest.mark.parametrize("la", [1, 2])
def test_getrf_1d_lookahead_equivalent(la):
    A = generators.plrnt(96, 96, 16, 16, seed=2, dtype=jnp.float32)
    with mca({"sweep.lookahead": "0"}):
        F0, p0 = lu.getrf_1d(A)
    with mca({"sweep.lookahead": str(la)}):
        F1, p1 = lu.getrf_1d(A)
    # identical panel inputs => identical pivot choices; the factors
    # agree to op-order tolerance (bit-exact on a deterministic
    # backend: the column split keeps every reduction's shape)
    assert (np.asarray(p0) == np.asarray(p1)).all()
    d0, d1 = np.asarray(F0.to_dense()), np.asarray(F1.to_dense())
    assert np.abs(d1 - d0).max() <= _tol(jnp.float32) * np.abs(d0).max()


@pytest.mark.parametrize("la,agg", [(0, 2), (0, 4), (1, 1), (1, 2),
                                    (2, 4)])
def test_geqrf_lookahead_agg_equivalent(la, agg):
    M = N = 96
    A = generators.plrnt(M, N, 16, 16, seed=3, dtype=jnp.float32)
    with mca({"sweep.lookahead": "0", "qr.agg_depth": "1"}):
        B0, T0 = qr.geqrf(A)
    with mca({"sweep.lookahead": str(la), "qr.agg_depth": str(agg)}):
        B1, T1 = qr.geqrf(A)
        Q = qr.ungqr(B1, T1).to_dense()
        R = jnp.triu(B1.to_dense()[:N, :])
    tol = _tol(jnp.float32)
    d0 = np.asarray(B0.to_dense())
    assert np.abs(np.asarray(B1.to_dense()) - d0).max() \
        <= tol * np.abs(d0).max()
    assert np.abs(np.asarray(T1.data) - np.asarray(T0.data)).max() \
        <= tol * max(np.abs(np.asarray(T0.data)).max(), 1.0)
    r, ok = checks.check_qr(A, Q, R)
    assert ok, r


@pytest.mark.parametrize("uplo", ["L", "U"])
@pytest.mark.parametrize("la", [1, 2])
def test_potrf_lookahead_equivalent(uplo, la):
    A = generators.plghe(96.0, 96, 16, seed=4, dtype=jnp.float32)
    with mca({"sweep.lookahead": "0"}):
        base = np.asarray(potrf_mod.potrf(A, uplo).to_dense())
    with mca({"sweep.lookahead": str(la)}):
        out = np.asarray(potrf_mod.potrf(A, uplo).to_dense())
    assert np.abs(out - base).max() <= _tol(jnp.float32) \
        * np.abs(base).max()


def test_lookahead_zero_is_bit_exact_baseline():
    """lookahead=0 / agg_depth=1 reproduces the serialized sweep's
    exact op order — bit-identical, not just close."""
    A = generators.plrnt(80, 80, 16, 16, seed=5, dtype=jnp.float64)
    with mca({"sweep.lookahead": "0", "qr.agg_depth": "1"}):
        one = np.asarray(qr.geqrf(A)[0].to_dense())
        two = np.asarray(qr.geqrf(A)[0].to_dense())
    assert (one == two).all()


# ------------------------------------------------------- dd-f64 route

@pytest.mark.parametrize("la,agg", [
    pytest.param(1, 1, marks=pytest.mark.slow),  # (1,2) covers both
    (1, 2)])
def test_geqrf_dd_route_lookahead_equivalent(la, agg):
    """The eager dd-f64 route (per-shape jitted engine callbacks)
    matches its serialized baseline (whose own correctness is pinned
    by test_panels' dd engine tests and the slow tier's
    test_geqrf_f64_under_dd — the dd ungqr walk is too heavy to
    repeat here)."""
    N, nb = 96, 32
    A = generators.plrnt(N, N, nb, nb, seed=6, dtype=jnp.float64)
    with mca({"dd_gemm": "always", "sweep.lookahead": "0",
              "qr.agg_depth": "1"}):
        B0, T0 = qr.geqrf(A)
    with mca({"dd_gemm": "always", "sweep.lookahead": str(la),
              "qr.agg_depth": str(agg)}):
        B1, T1 = qr.geqrf(A)
    d0 = np.asarray(B0.to_dense())
    assert np.abs(np.asarray(B1.to_dense()) - d0).max() \
        <= 1e-12 * np.abs(d0).max()
    t0 = np.asarray(T0.data)
    assert np.abs(np.asarray(T1.data) - t0).max() \
        <= 1e-12 * max(np.abs(t0).max(), 1.0)


def test_getrf_dd_eager_lookahead_and_fused_flush():
    """The eager dd LU route (> 8 panels): lookahead matches the
    serialized baseline (pivots included), and lu.agg_depth's fused
    far flushes match per-step flushes to rounding (pure dispatch
    fusion — same op order, unlike QR's reassociating aggregation).
    One shared 160^2 dd matrix: these factorizations cost ~10s each,
    so the two properties share the la=1 baselines (tier-1 budget)."""
    N, nb = 160, 16
    A = generators.plrnt(N, N, nb, nb, seed=7, dtype=jnp.float64)
    with mca({"dd_gemm": "always", "sweep.lookahead": "0",
              "lu.agg_depth": "1"}):
        F0, p0 = lu.getrf_1d(A)
    with mca({"dd_gemm": "always", "sweep.lookahead": "1",
              "lu.agg_depth": "1"}):
        F1, p1 = lu.getrf_1d(A)
    with mca({"dd_gemm": "always", "sweep.lookahead": "1",
              "lu.agg_depth": "4"}):
        F4, p4 = lu.getrf_1d(A)
    assert (np.asarray(p0) == np.asarray(p1)).all()
    d0 = np.asarray(F0.to_dense())
    assert np.abs(np.asarray(F1.to_dense()) - d0).max() \
        <= 1e-12 * max(np.abs(d0).max(), 1.0)
    # dispatch fusion: same pivots and op order as the per-step la=1
    # result; XLA compiles the fused program on its own terms (it
    # contracts across the fused steps), so agreement is to a few
    # roundings (5e-15 of max|F| measured, PR 21)
    assert (np.asarray(p4) == np.asarray(p1)).all()
    d1 = np.asarray(F1.to_dense())
    assert np.abs(np.asarray(F4.to_dense()) - d1).max() \
        <= 2e-14 * max(np.abs(d1).max(), 1.0)


def test_potrf_dd_route_ignores_lookahead():
    """The dd potrf fast path (kernels.dd.potrf_f64_blocked) replaces
    the sweep wholesale — lookahead on/off is trivially identical."""
    A = generators.plghe(64.0, 64, 16, seed=8, dtype=jnp.float64)
    with mca({"dd_gemm": "always", "sweep.lookahead": "0"}):
        base = np.asarray(potrf_mod.potrf(A, "L").to_dense())
    with mca({"dd_gemm": "always", "sweep.lookahead": "2"}):
        out = np.asarray(potrf_mod.potrf(A, "L").to_dense())
    assert (out == base).all()


# ------------------------------------------------------- 2x2 cyclic

def _with_grid(devices8, fn):
    from dplasma_tpu.parallel import mesh
    m = mesh.make_mesh(2, 2, devices8[:4])
    with mesh.use_grid(m):
        return fn()


def test_potrf_cyclic_lookahead_equivalent(devices8):
    from dplasma_tpu.parallel import cyclic
    dist = Dist(P=2, Q=2)
    N, mb = 40, 8
    A = generators.plghe(float(N), N, mb, seed=9, dtype=jnp.float64)

    def run(la):
        def body():
            C = cyclic.CyclicMatrix.from_tile(A, dist)
            return np.asarray(
                cyclic.potrf_cyclic(C, "L").to_tile().to_dense())
        with mca({"sweep.lookahead": str(la)}):
            return _with_grid(devices8, body)
    L0, L1 = run(0), run(1)
    assert np.abs(np.tril(L1) - np.tril(L0)).max() \
        <= _tol(jnp.float64) * np.abs(L0).max()


def test_getrf_cyclic_lookahead_equivalent(devices8):
    from dplasma_tpu.parallel import cyclic
    dist = Dist(P=2, Q=2)
    N, mb = 37, 8
    A = generators.plrnt(N, N, mb, mb, seed=10, dtype=jnp.float64)
    base = TileMatrix(A.pad_diag().data, A.desc)

    def run(la):
        def body():
            C = cyclic.CyclicMatrix.from_tile(base, dist)
            F, perm = cyclic.getrf_cyclic(C)
            return (np.asarray(F.to_tile().to_dense()),
                    np.asarray(perm))
        with mca({"sweep.lookahead": str(la)}):
            return _with_grid(devices8, body)
    (d0, p0), (d1, p1) = run(0), run(1)
    assert (p0 == p1).all()
    assert np.abs(d1 - d0).max() <= _tol(jnp.float64) \
        * max(np.abs(d0).max(), 1.0)


def test_geqrf_cyclic_lookahead_equivalent(devices8):
    from dplasma_tpu.parallel import cyclic
    dist = Dist(P=2, Q=2, kp=2, kq=2)
    N, mb = 48, 4
    A = generators.plrnt(N, N, mb, mb, seed=11, dtype=jnp.float32)

    def run(la):
        def body():
            C = cyclic.CyclicMatrix.from_tile(A, dist)
            F, Ts = cyclic.geqrf_cyclic(C)
            return (np.asarray(F.to_tile().to_dense()),
                    np.asarray(Ts))
        with mca({"sweep.lookahead": str(la)}):
            return _with_grid(devices8, body)
    (d0, t0), (d1, t1) = run(0), run(1)
    tol = _tol(jnp.float32)
    assert np.abs(d1 - d0).max() <= tol * max(np.abs(d0).max(), 1.0)
    assert np.abs(t1 - t0).max() <= tol * max(np.abs(t0).max(), 1.0)


# -------------------------------------------------- knobs / reporting

def test_parse_arguments_lookahead():
    from dplasma_tpu.drivers import common as dc
    ip = dc.parse_arguments(["-N", "64", "--lookahead", "3"])
    assert ip.lookahead == 3
    ip = dc.parse_arguments(["-N", "64", "--lookahead=0"])
    assert ip.lookahead == 0
    assert dc.parse_arguments(["-N", "64"]).lookahead == -1


def test_driver_lookahead_scoped_mca_override():
    """--lookahead overrides MCA sweep.lookahead for the driver's
    lifetime and restores the prior state at close()."""
    from dplasma_tpu.drivers import common as dc
    from dplasma_tpu.ops._sweep import sweep_params
    assert "sweep.lookahead" not in config._MCA_OVERRIDES
    ip = dc.parse_arguments(["-N", "16", "-t", "8", "--lookahead", "0"])
    drv = dc.Driver(ip, "probe")
    try:
        assert sweep_params()[0] == 0
        assert drv.pipeline["sweep.lookahead"] == 0
        assert drv.report.pipeline["sweep.lookahead"] == 0
    finally:
        drv.close()
    assert "sweep.lookahead" not in config._MCA_OVERRIDES


def test_report_pipeline_section_schema_v6(tmp_path, capsys):
    import json

    from dplasma_tpu.drivers import main
    rj = str(tmp_path / "r.json")
    rc = main(["-N", "64", "-t", "16", f"--report={rj}", "-v=2"],
              prog="testing_dgeqrf")
    out = capsys.readouterr().out
    assert rc == 0
    assert "#+ pipeline: sweep.lookahead=" in out
    doc = json.load(open(rj))
    assert doc["schema"] == 18
    # since v11 the section carries the FULL resolved knob vector
    # (autotuner evidence; --autotune runs add "tuning.source")
    assert set(doc["pipeline"]) == {"sweep.lookahead", "qr.agg_depth",
                                    "lu.agg_depth", "panel.kernel",
                                    "panel.qr", "panel.lu",
                                    "panel.tree_leaf",
                                    "panel.rec_base", "ring.enable"}
    # per-route panel-engine resolution is recorded, never raw "auto"
    assert doc["pipeline"]["panel.qr"] in ("chain", "tree", "pallas")
    assert doc["pipeline"]["panel.lu"] in ("chain", "rec", "pallas")


def test_mca_knobs_registered():
    assert config.mca_get("sweep.lookahead") == "1"
    assert config.mca_get("qr.agg_depth") == "4"
    assert "sweep.lookahead" in config.mca_help()
    assert config.mca_get("panel.kernel") == "auto"
    assert "panel.kernel" in config.mca_help()


# ------------------------------------------------ unmqr split caching

def test_qr_panels_split_cached_per_factor():
    """Repeated applies against one (Af, Tf) pair reuse the V split;
    a factor with different data misses the cache."""
    from dplasma_tpu.ops.qr import _qr_panels
    A = generators.plrnt(64, 64, 16, 16, seed=12, dtype=jnp.float32)
    Af, Tf = qr.geqrf(A)
    p1 = _qr_panels(Af, Tf)
    p2 = _qr_panels(Af, Tf)
    assert p1 is p2
    # replaced data -> fresh split (identity check, not shape check)
    Af2 = TileMatrix(Af.data + 0.0, Af.desc)
    p3 = _qr_panels(Af2, Tf)
    assert p3 is not p1
    # the cached split still drives a correct apply
    C = generators.plrnt(64, 8, 16, 16, seed=13, dtype=jnp.float32)
    out1 = np.asarray(qr.unmqr("L", "C", Af, Tf, C).to_dense())
    out2 = np.asarray(qr.unmqr("L", "C", Af, Tf, C).to_dense())
    assert (out1 == out2).all()
