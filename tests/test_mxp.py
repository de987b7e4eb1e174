"""HPL-MxP on a 2x2 grid of virtual CPU devices: the block-cyclic LU
without pivoting (f32 slabs, optional bf16 trailing updates), the
GMRES-IR solve ``refine.gesv_mxp`` and its driver, held to the plain
NumPy float64 reference in ``tests/mxp_reference.py``."""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dplasma_tpu.descriptors import Dist, TileMatrix
from dplasma_tpu.ops import generators, lu, refine
from dplasma_tpu.parallel import cyclic, mesh
from tests import mxp_reference as ref

N, NB = 256, 64
EPS32 = float(np.finfo(np.float32).eps)
EPS_BF16 = 2.0 ** -8


@pytest.fixture(scope="module")
def grid():
    return mesh.make_mesh(2, 2, jax.devices()[:4])


def _heavy_diagonal(n, dtype=jnp.float32):
    """U(-0.5, 0.5) entries with n/16 added to the diagonal: at this
    size the LU needs no pivot (|L| <= 1, U within 5% of its
    diagonal), and the trailing updates are large enough next to the
    entries that rounding their operands to bf16 shows in the factor
    (with the bump N of the deployment's matrix they are not)."""
    A = generators.plrnt(n, n, NB, NB, seed=11, dtype=dtype)
    return A.like(A.data + jnp.eye(n, dtype=dtype) * (n / 16))


def _factor(A, grid, update_dtype=None):
    with mesh.use_grid(grid):
        C = cyclic.CyclicMatrix.from_tile(A, Dist(P=2, Q=2))
        F, perm = cyclic.getrf_cyclic(C, pivot=False,
                                      update_dtype=update_dtype)
        packed = np.asarray(F.to_tile().data, np.float64)
    return packed, np.asarray(perm)


def _unpack(packed):
    return (np.tril(packed, -1) + np.eye(packed.shape[0]),
            np.triu(packed))


def test_nopiv_factor_matches_reference(grid):
    """(a) Without bf16 updates the grid factor is the reference's L
    and U to f32 rounding: each entry of U within 4 N f32 eps of
    max|U| (an f32 LU's normwise error bound, eps times the depth of
    the accumulations), each of L within as much of max|L| = O(1/N)
    times N; the elimination order is the identity."""
    A = _heavy_diagonal(N)
    packed, perm = _factor(A, grid)
    lo, up = _unpack(packed)
    rlo, rup = ref.lu_nopiv(np.asarray(A.to_dense()))
    assert (perm == np.arange(N)).all()
    assert np.abs(up - rup).max() <= 4 * N * EPS32 * np.abs(rup).max()
    assert np.abs(lo - rlo).max() <= 4 * N * EPS32 * np.abs(rlo).max()


def test_bf16_updates_lie_between_bf16_and_f32(grid):
    """(b) With bf16 trailing updates, ``||A - L U|| / ||A||`` lies
    above ten times the f32 factor's (so the one-pass bf16 products
    really ran: their operands carry 2^-8 relative error) and below the
    bf16 unit roundoff 2^-8 (rounding the operands, not the sums: the
    accumulation stays f32 and the updates are half of each entry)."""
    A = _heavy_diagonal(N)
    a = np.asarray(A.to_dense(), np.float64)

    def err(update_dtype):
        lo, up = _unpack(_factor(A, grid, update_dtype)[0])
        return np.abs(a - lo @ up).max() / np.abs(a).max()

    e32, e16 = err(None), err(jnp.bfloat16)
    assert 10 * e32 < e16 < EPS_BF16, (e32, e16)


@pytest.mark.parametrize("n", [128, 256])
def test_nopiv_tile_kernel_matches_reference(n):
    """The Pallas tile LU (the TPU's diagonal-block factor; interpret
    mode here) is the reference's L and U to f32 rounding, as (a)."""
    from dplasma_tpu.kernels import pallas_lu
    a = np.asarray(_heavy_diagonal(n).to_dense())
    lo, up = _unpack(np.asarray(pallas_lu.lu_nopiv_tile(a, interpret=True),
                                np.float64))
    rlo, rup = ref.lu_nopiv(a)
    assert np.abs(up - rup).max() <= 4 * n * EPS32 * np.abs(rup).max()
    assert np.abs(lo - rlo).max() <= 4 * n * EPS32 * np.abs(rlo).max()


@pytest.mark.parametrize("on_grid", [False, True])
def test_limb_matrix_matvec_is_f64_exact(grid, on_grid):
    """``dd.LimbMatrix``: A split once, ``A x`` for two columns within 8
    f64 eps of ||A|| ||x|| of the exactly rounded sums (math.fsum),
    also in a diagonally dominant row whose diagonal is N times its
    other entries (a whole-row scale would drop log2 N bits of them);
    the same on the 2x2 grid, the answer replicated; a NaN in a column
    of x makes that column NaN."""
    import contextlib
    import math
    from dplasma_tpu.kernels import dd
    n = 512
    rng = np.random.default_rng(3)
    a = rng.uniform(-0.5, 0.5, (n, n)) + n * np.eye(n)
    x = rng.standard_normal((n, 2))
    exact = np.array([[math.fsum(a[i] * x[:, c]) for c in range(2)]
                      for i in range(n)])
    ctx = mesh.use_grid(grid) if on_grid else contextlib.nullcontext()
    with ctx:
        f = jax.jit(lambda a_, x_: dd.LimbMatrix(a_).matmul(x_))
        y = f(jnp.asarray(a), jnp.asarray(x))
        x[5, 1] = np.nan
        ynan = np.asarray(f(jnp.asarray(a), jnp.asarray(x)))
    err = np.abs(np.asarray(y) - exact).max() / (
        np.abs(a).sum(axis=1).max() * np.abs(x[:, 0]).max())
    assert err <= 8 * np.finfo(np.float64).eps, err
    assert np.isfinite(ynan[:, 0]).all() and np.isnan(ynan[:, 1]).all()


def _deployment(n, seed=7):
    A = generators.plghe(float(n), n, NB, seed=seed, dtype=jnp.float64)
    B = generators.plrnt(n, 1, NB, 1, seed=seed + 1, dtype=jnp.float64)
    return A, B


def test_gesv_mxp_reaches_f64_and_passes_hpl(grid):
    """(c) On the deployment's matrix (plghe, bump N) the answer agrees
    with LAPACK's float64 solve to 1e-12 in the relative forward error
    (condition number about 1, so the f64 answer's own error is ~1e-16)
    and passes HPL's scaled-residual test (< 16); the f32 solve from
    the same factor, not refined, fails both."""
    A, B = _deployment(N)
    with mesh.use_grid(grid):
        X, info = jax.jit(lambda a, b: refine.gesv_mxp(
            A.like(a), B.like(b)))(A.data, B.data)

        def f32_solve(a, b):
            F = lu.getrf_nopiv(A.like(a.astype(jnp.float32)),
                               update_dtype=jnp.bfloat16)
            return lu.getrs("N", F, lu.nopiv_perm(F),
                            B.like(b.astype(jnp.float32))).data
        x32 = jax.jit(f32_solve)(A.data, B.data)
    a, b = np.asarray(A.to_dense()), np.asarray(B.to_dense())
    xs = ref.solve(a, b)

    def fwd(x):
        x = np.asarray(x, np.float64)[:N]
        return np.abs(x - xs).max() / np.abs(xs).max()

    assert bool(info["converged"]) and int(info["krylov_steps"]) > 0
    assert fwd(X.to_dense()) <= 1e-12
    assert ref.hpl_scaled_residual(a, np.asarray(X.to_dense()), b) < 16
    assert fwd(x32) > 1e-12
    assert ref.hpl_scaled_residual(a, np.asarray(x32, np.float64)[:N],
                                   b) >= 16


#: sha256 (first 16 hex digits) of the pivoted sweep's lowered program
#: (StableHLO text without locations) at the shapes below, lookahead 0
#: and 1, 64-bit types on as the suite runs, as it was before the sweep
#: took ``pivot`` and ``update_dtype``: the default keeps that program
PIVOTED_PROGRAM = {0: "a0133127307a2f6d", 1: "a50213280fce0de4"}


def test_pivoted_sweep_is_unchanged(grid):
    """(d) ``pivot=True`` with ``update_dtype=None`` lowers to the
    pivoted sweep's program as it was, so its factor is today's bit for
    bit; the explicit arguments and the defaults give the same bits."""
    A = generators.plrnt(128, 128, 32, 32, seed=5, dtype=jnp.float32)
    with mesh.use_grid(grid):
        C = cyclic.CyclicMatrix.from_tile(A, Dist(P=2, Q=2))
        for la, digest in PIVOTED_PROGRAM.items():
            low = cyclic._getrf_cyclic_jit.lower(C.data, C.desc, grid, la,
                                                 "chain", False, 0)
            text = low.as_text(debug_info=False)
            assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
        F0, p0 = cyclic.getrf_cyclic(C)
        F1, p1 = cyclic.getrf_cyclic(C, pivot=True, update_dtype=None)
    assert np.array_equal(np.asarray(F0.data), np.asarray(F1.data))
    assert np.array_equal(np.asarray(p0), np.asarray(p1))


def test_driver_gesv_mxp_grid(tmp_path, capsys):
    """(e) ``testing_dgesv_mxp -p 2 -q 2`` writes a report whose refine
    record converged with Krylov steps, exports them as
    ``refine_krylov_steps`` and solves on the cyclic slabs."""
    from dplasma_tpu.drivers import main
    rj = str(tmp_path / "r.json")
    rc = main(["-N", str(N), "-t", str(NB), "-p", "2", "-q", "2", "-x",
               f"--report={rj}"], prog="testing_dgesv_mxp")
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "[SUCCESS] GESV_MXP backward error" in out
    doc = json.load(open(rj))
    (rec,) = doc["refine"]
    assert rec["converged"] and rec["krylov_steps"] > 0
    got = {m["name"]: m for m in doc["metrics"]}
    assert got["refine_krylov_steps"]["value"] == rec["krylov_steps"]
    routes = {m["labels"]["route"]: m["value"] for m in doc["metrics"]
              if m["name"] == "lu_getrs_route_total"}
    assert routes.get("cyclic", 0) >= 1
