"""FP64-equivalent GEMM from bf16 limb matmuls (kernels.dd — the
SURVEY §7 "double-double GEMM" hard part). Accuracy is checked in
units of the standard error bound K·eps64·(|A|·|B|), against a
longdouble reference, side by side with numpy's own f64 error."""
import jax.numpy as jnp
import numpy as np
import pytest

from dplasma_tpu.kernels import dd

EPS = np.finfo(np.float64).eps


def _err_units(out, a, b):
    refq = np.asarray(a, np.longdouble) @ np.asarray(b, np.longdouble)
    mag = np.abs(a) @ np.abs(b)
    K = a.shape[1]
    return float(np.max(np.abs(out - refq) / (K * EPS * mag)))


@pytest.mark.parametrize("M,K,N", [
    pytest.param(64, 512, 64, marks=pytest.mark.slow),
    (48, 4096, 32), (33, 100, 57)])
def test_gemm_f64_equivalent(rng, M, K, N):
    # wide dynamic range stresses the per-row/col scaling
    a = rng.standard_normal((M, K)) * np.exp(rng.uniform(-8, 8, (M, 1)))
    b = rng.standard_normal((K, N)) * np.exp(rng.uniform(-8, 8, (1, N)))
    out = np.asarray(dd.gemm_f64(jnp.asarray(a), jnp.asarray(b)))
    e_dd = _err_units(out, a, b)
    e_np = _err_units(a @ b, a, b)
    # within a small factor of native f64's own rounding
    assert e_dd < max(8 * e_np, 0.5), (e_dd, e_np)


def test_dd_wired_into_tile_kernels(rng, monkeypatch):
    """MCA dd_gemm=always routes kernels.blas.dot f64/c128 through the
    limb GEMM — the exact wiring the TPU d-precision path uses."""
    from dplasma_tpu.kernels import blas as kb
    from dplasma_tpu.utils import config as cfg

    calls = []
    orig = dd.gemm_f64
    monkeypatch.setattr(dd, "gemm_f64", lambda *a, **k: calls.append(1) or orig(*a, **k))
    monkeypatch.setitem(cfg._MCA_OVERRIDES, "dd_gemm", "always")
    a = rng.standard_normal((40, 64))
    b = rng.standard_normal((64, 32))
    out = np.asarray(kb.dot(jnp.asarray(a), jnp.asarray(b)))
    assert calls, "dd path not engaged under dd_gemm=always"
    np.testing.assert_allclose(out, a @ b, rtol=1e-12, atol=1e-12)

    za = a[:, :32] + 1j * a[:, 32:]
    zb = b[:32] + 1j * b[32:]
    zout = np.asarray(kb.dot(jnp.asarray(za), jnp.asarray(zb)))
    np.testing.assert_allclose(zout, za @ zb, rtol=1e-12, atol=1e-12)

    monkeypatch.setitem(cfg._MCA_OVERRIDES, "dd_gemm", "never")
    calls.clear()
    np.asarray(kb.dot(jnp.asarray(a), jnp.asarray(b)))
    assert not calls


@pytest.mark.parametrize("N,nb,seed,uplo", [
    pytest.param(192, 64, 11, "L", marks=pytest.mark.slow),
    (192, 64, 51, "L"),     # the seed that caught refine=2 (review r3)
    (192, 64, 51, "U"),
    pytest.param(378, 93, 3872, "L", marks=pytest.mark.slow),
    # ^ odd sizes: edge tiles + identity padding (compile-heavy)
])
def test_dd_potrf_end_to_end(rng, N, nb, seed, uplo):
    """d-precision blocked POTRF runs entirely through the limb GEMM
    path and still meets the reference residual check (threshold 60,
    ref tests/testing_zpotrf.c check) — across seeds, uplo, and padded
    odd sizes (a single lucky configuration let a refine regression
    ship green in round 3's first cut)."""
    from dplasma_tpu.descriptors import TileMatrix
    from dplasma_tpu.ops import checks, generators, potrf as potrf_mod
    from dplasma_tpu.utils import config as cfg

    cfg.mca_set("dd_gemm", "always")
    try:
        A = generators.plghe(float(N), N, nb, seed=seed,
                             dtype=jnp.float64)
        L = potrf_mod.potrf(A, uplo)
        res, ok = checks.check_potrf(A, L, uplo)
        assert ok, res
    finally:
        cfg._MCA_OVERRIDES.pop("dd_gemm", None)


@pytest.mark.parametrize("kappa", [
    pytest.param(1.0, marks=pytest.mark.slow),
    pytest.param(1e3, marks=pytest.mark.slow), 1e6])
def test_potrf_f64_refinement_accuracy(rng, kappa):
    """f32-seed + limb-IR tile Cholesky reaches f64-level residuals
    even for ill-conditioned tiles (the d-precision CORE_zpotrf role)."""
    n = 96
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    d = np.logspace(0, np.log10(kappa), n)
    A = (q * d) @ q.T
    A = (A + A.T) / 2
    L = np.asarray(dd.potrf_f64(jnp.asarray(A), lower=True))
    resid = np.abs(L @ L.T - A).max() / (np.abs(A).max() * n * EPS)
    assert resid < 60.0, resid
    if kappa >= 1e3:
        # f32 alone is orders of magnitude worse once conditioning bites
        L32 = np.linalg.cholesky(A.astype(np.float32)).astype(np.float64)
        r32 = np.abs(L32 @ L32.T - A).max() / (np.abs(A).max() * n * EPS)
        assert r32 > 100 * max(resid, 1.0)


@pytest.mark.slow
def test_potrf_f64_upper_and_complex(rng):
    n = 64
    a = rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
    A = a @ a.conj().T + n * np.eye(n)
    U = np.asarray(dd.potrf_f64(jnp.asarray(A), lower=False))
    resid = np.abs(U.conj().T @ U - A).max() / (np.abs(A).max() * n * EPS)
    assert resid < 60.0, resid


@pytest.mark.parametrize("side,trans", [("L", "N"), ("L", "T"),
                                        ("R", "N"), ("R", "C")])
def test_trsm_f64_accuracy(rng, side, trans):
    n, m = 80, 48
    T = np.tril(rng.standard_normal((n, n))) + n * np.eye(n)
    B = rng.standard_normal((n, m) if side == "L" else (m, n))
    X = np.asarray(dd.trsm_f64(jnp.asarray(T), jnp.asarray(B),
                               side=side, lower=True, trans=trans,
                               alpha=2.0))
    op = T.T if trans in ("T", "C") else T
    ref = (np.linalg.solve(op, 2.0 * B) if side == "L"
           else (2.0 * B) @ np.linalg.inv(op))
    err = np.abs(X - ref).max() / (np.abs(ref).max() * n * EPS)
    assert err < 100.0, err


def test_trsm_f64_stored_triangle_contract(rng):
    """trsm/trtri must read ONLY the named triangle: a packed L\\U tile
    (scratch in the opposite triangle) must solve identically to the
    masked tile — the round-2 review repro (getrf under dd)."""
    n, m = 48, 32
    packed = rng.standard_normal((n, n)) + n * np.eye(n)  # both triangles
    B = rng.standard_normal((m, n))
    clean = np.tril(packed)
    out_packed = np.asarray(dd.trsm_f64(jnp.asarray(packed),
                                        jnp.asarray(B), side="R",
                                        lower=True, trans="N"))
    out_clean = np.asarray(dd.trsm_f64(jnp.asarray(clean),
                                       jnp.asarray(B), side="R",
                                       lower=True, trans="N"))
    np.testing.assert_allclose(out_packed, out_clean, rtol=1e-12)
    # unit-diagonal variant ignores the stored diagonal too
    u = np.asarray(dd.trtri_f64(jnp.asarray(packed), lower=True,
                                unit=True))
    ref = np.linalg.inv(np.tril(packed, -1) + np.eye(n))
    # unit-lower inverses grow exponentially; compare to the scale of
    # the result (both sides carry ~kappa*eps64 rounding)
    np.testing.assert_allclose(u, ref, rtol=1e-6,
                               atol=1e-12 * np.abs(ref).max())


@pytest.mark.slow
def test_getrf_f64_under_dd(rng):
    """Blocked f64 LU runs correctly with every trsm/dot on the dd
    path (the TPU d-precision route)."""
    from dplasma_tpu.descriptors import TileMatrix
    from dplasma_tpu.ops import lu as lu_mod
    from dplasma_tpu.utils import config as cfg

    cfg.mca_set("dd_gemm", "always")
    try:
        N, nb = 96, 32
        a = rng.standard_normal((N, N)) + N * np.eye(N)
        A = TileMatrix.from_dense(jnp.asarray(a), nb, nb)
        LU, perm = lu_mod.getrf_1d(A)
        x = np.asarray(LU.to_dense())
        L = np.tril(x, -1) + np.eye(N)
        U = np.triu(x)
        resid = np.abs(a[np.asarray(perm)] - L @ U).max() / (
            np.abs(a).max() * N * EPS)
        assert resid < 100.0, resid
    finally:
        cfg._MCA_OVERRIDES.pop("dd_gemm", None)


@pytest.mark.slow
def test_geqrf_f64_under_dd(rng):
    """Blocked f64 QR on the dd route (CholQR2+reconstruction panels,
    limb compact-WY applies): residual and orthogonality at reference
    thresholds."""
    from dplasma_tpu.descriptors import TileMatrix
    from dplasma_tpu.ops import qr as qr_mod
    from dplasma_tpu.ops.qr import unmqr
    from dplasma_tpu.utils import config as cfg

    cfg.mca_set("dd_gemm", "always")
    try:
        N, nb = 128, 64   # 3 panels; 39s at 192 (1-core box)
        a = rng.standard_normal((N, N))
        A = TileMatrix.from_dense(jnp.asarray(a), nb, nb)
        Af, Tf = qr_mod.geqrf(A)
        R = np.triu(np.asarray(Af.to_dense()))
        QR = np.asarray(unmqr(
            "L", "N", Af, Tf,
            TileMatrix.from_dense(jnp.asarray(R), nb, nb)).to_dense())
        resid = np.abs(QR - a).max() / (np.abs(a).max() * N * EPS)
        assert resid < 60.0, resid
        eye = np.eye(N)
        Q = np.asarray(unmqr(
            "L", "N", Af, Tf,
            TileMatrix.from_dense(jnp.asarray(eye), nb, nb)).to_dense())
        orth = np.abs(Q.T @ Q - eye).max() / (N * EPS)
        assert orth < 60.0, orth
    finally:
        cfg._MCA_OVERRIDES.pop("dd_gemm", None)


def test_gemm_f64_chunked_deep_k(rng):
    # K > KC exercises the batched chunk path (exactness must not
    # degrade with reduction depth — the round-1 clamp bug)
    M, K, N = 16, 3 * dd.KC + 17, 24
    a = rng.standard_normal((M, K)) * np.exp(rng.uniform(-6, 6, (M, 1)))
    b = rng.standard_normal((K, N)) * np.exp(rng.uniform(-6, 6, (1, N)))
    out = np.asarray(dd.gemm_f64(jnp.asarray(a), jnp.asarray(b)))
    e_dd = _err_units(out, a, b)
    e_np = _err_units(a @ b, a, b)
    assert e_dd < max(8 * e_np, 0.5), (e_dd, e_np)


def test_gemm_f64_beats_f32_by_many_digits(rng):
    M = K = N = 256
    a = rng.standard_normal((M, K))
    b = rng.standard_normal((K, N))
    out = np.asarray(dd.gemm_f64(jnp.asarray(a), jnp.asarray(b)))
    f32 = (a.astype(np.float32) @ b.astype(np.float32)).astype(np.float64)
    ref = a @ b
    assert np.max(np.abs(out - ref)) < 1e-10
    assert np.max(np.abs(f32 - ref)) > 1e-6  # f32 is far worse


def test_plan_respects_accumulator_width():
    for K in (64, 1024, 4096, 65536, 2**20):
        w, nl, kc = dd._plan(K, 53)
        assert 2 ** w - 1 <= 127  # digits are exact int8
        assert w * nl >= 53  # covers the f64 mantissa
        # worst per-chunk level sum (nl pairs, kc-deep digit dots)
        # stays exact in the MXU's native int32 accumulator
        # (ADVICE round-1: no silent clamp)
        assert nl * kc * (2 ** w - 1) ** 2 < 2 ** 31
        assert kc <= K


def test_gemm_dd_alpha_beta(rng):
    a = rng.standard_normal((32, 64))
    b = rng.standard_normal((64, 48))
    c = rng.standard_normal((32, 48))
    out = np.asarray(dd.gemm_dd(1.5, jnp.asarray(a), jnp.asarray(b),
                                -0.5, jnp.asarray(c)))
    assert np.allclose(out, 1.5 * (a @ b) - 0.5 * c, atol=1e-11)


def test_bits32_mode(rng):
    a = rng.standard_normal((64, 1024))
    b = rng.standard_normal((1024, 64))
    out = np.asarray(dd.gemm_f64(jnp.asarray(a), jnp.asarray(b), bits=32))
    ref = a @ b
    assert np.max(np.abs(out - ref) / np.max(np.abs(ref))) < 1e-8


def test_split_fixed_ff_matches_bits(rng):
    """The float-float digit split (MXU backends, where the x64
    rewriter cannot bitcast f64) must reproduce the bit-pattern split's
    reconstruction within its tail bound, with int8-safe digits."""
    x = rng.standard_normal((64, 32)) * np.exp(
        rng.uniform(-8, 8, (64, 1)))
    x[3] = 0.0
    x[4, :] = 1.0
    m = np.abs(x).max(1, keepdims=True)
    sc = np.asarray(dd._pow2_scale_bits(jnp.asarray(m)))
    assert (sc >= 2 * m).all()
    w, nl = dd.W8, 8
    for split in (dd._split_fixed, dd._split_fixed_ff):
        limbs = [np.asarray(l, np.int64)
                 for l in split(jnp.asarray(x), jnp.asarray(sc), w, nl)]
        assert max(np.abs(l).max() for l in limbs) <= 127
        rec = sum(l * 2.0 ** (-w * (i + 1))
                  for i, l in enumerate(limbs)) * sc
        # ff runs on true-f64 here, so its lo part rounds to 24 bits:
        # grant it the corresponding tail (2^-48); bits split gets the
        # full 2^-55 contract
        tol = 2.0 ** -48 if split is dd._split_fixed_ff else 2.0 ** -55
        assert (np.abs(rec - x) <= sc * tol).all(), split


@pytest.mark.slow
def test_getrf_dd_eager_many_panels():
    """[slow: ~107 s warm — the eager route compiles ~27 shape-cached
    executables and the cost is trace/lowering, not compute]
    The eager shape-cached dd LU route (>8 panels, non-traced):
    padded-panel pivot bookkeeping must match the getrf_1d contract
    (review r4: the route was only reachable on TPU bench runs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dplasma_tpu.descriptors import TileMatrix
    from dplasma_tpu.ops import generators, lu as lu_mod
    from dplasma_tpu.utils import config as cfg

    cfg.mca_set("dd_gemm", "always")
    try:
        N, nb = 144, 16                 # 9 panels -> eager route
        A0 = generators.plrnt(N, N, nb, nb, seed=5, dtype=jnp.float64)
        LU, perm = lu_mod.getrf_1d(A0)  # eager (non-Tracer input)
        x = np.asarray(LU.to_dense())
        p = np.asarray(perm)[:N]
        a = np.asarray(A0.to_dense())[p]
        L = np.tril(x, -1)[:N, :N] + np.eye(N)
        U = np.triu(x)[:N, :N]
        r = np.abs(a - L @ U).max() / (
            np.abs(a).max() * N * np.finfo(np.float64).eps)
        assert r < 60.0, r
        # must agree with the traced sweep bit-for-bit
        LUt, pt = jax.jit(
            lambda d: lu_mod.getrf_1d(TileMatrix(d, A0.desc)))(A0.data)
        assert np.array_equal(np.asarray(pt), np.asarray(perm))
        assert np.allclose(np.asarray(LUt.data), np.asarray(LU.data),
                           rtol=0, atol=0)

        # singular-panel pivot safety (ADVICE r4): with an exactly
        # zero trailing column AND pad rows present (N % nb != 0), the
        # pivot tie-break among all-zero candidates must keep pad-row
        # indices out of perm[:N] — pinned here so a future pivot-
        # search change cannot silently corrupt rows via the clipped
        # gather. Reuses the shape-cached executables from above.
        Ns = 140                        # pads to 144: 4 pad rows
        As = generators.plrnt(Ns, Ns, nb, nb, seed=7,
                              dtype=jnp.float64)
        data = As.data.at[:, Ns - 1].set(0.0)
        LUs, perms = lu_mod.getrf_1d(TileMatrix(data, As.desc))
        ps = np.asarray(perms)[:Ns]
        assert (ps < Ns).all(), ps[ps >= Ns]
        xs = np.asarray(LUs.to_dense())
        asd = np.asarray(TileMatrix(data, As.desc).to_dense())[ps]
        Ls = np.tril(xs, -1)[:Ns, :Ns] + np.eye(Ns)
        Us = np.triu(xs)[:Ns, :Ns]
        rs = np.abs(asd - Ls @ Us).max() / (
            np.abs(asd).max() * Ns * np.finfo(np.float64).eps)
        assert rs < 60.0, rs
    finally:
        cfg.mca_set("dd_gemm", None)


def test_pallas_recombine_base_matches_exact():
    """The Pallas double-single epilogue (interpret mode here) must
    match the exact emulated recombine to ~2^-45 relative — the DS
    width contract (kernels/pallas_dd.py)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dplasma_tpu.kernels import dd, pallas_dd

    rng = np.random.default_rng(3)
    M, N, nl, w = 64, 128, 8, 7
    levels = [jnp.asarray(rng.integers(-2**30, 2**30, (M, N)),
                          jnp.int32) for _ in range(nl)]
    base = jnp.asarray(rng.standard_normal((M, N)) * 8.0)
    sa = jnp.asarray(2.0 ** rng.integers(-2, 3, (M, 1)))
    sb = jnp.asarray(2.0 ** rng.integers(-2, 3, (1, N)))
    exact = np.asarray(base - dd._level_recombine(levels, w)
                       * (sa * sb))
    got = np.asarray(pallas_dd.recombine_base(levels, base, sa, sb, w,
                                              interpret=True))
    scale = np.abs(np.asarray(dd._level_recombine(levels, w)
                              * (sa * sb))).max()
    assert np.abs(got - exact).max() / scale < 2.0 ** -45


def test_gemm_residual_matches_sub():
    """gemm_residual(base, a, b) == base - gemm_f64(a, b) (the fused
    epilogue path used by every dd IR step)."""
    import jax.numpy as jnp
    import numpy as np
    from dplasma_tpu.kernels import dd

    rng = np.random.default_rng(5)
    m, k, n = 48, 32, 40
    a = jnp.asarray(rng.standard_normal((m, k)))
    b = jnp.asarray(rng.standard_normal((k, n)))
    base = jnp.asarray(rng.standard_normal((m, n)))
    ref = np.asarray(base) - np.asarray(a) @ np.asarray(b)
    got = np.asarray(dd.gemm_residual(base, a, b))
    assert np.abs(got - ref).max() < 1e-12


def test_trsm_f64_extreme_magnitudes(rng):
    """The IR trsm's f32 seed must survive f64 magnitudes outside
    f32's range (the pow2 prescales on BOTH operands — review r5):
    huge and denormal-tiny rhs columns solve to full relative
    accuracy instead of Inf/0."""
    n, m = 64, 8
    T = np.tril(rng.standard_normal((n, n))) + n * np.eye(n)
    B = rng.standard_normal((n, m))
    B[:, 0] *= 1e38
    B[:, 1] *= 1e-38
    X = np.asarray(dd.trsm_f64(jnp.asarray(T), jnp.asarray(B),
                               side="L", lower=True))
    ref = np.linalg.solve(T, B)
    rel = np.abs(X - ref) / np.abs(ref).max(axis=0, keepdims=True)
    assert np.isfinite(X).all()
    assert rel.max() < 1e-10, rel.max()


def test_pallas_epilogue_off_on_a_grid(monkeypatch, devices8):
    """GSPMD cannot partition a Mosaic kernel: on a multi-device grid
    the recombine takes the XLA path even on a float-float backend."""
    import jax.numpy as jnp

    from dplasma_tpu.kernels import dd
    from dplasma_tpu.parallel import mesh as pmesh
    monkeypatch.setattr(dd, "_ff_backend", lambda: True)
    levels = [jnp.zeros((8, 128), jnp.int32)]
    assert dd._pallas_epilogue_ok(levels, 128)
    with pmesh.use_grid(pmesh.make_mesh(2, 2, devices8[:4])):
        assert not dd._pallas_epilogue_ok(levels, 128)
