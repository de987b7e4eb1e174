"""Pallas kernel correctness (interpreter mode on the CPU mesh).

The reference's hot bodies are cuBLAS calls inside JDF chores
(src/zgemm_NN_gpu.jdf, src/zpotrf_L.jdf:432-470); here the TPU analogues
are Pallas kernels checked against the plain XLA path.

The kernels run in interpret mode here; tests/test_chip_compile.py
compiles them for the chip. Their static contracts are checked by
``analysis.palcheck`` (tests/test_palcheck.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dplasma_tpu.kernels import blas as k

from dplasma_tpu.kernels import pallas_kernels as pk


@pytest.fixture
def mats(rng):
    a = jnp.asarray(rng.standard_normal((300, 200)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((200, 260)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((300, 260)), jnp.float32)
    return a, b, c


def test_gemm_fused_matches_reference(mats):
    a, b, c = mats
    out = pk.gemm(a, b, c, alpha=2.0, beta=-0.5, bm=128, bn=128, bk=128)
    ref = 2.0 * (np.asarray(a, np.float64) @ np.asarray(b, np.float64)) \
        - 0.5 * np.asarray(c, np.float64)
    assert np.allclose(np.asarray(out), ref, atol=1e-3)


def test_matmul_beta_zero(mats):
    a, b, _ = mats
    out = pk.matmul(a, b, bm=128, bn=128, bk=64)
    ref = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    assert np.allclose(np.asarray(out), ref, atol=1e-3)


def test_block_clamping_small_problem(rng):
    # Problem smaller than the block quantum: single-block path.
    a = jnp.asarray(rng.standard_normal((64, 48)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((48, 32)), jnp.float32)
    c = jnp.zeros((64, 32), jnp.float32)
    out = pk.gemm(a, b, c, alpha=1.0, beta=0.0)
    ref = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    assert np.allclose(np.asarray(out), ref, atol=1e-4)


def test_blas_dispatch_toggle(mats):
    a, b, c = mats
    base = k.gemm(1.5, a, b, 0.5, c)
    pk.enable(True)
    try:
        assert pk.enabled()
        # below _MIN_DIM: still the XLA path, exact same result
        small = k.gemm(1.5, a, b, 0.5, c)
        assert np.array_equal(np.asarray(base), np.asarray(small))
        # force eligibility by lowering the threshold
        old = pk._MIN_DIM
        pk._MIN_DIM = 16
        try:
            fused = k.gemm(1.5, a, b, 0.5, c)
        finally:
            pk._MIN_DIM = old
    finally:
        pk.enable(False)
    assert np.allclose(np.asarray(fused), np.asarray(base), atol=1e-3)


def test_bf16_inputs(rng):
    a = jnp.asarray(rng.standard_normal((128, 128)), jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal((128, 128)), jnp.bfloat16)
    c = jnp.zeros((128, 128), jnp.bfloat16)
    out = pk.gemm(a, b, c, alpha=1.0, beta=0.0, bm=128, bn=128, bk=128)
    ref = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    assert out.dtype == jnp.bfloat16
    assert np.allclose(np.asarray(out, np.float64), ref, rtol=0.05, atol=0.5)


def test_pallas_lu_panel_matches_vendor():
    """Blocked register-tile LU panel (kernels/pallas_lu.py, interpret
    mode here): packed factor residual at f32 level and EXACT pivot
    agreement with the vendor custom call (lowest-index ties — the
    invariant the eager dd sweeps' pad-row safety pins)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dplasma_tpu.kernels import pallas_lu

    rng = np.random.default_rng(2)
    for M, nb in ((96, 16), (64, 8)):
        a = rng.standard_normal((M, nb)).astype(np.float32)
        packed, perm = pallas_lu.lu_panel(jnp.asarray(a))
        packed = np.asarray(packed)
        perm = np.asarray(perm)
        L = np.tril(packed, -1)
        L[:nb] += np.eye(nb, dtype=np.float32)
        U = np.triu(packed[:nb])
        r = np.abs(a[perm] - L @ U).max() / np.abs(a).max()
        assert r < 1e-5, (M, nb, r)
        _, _, p_ = jax.lax.linalg.lu(jnp.asarray(a))
        assert np.array_equal(perm, np.asarray(p_)), (M, nb)


def test_pallas_lu_panel_mca_routing(monkeypatch):
    """MCA lu.pallas_panel=on routes _base_lu through the kernel."""
    import jax.numpy as jnp
    import numpy as np

    from dplasma_tpu.kernels import pallas_lu
    from dplasma_tpu.ops import lu as lu_mod
    from dplasma_tpu.utils import config as cfg

    calls = []
    orig = pallas_lu.lu_panel
    monkeypatch.setattr(pallas_lu, "lu_panel",
                        lambda *a, **k: calls.append(1) or orig(*a, **k))
    cfg.mca_set("lu.pallas_panel", "on")
    try:
        rng = np.random.default_rng(3)
        a = rng.standard_normal((64, 16)).astype(np.float32)
        packed, perm = lu_mod._base_lu(jnp.asarray(a))
        assert calls, "pallas panel not engaged under MCA on"
        L = np.tril(np.asarray(packed), -1)
        L[:16] += np.eye(16, dtype=np.float32)
        U = np.triu(np.asarray(packed)[:16])
        r = np.abs(a[np.asarray(perm)] - L @ U).max()
        assert r < 1e-4, r
    finally:
        cfg.mca_set("lu.pallas_panel", None)
