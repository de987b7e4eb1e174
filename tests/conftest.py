"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's CI strategy of simulating multi-node with
oversubscribed local ranks (ref: .github/workflows/build_cmake.yml:36,
tests/Testings.cmake:168-274) — here via XLA's host-platform device count.
"""
import contextlib
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8"
                           ).strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from dplasma_tpu.utils.config import use_compile_cache  # noqa: E402

jax.config.update("jax_enable_x64", True)
# the suite is compile-dominated: executables persist across runs
use_compile_cache()


@contextlib.contextmanager
def mca_overrides(kv):
    """Scoped MCA overrides with exact save/restore of the override
    store (shared by test_pipeline / test_panels — keep the semantics
    in ONE place)."""
    from dplasma_tpu.utils import config
    saved = dict(config._MCA_OVERRIDES)
    try:
        for key, val in kv.items():
            config.mca_set(key, val)
        yield
    finally:
        config._MCA_OVERRIDES.clear()
        config._MCA_OVERRIDES.update(saved)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def rng():
    return np.random.default_rng(3872)
