"""Mosaic compiles of the main path's Pallas kernels for a v5e chip.

No chip is attached here: the topology is described, never run, so
these tests catch what interpret mode cannot (unaligned tiles, VMEM
overflow, i64 index maps) at no chip time. The d-precision shapes are
the ones ``testing_dpotrf -N 8192 -t 512`` produces; the ring kernels
compile under a 4-device mesh of the described 2x2 host.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _mosaic(fn, *args) -> str:
    """Compile ``fn`` for the described chip with x64 off (as every
    kernel traces); the compiled module text."""
    with jax.enable_x64(False):
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("M,N", [(512, 8192), (8192, 512)])
def test_recombine_compiles(one_chip, M, N):
    from dplasma_tpu.kernels import pallas_dd
    nl = 7
    _mosaic(lambda lv, bh, bl, sa, sb: pallas_dd._recombine_call(
                lv, bh, bl, sa, sb, 7, False),
            _sds((nl, M, N), jnp.int32, one_chip),
            _sds((M, N), jnp.float32, one_chip),
            _sds((M, N), jnp.float32, one_chip),
            _sds((M, 1), jnp.float32, one_chip),
            _sds((1, N), jnp.float32, one_chip))


def test_matmul_compiles(one_chip):
    from dplasma_tpu.kernels import pallas_kernels as pk
    x = _sds((1024, 1024), jnp.float32, one_chip)
    _mosaic(lambda a, b: pk.matmul(a, b, interpret=False), x, x)


@pytest.mark.parametrize("n", [128, 512])
def test_lu_nopiv_tile_compiles(one_chip, n):
    """The diagonal-block LU of the grid sweep without pivoting, up to
    its largest tile (a 1024 tile overflows scoped VMEM)."""
    from dplasma_tpu.kernels import pallas_lu
    assert n <= pallas_lu.NOPIV_MAX
    _mosaic(lambda a: pallas_lu._nopiv_call(a, False),
            _sds((n, n), jnp.float32, one_chip))


def test_a2a_conversion_memory_on_chip(topo):
    """The all_to_all conversion to cyclic slabs holds about two local
    blocks (the received pieces and the result) on a v5e, not one piece
    stack per destination slot: with 32 row tiles a stack of whole
    pieces took 8 local blocks, and at HPL-MxP's N 32768 (64 tiles)
    16 GiB of a chip's 15.75."""
    from dplasma_tpu.descriptors import Dist, TileDesc, TileMatrix
    from dplasma_tpu.parallel import cyclic
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 2), ("p", "q"))
    n, nb = 4096, 128
    desc = TileDesc(n, n, nb, nb)
    f = jax.jit(lambda a: cyclic.from_tile_a2a(
        TileMatrix(a, desc), Dist(P=2, Q=2), mesh).data)
    stats = f.lower(_sds((n, n), jnp.float32, NamedSharding(
        mesh, P("p", "q")))).compile().memory_analysis()
    local = n * n * 4 // 4
    assert stats.temp_size_in_bytes <= 3 * local, stats.temp_size_in_bytes


# The fused panels (opt-in: MCA panel.kernel=pallas) have no Mosaic
# lowering yet: the LU kernel slices a zero-width block and both write
# their pivot/tau vectors by scatter. Strict, so a fix must say so.
@pytest.mark.xfail(strict=True, reason="no Mosaic lowering of the fused "
                   "panels (zero-width slice; scatter)")
@pytest.mark.parametrize("kernel", ["lu", "qr"])
def test_panel_compiles(one_chip, kernel):
    from dplasma_tpu.kernels import pallas_lu, pallas_qr
    call = pallas_lu._panel_call if kernel == "lu" \
        else pallas_qr._geqrt_call
    _mosaic(lambda a: call(a, False),
            _sds((2048, 256), jnp.float32, one_chip))


@pytest.mark.parametrize("op", ["shift", "allreduce"])
def test_ring_compiles(topo, op):
    from dplasma_tpu.kernels import pallas_ring as pring
    mesh = Mesh(np.asarray(topo.devices[:4]), ("x",))
    ring = pring.ring_shift if op == "shift" else pring.ring_allreduce
    f = shard_map(
        lambda a: ring(a, axis="x", axes=(("x", 4),), interpret=False),
        mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False)
    _mosaic(f, _sds((4 * 256, 512), jnp.float32,
                    NamedSharding(mesh, P("x"))))
