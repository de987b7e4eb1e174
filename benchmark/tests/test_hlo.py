"""The HLO index: flops of dots and convolutions, and op categories."""
import jax
import jax.numpy as jnp
from jax import lax

from benchmark import hlo


def _index(fn, *args):
    return hlo.index([jax.jit(fn).lower(*args).compile().as_text()])


def test_dot_flops_and_operand():
    a = jnp.ones((64, 32), jnp.float32)
    b = jnp.ones((32, 48), jnp.float32)
    idx = _index(lambda x, y: jnp.tanh(x @ y), a, b)
    mm = [v for v in idx.values() if v["flops"] > 0]
    assert sum(v["flops"] for v in mm) == 2 * 64 * 48 * 32
    assert {v["operand"] for v in mm} == {"f32"}


def test_int8_operand():
    a = jnp.ones((16, 8), jnp.int8)
    b = jnp.ones((8, 24), jnp.int8)
    idx = _index(lambda x, y: lax.dot(x, y, preferred_element_type=jnp.int32),
                 a, b)
    mm = [v for v in idx.values() if v["flops"] > 0]
    assert sum(v["flops"] for v in mm) == 2 * 16 * 24 * 8
    assert {v["operand"] for v in mm} == {"s8"}


def test_panel_category_from_op_name():
    a = jnp.eye(32, dtype=jnp.float32) * 4
    idx = _index(lambda x: lax.linalg.cholesky(x), a)
    assert any(v["category"] == "panel" for v in idx.values())


def test_convolution_flops():
    text = """
ENTRY %main (p0: f32[8,16], p1: f32[16,4]) -> f32[8,4] {
  %p0 = f32[8,16]{1,0} parameter(0)
  %p1 = f32[16,4]{1,0} parameter(1)
  ROOT %convolution.1 = f32[8,4]{1,0} convolution(f32[8,16]{1,0} %p0, f32[16,4]{1,0} %p1), dim_labels=bf_io->bf
}
"""
    idx = hlo.index([text])
    assert idx["convolution.1"]["flops"] == 2 * 8 * 4 * 16
    assert idx["convolution.1"]["category"] == "matmul"


def test_collective_and_ring_categories():
    text = """
ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %all-reduce.1 = f32[8]{0} all-reduce(%p0), replica_groups={}, to_apply=%add
  ROOT %dplasma_ring_bcast_q.2 = f32[8]{0} custom-call(%all-reduce.1), custom_call_target="tpu_custom_call"
}
"""
    idx = hlo.index([text])
    assert idx["all-reduce.1"]["category"] == "collective"
    assert idx["dplasma_ring_bcast_q.2"]["category"] == "collective"
