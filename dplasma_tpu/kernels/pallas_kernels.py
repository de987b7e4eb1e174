"""Pallas TPU kernels for the hot tile ops.

The reference's FLOP-carrying bodies are cuBLAS calls inside JDF CUDA
chores (e.g. the GEMM body of src/zgemm_NN_gpu.jdf and the trailing
updates of src/zpotrf_L.jdf:432-470). On TPU the analogue is a blocked
Pallas matmul that tiles onto the 128x128 MXU with a VMEM accumulator,
plus a fused alpha/beta epilogue so GEMM's ``C = alpha*A@B + beta*C``
runs as ONE kernel (one HBM read of C, one write).

Grid layout: (i, j, k) with k innermost; the f32 VMEM scratch accumulator
carries partial sums across the k steps of one (i, j) output block
(revolving-buffer pattern). Block sizes default to MXU-friendly 512/512/512
and are clamped to the (padded) problem.

On CPU (tests, the 8-device virtual mesh) kernels run in interpreter
mode; on TPU they compile to Mosaic. ``kernels.blas`` dispatches here
for eligible dtypes/shapes when enabled via :func:`enable` — an opt-in:
XLA's own matmul outpaces this kernel for plain products on current
TPUs (measured ~2-3x on v5e), so the fused path is for epilogue-bound
compositions and as the substrate for custom fusions, not the default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ENABLED = False
# Threshold below which pallas dispatch is not worth it (one MXU pass).
_MIN_DIM = 256


def enable(on: bool = True) -> None:
    global _ENABLED
    _ENABLED = on


def enabled() -> bool:
    return _ENABLED


def _interpret() -> bool:
    """Kernels interpret everywhere but on a real TPU backend."""
    return jax.default_backend() != "tpu"


def _block(dim: int, want: int, quantum: int) -> int:
    """Largest multiple of ``quantum`` <= want that isn't silly for dim."""
    if dim <= want:
        return dim
    return max(quantum, (want // quantum) * quantum)


def _accumulate(a_ref, b_ref, acc_ref, precision):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(
        a_ref[:], b_ref[:], preferred_element_type=jnp.float32,
        precision=precision,
    )


def _gemm_kernel(a_ref, b_ref, c_ref, o_ref, acc_ref, *, alpha, beta, nk,
                 precision):
    """Fused C = alpha*A@B + beta*C."""
    _accumulate(a_ref, b_ref, acc_ref, precision)

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        o_ref[:] = (alpha * acc_ref[:] +
                    beta * c_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, alpha, nk, precision):
    """alpha*A@B — the beta=0 variant; C never read (no HBM traffic)."""
    _accumulate(a_ref, b_ref, acc_ref, precision)

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        o_ref[:] = (alpha * acc_ref[:]).astype(o_ref.dtype)


def _pad_to(x, m, n):
    pm, pn = m - x.shape[0], n - x.shape[1]
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


@functools.partial(
    jax.jit,
    static_argnames=("alpha", "beta", "bm", "bn", "bk", "precision",
                     "interpret"))
def gemm(a, b, c=None, *, alpha=1.0, beta=1.0, bm=512, bn=512, bk=512,
         precision=jax.lax.Precision.HIGHEST, interpret=None):
    """C = alpha * A @ B + beta * C as one fused Pallas kernel.

    A:(M,K) B:(K,N) C:(M,N), real f32/bf16. Inputs are padded up to the
    block quantum; the pad region is zero so the (M, N) result is exact.
    ``c=None`` (or beta=0) selects a two-input variant that never reads
    C — no HBM traffic for it. ``interpret=None`` interprets off the
    TPU backend.
    """
    M, K = a.shape
    K2, N = b.shape
    if beta == 0.0:
        c = None
    assert K == K2 and (c is None or c.shape == (M, N)), \
        (a.shape, b.shape, None if c is None else c.shape)
    out_dtype = a.dtype if c is None else c.dtype
    sub = 16 if a.dtype == jnp.bfloat16 else 8
    bm = _block(M, bm, sub)
    bn = _block(N, bn, 128)
    bk = _block(K, bk, 128)
    gm, gn, gk = pl.cdiv(M, bm), pl.cdiv(N, bn), pl.cdiv(K, bk)
    a = _pad_to(a, gm * bm, gk * bk)
    b = _pad_to(b, gk * bk, gn * bn)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    operands = [a, b]
    if c is None:
        body = functools.partial(
            _matmul_kernel, alpha=alpha, nk=gk, precision=precision)
    else:
        operands.append(_pad_to(c, gm * bm, gn * bn))
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)))
        body = functools.partial(
            _gemm_kernel, alpha=alpha, beta=beta, nk=gk,
            precision=precision)

    out = pl.pallas_call(
        body,
        grid=(gm, gn, gk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((gm * bm, gn * bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=_interpret() if interpret is None else interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*operands)
    return out[:M, :N]


def matmul(a, b, **kw):
    """A @ B via the C-free kernel variant (C never touches HBM)."""
    return gemm(a, b, None, alpha=kw.pop("alpha", 1.0), beta=0.0, **kw)


def eligible(a, b, c=None) -> bool:
    """Cheap trace-time test: is the pallas path worth dispatching?"""
    if not _ENABLED:
        return False
    if a.ndim != 2 or b.ndim != 2:
        return False
    if a.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if a.dtype != b.dtype or (c is not None and c.dtype != a.dtype):
        return False
    M, K = a.shape
    N = b.shape[1]
    return min(M, K, N) >= _MIN_DIM
