#!/usr/bin/env python
"""Machine-peak GEMM microbenchmark (tools/gemmpeak analogue).

The reference measures attainable GEMM peak on CPU threads and on CUDA
(`tools/gemmpeak/mt-gemmpeak.c`, `cu-gemmpeak.cpp`, plotted by
`plot.gnuplot`) to normalize library results against hardware capability.
This twin sweeps square GEMM sizes per dtype/precision mode on the
available backend (TPU chip or host CPU) and prints one line per point:

    gemmpeak <backend> <dtype> <mode> N <n> <gflops>

plus a gnuplot-ready data block when --data is given. The bench harness
(bench.py) reuses :func:`measure_peak` for its %-of-peak normalization.

Usage: python tools/gemmpeak.py [--sizes 1024,2048,4096] [--iters 30]
       [--dtypes f32,bf16] [--data peak.dat]
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def _sync_fetch(x):
    import jax
    jax.block_until_ready(x)


def measure_peak(n: int = 4096, iters: int = 100, dtype="float32",
                 precision=None) -> float:
    """GFLOP/s of an n×n×n GEMM (the mt-gemmpeak timing model).

    Two defenses make this robust:

    * the matmul CHAIN feeds each product into the next (renormalized so
      values stay finite) — XLA cannot dead-code or hoist any of them;
    * per-iteration time is the DIFFERENCE between a long and a short
      loop, cancelling the fixed dispatch and sync latency, min-of-3
      each.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    dt = jnp.dtype(dtype)
    rng = np.random.default_rng(0)
    if dt == jnp.int8:
        # the MXU's integer systolic path (what the FP64-equivalent
        # limb engine rides): int8 x int8 -> native int32 accumulate
        a = jnp.asarray(rng.integers(-63, 64, (n, n)), jnp.int8)
        b = jnp.asarray(rng.integers(-63, 64, (n, n)), jnp.int8)
    else:
        a = jnp.asarray(rng.standard_normal((n, n)), dt)
        b = jnp.asarray(rng.standard_normal((n, n)), dt)

    def make_loop(k):
        @jax.jit
        def loop(a, b):
            def body(i, carry):
                acc, bb = carry
                if dt == jnp.int8:
                    y = lax.dot_general(
                        a, bb, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.int32)
                    # requantize so the chain stays live and nonzero
                    bb = lax.clamp(
                        jnp.int32(-63), y // jnp.int32(n * 16),
                        jnp.int32(63)).astype(jnp.int8) | jnp.int8(1)
                    return (acc + y[0, 0].astype(jnp.float32), bb)
                y = jnp.matmul(a, bb, precision=precision,
                               preferred_element_type=None
                               if dt == jnp.float64 else jnp.float32)
                s = lax.rsqrt(jnp.mean(y * y) + 1.0)
                return (acc + (y[0, 0] * s).astype(jnp.float32),
                        (y * s).astype(dt))
            out = lax.fori_loop(
                0, k, body, (jnp.zeros((), jnp.float32), b))
            return out[0]
        return loop

    lo, hi = max(iters // 20, 2), max(iters, 20)
    times = {}
    for k in (lo, hi):
        loop = make_loop(k)
        _sync_fetch(loop(a, b))  # warm compile
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            _sync_fetch(loop(a, b))
            best = min(best, time.perf_counter() - t0)
        times[k] = best
    per_iter = (times[hi] - times[lo]) / (hi - lo)
    if per_iter <= 0:
        return 0.0
    return 2.0 * n ** 3 / per_iter / 1e9


_MODES = {
    "float32": [("default", None), ("highest", "highest")],
    "bfloat16": [("default", None)],
    "int8": [("default", None)],
    "float64": [("default", None)],
}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--sizes", default="1024,2048,4096")
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--dtypes", default="float32,bfloat16")
    p.add_argument("--data", default=None,
                   help="write gnuplot-ready data file")
    args = p.parse_args(argv)

    import jax
    backend = jax.default_backend()
    sizes = [int(s) for s in args.sizes.split(",")]
    rows = []
    for dtype in args.dtypes.split(","):
        for mode, prec in _MODES.get(dtype, [("default", None)]):
            for n in sizes:
                try:
                    gf = measure_peak(n, args.iters, dtype, prec)
                except Exception as e:  # dtype unsupported on backend
                    print(f"gemmpeak {backend} {dtype} {mode} N {n} "
                          f"SKIP ({type(e).__name__})", file=sys.stderr)
                    continue
                rows.append((backend, dtype, mode, n, gf))
                print(f"gemmpeak {backend} {dtype} {mode} N {n} "
                      f"{gf:.1f}")
    if args.data:
        with open(args.data, "w") as f:
            f.write("# backend dtype mode N gflops\n")
            for r in rows:
                f.write(" ".join(map(str, r)) + "\n")
    if rows:
        best = max(rows, key=lambda r: r[-1])
        print(f"gemmpeak PEAK {best[1]}/{best[2]} N={best[3]} "
              f"{best[4]:.1f} GFLOP/s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
