"""The plain reference: what decides whether an answer is right.

``backward_errors`` judges each answer ``x`` of ``A x = b`` by the
equation itself, evaluated in float64 with NumPy on the host from the
benchmark's own ``A`` and ``b``: the normwise backward error
``||b - A x||_inf / ((||A||_inf ||x||_inf + ||b||_inf) eps)`` in units of
the configuration's machine epsilon. It takes nothing from the program
but the answer.

``solve`` is a plain blocked solver (right-looking Cholesky, or LU with
partial pivoting) in ``jax.numpy``, with every product of the bulk of
the work at an explicit ``precision``: ``"highest"`` (full-precision
products of the operand type), or ``"high"``: three bf16 passes, the
hi*hi + hi*lo + lo*hi split written out, so it means the same on any
backend (the split is made on the bit pattern, see ``_bf16_split``).
It imports nothing of the program. Put in the program's place
in the precision below the configuration's, it is the control that the
comparison has to refuse (``benchmark/control.py``).
"""
from __future__ import annotations

import numpy as np


def backward_errors(a, xs, bs, eps: float) -> np.ndarray:
    """Backward error of each column of ``xs`` against ``bs``."""
    a = np.asarray(a, np.float64)
    xs = np.asarray(xs, np.float64).reshape(a.shape[0], -1)
    bs = np.asarray(bs, np.float64).reshape(a.shape[0], -1)
    anorm = np.abs(a).sum(axis=1).max()
    r = np.abs(bs - a @ xs).max(axis=0)
    den = (anorm * np.abs(xs).max(axis=0) + np.abs(bs).max(axis=0)) * eps
    with np.errstate(invalid="ignore", divide="ignore"):
        out = r / den
    return np.where(np.isfinite(out), out, np.inf)


def _bf16_split(x):
    """``x`` (f32) as hi + lo, both bf16, as the MXU's three-pass mode
    splits it: hi is ``x`` rounded to bf16 (to nearest, ties to even),
    made on the bit pattern so that a compiler that may elide an
    f32 -> bf16 -> f32 round trip cannot elide it; lo is the rest,
    rounded to bf16."""
    import jax.numpy as jnp
    from jax import lax
    u = lax.bitcast_convert_type(x, jnp.uint32)
    u = u + jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))
    hi = lax.bitcast_convert_type(u & jnp.uint32(0xFFFF0000), jnp.float32)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def mm(a, b, precision: str):
    import jax.numpy as jnp
    from jax import lax
    if precision == "high":
        a_hi, a_lo = _bf16_split(a.astype(jnp.float32))
        b_hi, b_lo = _bf16_split(b.astype(jnp.float32))

        def one(x, y):
            return jnp.matmul(x, y, preferred_element_type=jnp.float32)
        return (one(a_hi, b_hi) + one(a_hi, b_lo) + one(a_lo, b_hi)) \
            .astype(a.dtype)
    if precision == "highest":
        return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


def _tri_inv(t, lower: bool, unit: bool = False):
    import jax.numpy as jnp
    from jax import lax
    eye = jnp.eye(t.shape[0], dtype=t.dtype)
    return lax.linalg.triangular_solve(t, eye, left_side=True, lower=lower,
                                       unit_diagonal=unit)


def _cholesky(a, nb: int, precision: str):
    """Lower Cholesky factor, right-looking, in column blocks."""
    import jax.numpy as jnp
    from jax import lax
    n = a.shape[0]
    cols = []
    for k in range(0, n, nb):
        e = min(k + nb, n)
        lkk = jnp.tril(lax.linalg.cholesky(a[k:e, k:e],
                                           symmetrize_input=False))
        if e < n:
            lik = mm(a[e:, k:e], _tri_inv(lkk, True).T, precision)
            a = a.at[e:, e:].add(-mm(lik, lik.T, precision))
            cols.append(jnp.concatenate(
                [jnp.zeros((k, e - k), a.dtype), lkk, lik], axis=0))
        else:
            cols.append(jnp.concatenate(
                [jnp.zeros((k, e - k), a.dtype), lkk], axis=0))
    return jnp.concatenate(cols, axis=1)


def _panel_lu(p):
    """Unblocked partial-pivot LU of a tall panel: (packed L\\U, perm)."""
    import jax
    import jax.numpy as jnp
    m, w = p.shape
    rows = jnp.arange(m)

    def step(j, carry):
        p, perm = carry
        col = jnp.where(rows >= j, jnp.abs(p[:, j]), -1)
        piv = jnp.argmax(col)
        rj, rp = p[j], p[piv]
        p = p.at[j].set(rp).at[piv].set(rj)
        perm = perm.at[j].set(perm[piv]).at[piv].set(perm[j])
        below = rows > j
        lcol = jnp.where(below, p[:, j] / p[j, j], 0)
        upd = lcol[:, None] * jnp.where(jnp.arange(w) > j, p[j], 0)[None]
        p = (p - upd).at[:, j].set(jnp.where(below, lcol, p[:, j]))
        return p, perm

    return jax.lax.fori_loop(0, w, step, (p, jnp.arange(m)))


def _lu(a, nb: int, precision: str):
    """Packed L\\U and row permutation: ``a[perm] = L U``."""
    import jax.numpy as jnp
    n = a.shape[0]
    perm = jnp.arange(n)
    for k in range(0, n, nb):
        e = min(k + nb, n)
        pan, pp = _panel_lu(a[k:, k:e])
        a = a.at[k:].set(a[k:][pp]).at[k:, k:e].set(pan)
        perm = perm.at[k:].set(perm[k:][pp])
        if e < n:
            l11 = pan[:e - k]
            u12 = mm(_tri_inv(l11, True, unit=True), a[k:e, e:], precision)
            a = a.at[k:e, e:].set(u12)
            a = a.at[e:, e:].add(-mm(pan[e - k:], u12, precision))
    return a, perm


def _forward(t, b, nb: int, precision: str, unit: bool):
    """Solve tril(t) y = b in row blocks."""
    import jax.numpy as jnp
    n = t.shape[0]
    ys = []
    for k in range(0, n, nb):
        e = min(k + nb, n)
        r = b[k:e]
        if ys:
            r = r - mm(t[k:e, :k], jnp.concatenate(ys, axis=0), precision)
        ys.append(mm(_tri_inv(jnp.tril(t[k:e, k:e]), True, unit), r,
                     precision))
    return jnp.concatenate(ys, axis=0)


def _backward(t, b, nb: int, precision: str):
    """Solve triu(t) x = b in row blocks."""
    import jax.numpy as jnp
    n = t.shape[0]
    xs = []
    for k in reversed(range(0, n, nb)):
        e = min(k + nb, n)
        r = b[k:e]
        if xs:
            r = r - mm(t[k:e, e:], jnp.concatenate(xs, axis=0), precision)
        xs.insert(0, mm(_tri_inv(jnp.triu(t[k:e, k:e]), False), r,
                        precision))
    return jnp.concatenate(xs, axis=0)


def solve(kind: str, a, bs, nb: int, precision: str):
    """Solve ``a x = b`` for each column of ``bs``: ``kind`` is
    ``"cholesky"`` (a SPD, lower triangle read) or ``"lu"``."""
    if kind == "cholesky":
        lo = _cholesky(a, nb, precision)
        y = _forward(lo, bs, nb, precision, unit=False)
        return _backward(lo.T, y, nb, precision)
    if kind == "lu":
        f, perm = _lu(a, nb, precision)
        y = _forward(f, bs[perm], nb, precision, unit=True)
        return _backward(f, y, nb, precision)
    raise ValueError(f"unknown solver {kind!r}")
