"""Panel-factorization engine: fused tree/recursive panel kernels.

The bench ladder says the wide updates are healthy and the panels are
not (r04/r05: sgemm ~1.15-1.36x baseline, sgetrf 0.43x, sgeqrf 0.57x,
dd-f64 routes 0.12-0.22x): the reference's JDF decomposition turns
every panel into an O(mt)-deep geqrt -> tsqrt ladder of tile tasks
(src/zgeqrf_wrapper.c), and PR 4's lookahead only *hides* that chain
behind the far update — the chain itself is still a ladder of tiny
latency-bound dispatches.  This module replaces the chain:

* **QR tree** (:func:`geqrt_tree`) — a TSQR/CAQR binary-reduction
  panel (Demmel/Grigori/Hoemmen/Langou communication-avoiding QR):
  the tall panel splits into leaf blocks factored by ONE batched
  (vmapped) geqrf, sibling R triangles reduce pairwise up an
  O(log mt)-deep tree of batched stacked QRs, and the root's thin Q
  is pushed back down through the tree's Q factors.  TSQR-HR
  Householder reconstruction
  (:func:`~dplasma_tpu.kernels.householder.householder_reconstruct`)
  then recovers the compact-WY ``(packed, V, T)`` contract, so every
  downstream ``tsmqr``/WY apply is untouched.

* **LU rec** (:func:`lu_panel_rec`) — a blocked-recursive pivoted
  panel (Toledo's recursive LU; the role of the reference's
  CORE_zgetrf_rectil): columns halve recursively down to a
  ``panel.rec_base``-wide base case whose pivot search / swap / scale
  / rank-1 chain is fully vectorized over the slab (masked reductions,
  no one-hot over the panel) — O(log nb) *large* ops (trsm + Schur
  matmul per level) instead of nb rank-1 dispatches or the slow vendor
  LuDecompositionBlock custom call (~3.6 ns/element at panel shapes,
  r4/r5).  Pivot ties break to the LOWEST row index (the vendor /
  pallas_lu invariant the pad-row safety of the eager dd sweeps pins).
  :func:`lu_panel_rec_nopiv` is the unpivoted twin.

Selection rides MCA ``panel.kernel`` in {auto, chain, rec, tree,
pallas}: ``chain`` is bit-identical to the pre-engine routes, ``auto``
resolves per (route, backend) — the tree QR panel on MXU backends,
``chain`` everywhere else (``rec`` stays opt-in: see _TPU_DEFAULTS).
``pallas`` selects the fused Pallas panel kernels (kernels/pallas_lu,
kernels/pallas_qr) where the shape fits VMEM, falling back to rec/tree
otherwise; they run in interpret mode only, having no Mosaic lowering
yet (tests/test_chip_compile.py).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from dplasma_tpu.kernels import blas as k
from dplasma_tpu.kernels import householder as hh
from dplasma_tpu.utils import config as _cfg

_KERNELS = ("auto", "chain", "rec", "tree", "pallas")

#: per-route defaults for ``panel.kernel auto`` on MXU backends; every
#: other route resolves to ``chain`` (the vendor calls). The LU routes
#: stay on ``chain``: ``rec`` unrolls every pivot column, and at
#: N=8192 nb=1024 its sgetrf program lowered to ~1M StableHLO lines
#: whose TPU compile passed 26 GB of host memory (the 2x2 cyclic LU:
#: 1.8M lines), against ~650 lines for ``chain``
_TPU_DEFAULTS = {"qr": "tree"}

_cfg.mca_register(
    "panel.kernel", "auto",
    "Panel-factorization kernel of the blocked sweeps (qr.geqrf, "
    "ops.lu pivoted+nopiv incl. the eager dd routes, the cyclic LU "
    "panel election/playoff): chain (the pre-engine per-route panel, "
    "bit-identical), rec (blocked-recursive LU panel, vectorized "
    "pivot search), tree (TSQR/CAQR binary-reduction QR panel + "
    "TSQR-HR compact-WY reconstruction), pallas (fused Pallas panel "
    "kernels, interpret mode only; fall back to rec/tree past VMEM), "
    "auto (tree for QR on MXU backends, chain otherwise).")
_cfg.mca_register(
    "panel.tree_leaf", "2",
    "Leaf-block height of the TSQR tree panel, in multiples of the "
    "panel width (>=1): taller leaves mean fewer tree levels, shorter "
    "leaves more batch parallelism per level.")
_cfg.mca_register(
    "panel.rec_base", "8",
    "Base-case column width of the blocked-recursive LU panel: below "
    "this width columns eliminate by the vectorized pivot loop; above "
    "it, recursion halves (trsm + rank-h Schur per level).")


def panel_kernel_config() -> str:
    """The raw MCA ``panel.kernel`` value (bench/report provenance)."""
    return (_cfg.mca_get("panel.kernel") or "auto").lower()


def panel_kernel(route: str) -> str:
    """Resolve the active panel kernel for ``route`` in {qr, lu,
    nopiv}: explicit MCA value wins (cross-family names map to the
    route's own engine: tree->rec for LU, rec->tree for QR), ``auto``
    resolves per backend, and nopiv has no ``pallas`` kernel."""
    v = panel_kernel_config()
    if v not in _KERNELS:
        v = "auto"
    if v == "auto":
        if jax.default_backend() == "tpu":
            v = _TPU_DEFAULTS.get(route, "chain")
        else:
            v = "chain"
    if v == "pallas" and route == "nopiv":
        v = "rec"  # nopiv has no fused pallas kernel
    if route == "qr" and v == "rec":
        v = "tree"
    elif route in ("lu", "nopiv") and v == "tree":
        v = "rec"
    return v


# ---------------------------------------------------------------------
# TSQR tree panel (QR)
# ---------------------------------------------------------------------

def _mm(a, b):
    """Full-precision (batched) matmul for the tree's push-down —
    plain f32 matmuls at HIGHEST precision (the dd route has its own
    limb-exact tree in kernels.dd)."""
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST,
                      preferred_element_type=k._acc_type(a.dtype)
                      ).astype(a.dtype)


def tree_leaf_height(nb: int) -> int:
    """Leaf-block height of the TSQR tree (MCA ``panel.tree_leaf``
    multiples of the panel width, floor 1)."""
    return max(_cfg.mca_get_int("panel.tree_leaf", 2), 1) * nb


def tsqr(a, leaf: int | None = None, *, need_q: bool = True):
    """Thin QR of a tall panel by TSQR binary-tree reduction.

    Level 0 factors ``leaf``-tall blocks with one batched (vmapped)
    geqrf; each subsequent level stacks sibling R pairs and factors
    the (2n, n) couples with one batched geqrf — O(log mt) levels.
    The root's thin Q is pushed back down through the per-level Q
    factors (each level one batched matmul), so ``a = q @ r`` with
    ``q`` orthonormal (m, n) and ``r`` the root triangle.

    The block count pads to a power of two with ZERO blocks: for a
    (numerically) full-rank panel the pad rows of Q are exactly zero
    (Q = [A; 0] R^{-1}), so the sliced q is orthonormal; rank-deficient
    panels keep a valid q only when no row padding was needed (the
    geqrf caller identity-pads its edge tiles, same envelope as the
    CholeskyQR2 panel but without the Gram's condition squaring).

    ``need_q=False`` skips the push-down entirely and returns
    ``(None, r)`` — the R-only reduction (half the tree's matmul
    work) for callers that rebuild Q themselves (the dd tree panel's
    IR right-solve).
    """
    m, n = a.shape
    lb = tree_leaf_height(n) if leaf is None else max(int(leaf), n)
    if m <= lb:
        q, r = jnp.linalg.qr(a, mode="reduced")
        return (q if need_q else None), r
    L = -(-m // lb)
    L2 = 1 << (L - 1).bit_length()      # pad block count to a power of 2
    ap = jnp.pad(a, ((0, L2 * lb - m), (0, 0)))
    q0, r = jax.vmap(partial(jnp.linalg.qr, mode="reduced"))(
        ap.reshape(L2, lb, n))
    qs = []                             # per-level (B, 2n, n) Q factors
    while r.shape[0] > 1:
        pairs = r.reshape(r.shape[0] // 2, 2 * n, n)
        qi, r = jax.vmap(partial(jnp.linalg.qr, mode="reduced"))(pairs)
        if need_q:
            qs.append(qi)
    if not need_q:
        return None, r[0]
    # push the root's Q back down: W starts as I at the root, each
    # level maps a node's (n, n) W to its two children's W blocks
    w = jnp.eye(n, dtype=a.dtype)[None]
    for qi in reversed(qs):
        w = _mm(qi, w).reshape(qi.shape[0] * 2, n, n)
    q = _mm(q0, w).reshape(L2 * lb, n)[:m]
    return q, r[0]


def geqrt_tree(a, leaf: int | None = None):
    """TSQR/CAQR panel QR: tree-reduced thin (Q, R), then TSQR-HR
    Householder reconstruction back to the compact-WY ``(packed, V,
    T)`` contract of :func:`~dplasma_tpu.kernels.householder.geqrt` —
    downstream appliers never see the tree."""
    q, r = tsqr(a, leaf)
    return hh.householder_reconstruct(q, r)


def qr_panel(a, kind: str | None = None, *, rankfull: bool = True):
    """One (m, nb) QR panel by the selected kernel: ``(packed, V, T)``
    in the :func:`~dplasma_tpu.kernels.householder.geqrt` contract.
    ``pallas`` falls back to ``tree`` when the shape misses the fused
    kernel's VMEM/alignment gate; ``chain`` is today's vendor panel
    (still honoring MCA ``qr_panel``)."""
    kind = panel_kernel("qr") if kind is None else kind
    if kind == "pallas":
        from dplasma_tpu.kernels import pallas_qr
        if pallas_qr.eligible(a):
            return pallas_qr.geqrt_panel(a)
        kind = "tree"
    if kind == "tree":
        return geqrt_tree(a)
    return hh.geqrt(a, rankfull=rankfull)


# ---------------------------------------------------------------------
# Blocked-recursive LU panel
# ---------------------------------------------------------------------

def rec_base_width() -> int:
    return max(_cfg.mca_get_int("panel.rec_base", 8), 1)


def _lu_base_vec(a, pivot: bool):
    """Vectorized elimination of a narrow (m, w) strip: per column one
    masked lowest-index arg-max pivot search (a pure reduction — no
    one-hot over the panel), a two-row swap, scale, and a rank-1
    update confined to the strip.  Returns (packed, perm)."""
    m, w = a.shape
    rowv = jnp.arange(m)
    perm = jnp.arange(m)
    A = a
    for j in range(w):
        if pivot:
            cand = jnp.where(rowv >= j, jnp.abs(A[:, j]), -1.0)
            piv = jnp.argmax(cand)      # first max = lowest row index
            rj, rp = A[j], A[piv]
            A = A.at[j].set(rp).at[piv].set(rj)
            pj, pp = perm[j], perm[piv]
            perm = perm.at[j].set(pp).at[piv].set(pj)
        d = A[j, j]
        inv = jnp.where(d != 0, 1.0 / jnp.where(d != 0, d, 1), 0.0)
        below = rowv > j
        lcol = jnp.where(below, A[:, j] * inv, 0.0)
        A = A.at[:, j].set(jnp.where(below, lcol, A[:, j]))
        if j + 1 < w:
            upd = lcol[:, None] * A[j, j + 1:][None, :]
            A = A.at[:, j + 1:].add(-jnp.where(below[:, None], upd, 0.0))
    return A, perm


def _lu_rec(a, bw: int, pivot: bool):
    m, n = a.shape
    if n <= bw:
        return _lu_base_vec(a, pivot)
    h = n // 2
    l1, p1 = _lu_rec(a[:, :h], bw, pivot)
    rest = a[:, h:]
    if pivot:
        rest = rest[p1]
    u12 = k.trsm(l1[:h], rest[:h], side="L", lower=True, unit=True)
    s = rest[h:] - k.dot(l1[h:], u12)
    l2, p2 = _lu_rec(s, bw, pivot)
    bot_l = l1[h:]
    if pivot:
        bot_l = bot_l[p2]
        perm = p1[jnp.concatenate([jnp.arange(h), h + p2])]
    else:
        perm = jnp.arange(m)
    top = jnp.concatenate([l1[:h], u12], axis=1)
    bot = jnp.concatenate([bot_l, l2], axis=1)
    return jnp.concatenate([top, bot], axis=0), perm


def lu_panel_rec(a, base: int | None = None):
    """Blocked-recursive partial-pivoting LU of an (m, n) slab
    (m >= n): ``a[perm] = L U``.  Returns (packed L\\U with unit L
    implicit, perm) — the exact :func:`dplasma_tpu.ops.lu._base_lu`
    contract.  All off-base work is trsm/matmul (MXU-shaped); no
    vendor custom call, no VMEM row ceiling, no CALU chunking."""
    bw = rec_base_width() if base is None else max(int(base), 1)
    return _lu_rec(a, bw, pivot=True)


def lu_panel_rec_nopiv(a, base: int | None = None):
    """Unpivoted twin of :func:`lu_panel_rec`: packed L\\U of the
    (m, n) slab (the getrf_nopiv panel contract: diagonal-block
    L\\U on top, L21 = A21 U^{-1} below)."""
    bw = rec_base_width() if base is None else max(int(base), 1)
    packed, _ = _lu_rec(a, bw, pivot=False)
    return packed
