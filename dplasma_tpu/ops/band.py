"""Band → tridiagonal / bidiagonal via scan-compiled bulge chasing.

The reference's stage-2 kernels are sequential bulge chasing sweeps
(``zhbrdt.jdf:41-60`` band→tridiag; ``tests/testing_zgesvd.c:106-145``
finishes the band bidiagonal with LAPACK ``zgbbrd``). A trace-time
unrolled translation would emit O(N·b) ops — unusable compile times at
scale. TPU-native design here:

* the full rotation SCHEDULE (which Givens rotation, in which order) is
  pure index algebra — computed once in numpy at trace time (the same
  property as the reference's dep expressions, SURVEY §3.3);
* execution is ONE ``lax.scan`` over that schedule; every step applies
  a complex-safe Givens rotation to fixed-shape row/column strips of a
  padded dense array via dynamic slices. Compile cost is O(1) in N.

Chase chains (derived from band sparsity):
* Hermitian (bandwidth b → 1): eliminating A[s+j, s] with a rotation on
  rows (i−1, i), i = s+j, fills A[i+b, i−1]; the chain
  (i, c) → (i+b, i−1) walks off the matrix.
* Bidiagonal (upper bandwidth b → 1): a column rotation zeroing
  A[s, s+j] fills the subdiagonal A[q, q−1] (q = s+j); the row rotation
  clearing it fills A[q−1, q+b]; the chain advances by b with
  alternating column/row rotations.

These chases are sequential VPU work — right for the *narrow-band tail*
(the blocked matmul sweeps in ``ops.eig`` take the band down first; see
``eig.hbrdt``/``eig.gebrd``).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax


def _lartg(f, g):
    """Complex-safe Givens: returns (c, s) with c real such that
    [[c, s], [-conj(s), c]] @ [f, g]^T = [r, 0]^T."""
    af = jnp.abs(f)
    ag = jnp.abs(g)
    r = jnp.sqrt(af * af + ag * ag)
    safe = r > 0
    rs = jnp.where(safe, r, 1.0)
    c = jnp.where(safe, af / rs, 1.0)
    phase = jnp.where(af > 0, f / jnp.where(af > 0, af, 1.0).astype(f.dtype),
                      jnp.ones((), f.dtype))
    s = jnp.where(safe, phase * jnp.conj(g) / rs.astype(f.dtype),
                  jnp.zeros((), f.dtype))
    # af == 0 but ag > 0: pure swap
    swap = (af == 0) & (ag > 0)
    c = jnp.where(swap, 0.0, c)
    s = jnp.where(swap, jnp.ones((), f.dtype), s)
    return c.astype(f.dtype), s


# ---------------------------------------------------------------------
# Hermitian band -> tridiagonal
# ---------------------------------------------------------------------

def herm_chase_schedule(N: int, b: int) -> np.ndarray:
    """Rotation schedule (K, 2) of (i, c): rotate rows (i-1, i) to zero
    A[i, c], then chase the (i+b, i-1) fills down the band."""
    steps = []
    for s in range(N - 2):
        for j in range(min(b, N - 1 - s), 1, -1):
            i, c = s + j, s
            while i < N:
                steps.append((i, c))
                i, c = i + b, i - 1
    if not steps:
        return np.zeros((0, 2), dtype=np.int32)
    return np.asarray(steps, dtype=np.int32)


def herm_band_to_tridiag(X, N: int, b: int):
    """Reduce a dense-stored Hermitian band matrix (bandwidth b, both
    triangles populated, logical size N) to tridiagonal. Returns (d, e)
    real.  One lax.scan over the precomputed rotation schedule."""
    if N <= 2 or b <= 1:
        d = jnp.real(jnp.diagonal(X))[:N]
        e = jnp.abs(jnp.diagonal(X, offset=-1))[:N - 1] if N > 1 else \
            jnp.zeros((0,), jnp.real(X).dtype)
        return d, e
    sched = herm_chase_schedule(N, b)
    D = b + 2                      # window margin (band + bulge)
    L = 2 * D + 2                  # strip length covering both rows/cols
    P = D + 1                      # padding so slices never clamp
    Xp = jnp.zeros((N + 2 * P, N + 2 * P), X.dtype)
    Xp = Xp.at[P:P + N, P:P + N].set(X[:N, :N])

    def step(Xp, ic):
        i, c = ic[0], ic[1]
        f = Xp[i - 1 + P, c + P]
        g = Xp[i + P, c + P]
        cs, sn = _lartg(f, g)
        row0 = i - 1 + P
        col0 = i - 1 - D + P
        # rows (i-1, i): A <- G A on a (2, L) strip
        R = lax.dynamic_slice(Xp, (row0, col0), (2, L))
        Rn = jnp.stack([cs * R[0] + sn * R[1],
                        -jnp.conj(sn) * R[0] + cs * R[1]])
        Xp = lax.dynamic_update_slice(Xp, Rn, (row0, col0))
        # cols (i-1, i): A <- A G^H on an (L, 2) strip
        C = lax.dynamic_slice(Xp, (col0, row0), (L, 2))
        Cn = jnp.stack([cs * C[:, 0] + jnp.conj(sn) * C[:, 1],
                        -sn * C[:, 0] + cs * C[:, 1]], axis=1)
        Xp = lax.dynamic_update_slice(Xp, Cn, (col0, row0))
        return Xp, None

    Xp, _ = lax.scan(step, Xp, jnp.asarray(sched))
    body = Xp[P:P + N, P:P + N]
    d = jnp.real(jnp.diagonal(body))
    e = jnp.abs(jnp.diagonal(body, offset=-1))
    return d, e


# ---------------------------------------------------------------------
# Pipelined blocked SBR (stage 2): the multi-bulge replacement for
# per-rotation bulge chasing.
#
# One sweep reduces Hermitian bandwidth b -> w (w <= b//4) by panel QR
# + full bulge chasing (Bischof-Lang-Sun successive band reduction):
#   panel j (cols [s, s+w), s = j*w): QR of the b x w block at rows
#     [s+w, s+w+b) brings the panel to bandwidth w; the two-sided
#     compact-WY update fills a bulge over cols [s+w, s+w+b);
#   chase m >= 1: QR of the b x b block rows [r0, r0+b) x cols
#     [r0-b, r0), r0 = s+w+m*b, restores bandwidth b for those columns
#     and pushes the bulge b rows down — until it falls off the matrix.
# Every step is ONE geqrt + two compact-WY strip applies in a static
# V = 3b+w window anchored at c0 (panel: c0 = s; chase: c0 = r0-b) —
# matmul work, no per-rotation latency.
#
# Pipelining: panel j starts at time 5j; at any time the active
# panels' windows are pairwise disjoint (anchor gap >= 4b vs window
# V = 3b+w, w <= b//4), so each scan step runs up to G = ceil(M/5)+1
# independent steps batched with vmap, scattered back to disjoint
# windows. The reference's stage-2 (zhbrdt.jdf:41-60) is the
# sequential rotation schedule this replaces wholesale.
# ---------------------------------------------------------------------

def _sbr_schedule(N: int, b: int, w: int):
    """(c0, u, T, G, V, park): pipelined step tables for one sweep.

    c0, u: (T, G) int32 window anchors and elimination widths (u = w
    panel, u = b chase; invalid slots park at a per-slot zero region
    past the data so the batched scatter stays disjoint)."""
    starts = list(range(0, max(N - w - 1, 0), w))
    V = 3 * b + w
    if not starts:
        return None
    # steps per panel: 1 panel step + chases while r0 = s+w+m*b < N
    M = [1 + max(0, -(-(N - s - w) // b) - 1) for s in starts]
    Mx = max(M)
    G = -(-Mx // 5) + 1
    T = max(5 * j + M[j] for j in range(len(starts)))
    park0 = N + 3 * b + w
    c0 = np.full((T, G), 0, np.int32)
    uu = np.full((T, G), 0, np.int32)
    for g in range(G):
        c0[:, g] = park0 + g * V
    for j, s in enumerate(starts):
        g = j % G
        for m in range(M[j]):
            t = 5 * j + m
            c0[t, g] = s if m == 0 else s + w + (m - 1) * b
            uu[t, g] = w if m == 0 else b
    return c0, uu, T, G, V, park0


def herm_sbr_sweep(X, N: int, b: int, w: int):
    """One pipelined SBR sweep: Hermitian band ``b`` -> ``w``
    (``w <= b//4``; see the section comment for the schedule). ``X``
    dense-stored (both triangles live), logical size ``N``, true
    bandwidth ``<= b``. Returns the swept array, same logical content,
    possibly grown padding."""
    from dplasma_tpu.kernels import householder as hh
    assert 1 <= w <= b // 4 or (b <= 4 and w == 1), (b, w)
    sched = _sbr_schedule(N, b, w)
    if sched is None or N <= 2 or b <= 1:
        return X
    c0s, us, T, G, V, park0 = sched
    Mp = X.shape[0]
    Mp2 = park0 + G * V
    Xp = jnp.zeros((Mp2, Mp2), X.dtype).at[:Mp, :Mp].set(X) \
        if Mp2 > Mp else X

    bcols = jnp.arange(b)

    def one(win, u):
        """Process one window: masked QR of the b x b block at
        (u, 0) eliminating its first u columns, two-sided apply."""
        blk = lax.dynamic_slice(win, (u, jnp.zeros_like(u)), (b, b))
        blk = jnp.where((bcols < u)[None, :], blk, 0)
        _, v, tT = hh.geqrt(blk)
        rows = lax.dynamic_slice(win, (u, jnp.zeros_like(u)), (b, V))
        rows = hh.apply_q(v, tT, rows, trans="C")
        win = lax.dynamic_update_slice(win, rows,
                                       (u, jnp.zeros_like(u)))
        cols = lax.dynamic_slice(win, (jnp.zeros_like(u), u), (V, b))
        cols = hh.apply_q_right(v, tT, cols, trans="N")
        return lax.dynamic_update_slice(win, cols,
                                        (jnp.zeros_like(u), u))

    def step(Xp, tc):
        c0, u = tc

        def gat(g, buf):
            w_ = lax.dynamic_slice(Xp, (c0[g], c0[g]), (V, V))
            return lax.dynamic_update_slice(buf, w_[None], (g, 0, 0))

        wins = lax.fori_loop(
            0, G, gat, jnp.zeros((G, V, V), Xp.dtype))
        wins = jax.vmap(one)(wins, u)
        # windows are pairwise disjoint: G sequential native
        # dynamic_update_slices instead of a general 2-D scatter
        def sca(g, x):
            return lax.dynamic_update_slice(x, wins[g],
                                            (c0[g], c0[g]))

        return lax.fori_loop(0, G, sca, Xp), None

    Xp, _ = lax.scan(step, Xp, (jnp.asarray(c0s), jnp.asarray(us)))
    return Xp


def _sbr_schedule_bidiag(K: int, b: int, w: int, wide: bool):
    """Pipelined step tables for one bidiagonal QR/LQ sweep.

    Panel j (rows [s, s+w), s = j*w) starts at t = 10j; step m = 0 is
    the panel LQ, then chase pairs k: QR at m = 2k-1, LQ at m = 2k,
    both anchored at a = s+w+(k-1)b. With the even delay every time
    step holds a single kind: t odd = QR, t even = LQ. ``wide``
    (M < N): the tail rows [K-w, K) still have excess columns right of
    the diagonal block, so panels run through them (masked to the rows
    that exist)."""
    starts = list(range(0, max(K if wide else K - w, 0), w))
    V = 3 * b + w
    if not starts:
        return None
    M = [1 + 2 * max(0, -(-(K - s - w) // b)) for s in starts]
    Mx = max(M)
    G = -(-Mx // 10) + 1
    T = max(10 * j + M[j] for j in range(len(starts)))
    park0 = K + 3 * b + w
    c0 = np.zeros((T, G), np.int32)
    uu = np.zeros((T, G), np.int32)
    for g in range(G):
        c0[:, g] = park0 + g * V
    off = np.zeros((T, G), np.int32)
    for j, s in enumerate(starts):
        g = j % G
        for m in range(M[j]):
            t = 10 * j + m
            if m == 0:
                # mask rows beyond the matrix (tail panels, wide mode)
                # but keep the column offset at w: with offset u < w the
                # mixed columns still hold band-w content of rows
                # [s+u-w, s) — outside the window (r4 debug)
                c0[t, g], uu[t, g], off[t, g] = s, min(w, K - s), w
            else:
                c0[t, g] = s + w + ((m + 1) // 2 - 1) * b
                uu[t, g], off[t, g] = b, b
    return c0, uu, off, T, G, V, park0


def bidiag_sbr_sweep(X, M: int, N: int, b: int, w: int):
    """One pipelined SBR sweep on an upper-band matrix: band ``b`` ->
    ``w`` (``w <= b//4``) by row-panel LQ + alternating QR/LQ bulge
    chasing (the SVD twin of :func:`herm_sbr_sweep`; replaces the
    reference's sequential stage-2 schedule,
    tests/testing_zgesvd.c:106-145 via zgbbrd). ``X`` dense-stored
    logical ``M x N``, upper bandwidth ``<= b``."""
    from dplasma_tpu.kernels import householder as hh
    assert 1 <= w <= b // 4 or (b <= 4 and w == 1), (b, w)
    K = min(M, N)
    sched = _sbr_schedule_bidiag(K, b, w, M < N)
    if sched is None or K <= 1 or b <= 1:
        return X
    c0s, us, offs, T, G, V, park0 = sched
    Mp, Np = X.shape
    lim = park0 + G * V
    Xp = X
    if lim > Mp or lim > Np:
        Xp = jnp.zeros((max(lim, Mp), max(lim, Np)),
                       X.dtype).at[:Mp, :Np].set(X)

    brows = jnp.arange(b)

    def qr_one(win, u, off):
        del u, off
        blk = win[:b, :b]
        _, v, tT = hh.geqrt(blk)
        rows = hh.apply_q(v, tT, win[:b, :], trans="C")
        return win.at[:b, :].set(rows)

    def lq_one(win, u, off):
        blk = lax.dynamic_slice(win, (jnp.zeros_like(off), off),
                                (b, b))
        blk = jnp.where((brows < u)[:, None], blk, 0)
        _, v, tT = hh.geqrt(blk.conj().T)
        cols = lax.dynamic_slice(win, (jnp.zeros_like(off), off),
                                 (V, b))
        cols = hh.apply_q_right(v, tT, cols, trans="N")
        return lax.dynamic_update_slice(win, cols,
                                        (jnp.zeros_like(off), off))

    def step(Xp, tc):
        c0, u, off, is_qr = tc

        def gat(g, buf):
            w_ = lax.dynamic_slice(Xp, (c0[g], c0[g]), (V, V))
            return lax.dynamic_update_slice(buf, w_[None], (g, 0, 0))

        wins = lax.fori_loop(
            0, G, gat, jnp.zeros((G, V, V), Xp.dtype))
        wins = lax.cond(is_qr, jax.vmap(qr_one), jax.vmap(lq_one),
                        wins, u, off)

        def sca(g, x):
            return lax.dynamic_update_slice(x, wins[g],
                                            (c0[g], c0[g]))

        return lax.fori_loop(0, G, sca, Xp), None

    kinds = jnp.asarray((np.arange(T) % 2) == 1)
    Xp, _ = lax.scan(step, Xp,
                     (jnp.asarray(c0s), jnp.asarray(us),
                      jnp.asarray(offs), kinds))
    return Xp[:Mp, :Np] if (lim > Mp or lim > Np) else Xp


def bidiag_band_to_bidiag_scan(X, M: int, N: int, b: int):
    """Upper-band -> bidiagonal by successive :func:`bidiag_sbr_sweep`
    quarter-width sweeps. Returns (|d|, |e|) with the same tail
    contract as :func:`bidiag_band_to_bidiag`."""
    bb = b
    while bb > 1:
        w = max(1, bb // 4)
        X = bidiag_sbr_sweep(X, M, N, bb, w)
        bb = w
    K = min(M, N)
    ne = K if (M < N and K >= 1) else max(K - 1, 0)
    d = jnp.abs(jnp.diagonal(X))[:K]
    e = jnp.abs(jnp.diagonal(X, offset=1))[:ne]
    return d, e


# ---------------------------------------------------------------------
# Band-storage pipelined SBR: the step-IO rewrite.
#
# On the dense layout each scan step paid a G-way window gather +
# scatter (0.5-9 ms of general-scatter cost per step — measured r4).
# On column-aligned band storage the active window anchors at time t
# are EXACTLY arithmetic in the slot index (with panel stagger delta:
# a(t, j) = t*b - j*(delta*b - w) + w - b; this schedule runs
# delta = 4), so the G windows live at uniform stride
# S = delta*b - w and batched IO is ONE dynamic_slice + reshape. Inside a window, matrix
# rows/columns shear-align with pad+reshape (native ops), making the
# QR block and both strips STATIC slices of the sheared view:
#   Y[g, t', D + rr] = A[c0 + rr, c0 + t']   (rr = row - anchor)
#   block  = Y[:, :b, D+b : D+2b]        (mask cols t' < b - u)
#   rows   = Y[:, :V, D+b : D+2b]        (left compact-WY apply)
#   cols   = Y[:, b:2b, D : D+V]         (right apply; final values)
# Both panel (u = w) and chase (u = b) steps share this geometry when
# the panel window anchors at s - (b - w); inactive slots carry u = 0
# whose empty column mask makes the step an exact identity.
# ---------------------------------------------------------------------

def _shear_fwd(Wt, H: int):
    """Y[g, t, k] = Wt[g, t, k - t] (zero where k - t outside [0, H));
    Wt (G, S, H) -> (G, S, H + S - 1)."""
    G, S, _ = Wt.shape
    Wp = jnp.pad(Wt, ((0, 0), (0, 0), (0, S)))          # width H + S
    flat = Wp.reshape(G, S * (H + S))
    return flat[:, :S * (H + S - 1)].reshape(G, S, H + S - 1)


def _shear_bwd(Y, H: int):
    """Inverse of :func:`_shear_fwd`: Wt[g, t, h] = Y[g, t, h + t]."""
    G, S, Wsh = Y.shape                                  # Wsh = H+S-1
    flat = Y.reshape(G, S * Wsh)
    flat = jnp.pad(flat, ((0, 0), (0, S)))
    return flat.reshape(G, S, Wsh + 1)[:, :, :H]


def _sbr_banded_schedule(N: int, b: int, w: int, delta: int = 4):
    """base (T,), u (T, G) for the band-layout sweep; plus geometry.

    ``delta``: panel-start stagger in steps. Slot windows are
    structurally disjoint on band storage (contiguous S-strided
    slabs), so delta is bounded only by the data dependency — panel
    j+1's columns are restored to band b by panel j's FIRST chase
    step, delta-1 steps earlier — and by S = delta*b - w >= V, i.e.
    delta=4 needs w <= b/2 (the ladder uses b/4). The dense-layout
    sweep needs delta=5 for its window-overlap proof."""
    starts = list(range(0, max(N - w - 1, 0), w))
    if not starts:
        return None
    assert delta * b - w >= 3 * b + w, (b, w, delta)
    P = len(starts)
    M = [1 + max(0, -(-(N - s - w) // b) - 1) for s in starts]
    Mx = max(M)
    S = delta * b - w
    V = 3 * b + w
    G = -(-Mx // delta) + 1
    T = max(delta * j + M[j] for j in range(P))
    base = np.zeros(T, np.int64)
    uu = np.zeros((T, G), np.int32)
    for t in range(T):
        jmax = min(t // delta, P - 1)
        base[t] = t * b - jmax * S + (w - b)
        for g in range(G):
            j = jmax - g
            if j < 0:
                continue
            m = t - delta * j
            if 0 <= m < M[j]:
                uu[t, g] = w if m == 0 else b
    L0 = int(max(0, -base.min()))
    hi = int(base.max()) + G * S
    return base, uu, T, G, S, V, L0, hi


def _band_full(X, N: int, D: int, L0: int, Nc: int):
    """Full-band COLUMN-MAJOR band storage from dense:
    F[L0 + c, D + (r-c)] = X[r, c] for |r - c| <= D (columns lead so
    the sweep's strided slab slice needs no transposes)."""
    c = jnp.arange(N)[:, None]
    k = jnp.arange(-D, D + 1)[None, :]
    r = c + k
    valid = (r >= 0) & (r < N)
    body = jnp.where(valid, X[r.clip(0, N - 1), c.clip(0, N - 1)], 0)
    F = jnp.zeros((Nc, 2 * D + 1), X.dtype)
    return jax.lax.dynamic_update_slice(F, body, (L0, 0))


def herm_sbr_sweep_banded(F, N: int, b: int, w: int, D: int, L0: int,
                          sched=None):
    """One pipelined SBR sweep on full-band storage ``F``
    ((Nc, 2D+1) column-major, D >= 2b + w, logical col c at row
    L0 + c). Band b -> w.
    ``sched``: a precomputed :func:`_sbr_banded_schedule` (the ladder
    passes its own — the O(T*G) Python build is tens of millions of
    iterations for the narrow rungs at large N, not worth doubling).
    Returns the swept F (same shape/geometry)."""
    from dplasma_tpu.kernels import householder as hh
    if sched is None:
        sched = _sbr_banded_schedule(N, b, w)
    if sched is None or N <= 2 or b <= 1:
        return F
    base, uu, T, G, S, V, L0_need, hi = sched
    H = F.shape[1]
    assert D >= 2 * b + w and H == 2 * D + 1
    assert L0 >= L0_need and L0 + hi <= F.shape[0], (L0, hi, F.shape)
    Dc = D                                  # center row of F
    bcols = jnp.arange(b)

    def one(Y, u):
        """Process one sheared window Y (S, H + S - 1)."""
        blk = Y[:b, Dc + b:Dc + 2 * b].T                 # (i, t')
        # elimination columns (t' in [b-u, b)) must sit LEFTMOST for
        # the QR's below-diagonal contract: roll them to [0, u) — the
        # wrapped-in columns are the masked zeros. The reflectors act
        # on ROWS, so everything downstream is column-order blind.
        blk = jnp.where((bcols >= b - u)[None, :], blk, 0)
        blk = jnp.roll(blk, u - b, axis=1)
        _, v, tT = hh.geqrt(blk)
        R = Y[:V, Dc + b:Dc + 2 * b].T                   # (b=i, V=t')
        R1 = hh.apply_q(v, tT, R, trans="C")
        # col strip: unchanged rows are the Hermitian mirror of the
        # ORIGINAL strip; mixed rows carry the left-updated block
        # UNTRANSPOSED — Q^H A is not Hermitian, C1[b+x, i] =
        # A1[c0+b+x, c0+b+i] = R1[x, b+i] directly (r4 debug)
        C1 = jnp.conj(R).T                               # (V, b)
        C1 = C1.at[b:2 * b, :].set(R1[:, b:2 * b])
        C2 = hh.apply_q_right(v, tT, C1, trans="N")
        R2 = R1.at[:, b:2 * b].set(C2[b:2 * b, :])
        Y = Y.at[:V, Dc + b:Dc + 2 * b].set(R2.T)
        Y = Y.at[b:2 * b, Dc:Dc + V].set(C2.T)
        return Y

    def step(F, tc):
        bs, u = tc
        # column-major band storage: the G stride-S window slabs are
        # one contiguous row range — slice + reshape, NO transposes
        blk = jax.lax.dynamic_slice(
            F, (bs, jnp.zeros_like(bs)), (G * S, H))     # ONE slice
        Wt = blk.reshape(G, S, H)
        Y = _shear_fwd(Wt, H)
        Y = jax.vmap(one)(Y, u)
        Wt = _shear_bwd(Y, H)
        return jax.lax.dynamic_update_slice(
            F, Wt.reshape(G * S, H), (bs, jnp.zeros_like(bs))), None

    bases = jnp.asarray(base + L0, jnp.int32)
    F, _ = jax.lax.scan(step, F, (bases, jnp.asarray(uu)))
    return F


def herm_band_to_tridiag_scan(X, N: int, b: int):
    """Band -> tridiagonal by successive pipelined SBR sweeps
    (b -> b//4 -> ... -> 1) on band storage (see the section comment:
    all step IO is native slice+reshape). Returns (d, e) real."""
    if N <= 2 or b <= 1:
        body = X[:N, :N]
        d = jnp.real(jnp.diagonal(body))
        rdt = d.dtype
        e = (jnp.abs(jnp.diagonal(body, offset=-1)).astype(rdt)
             if N > 1 else jnp.zeros((0,), rdt))
        return d, e
    ws = []
    bb = b
    while bb > 1:
        w_ = max(1, bb // 4)
        ws.append((bb, w_))
        bb = w_
    F = None
    D = L0 = 0
    for (bs_, ws_) in ws:
        sched = _sbr_banded_schedule(N, bs_, ws_)
        if sched is None:
            continue
        _, _, _, G_, S_, _, L0n, hin = sched
        Dn = 2 * bs_ + ws_
        Ncn = L0n + max(hin, N) + S_
        if F is None:
            F = _band_full(X, N, Dn, L0n, Ncn)
        else:
            # re-center the band into the new (smaller) geometry
            body = jax.lax.dynamic_slice(
                F, (L0, D - Dn), (N, 2 * Dn + 1))
            F = jnp.zeros((Ncn, 2 * Dn + 1), F.dtype)
            F = jax.lax.dynamic_update_slice(F, body, (L0n, 0))
        D, L0 = Dn, L0n
        F = herm_sbr_sweep_banded(F, N, bs_, ws_, D, L0, sched=sched)
    d = jnp.real(F[L0:L0 + N, D])
    rdt = d.dtype
    e = jnp.abs(F[L0:L0 + N - 1, D + 1]).astype(rdt)
    return d, e


# ---------------------------------------------------------------------
# Blocked SBR on band storage (stage 2, wide bands)
# ---------------------------------------------------------------------

def to_lower_band(X, D: int, N: int, margin: int = 0):
    """Column-aligned lower-band storage from a dense (Hermitian) array:
    S[k, c] = X[c + k, c] for k in [0, D). O(N*D) memory — the band
    working set of the stage-2 sweeps (ref zhbrdt.jdf operates on the
    band object; SURVEY §5.7). ``margin`` adds zero columns so windowed
    sweeps never clip."""
    Nc = N + margin
    c = jnp.arange(Nc)[None, :]
    k = jnp.arange(D)[:, None]
    r = c + k
    valid = (r < min(N, X.shape[0])) & (c < min(N, X.shape[1]))
    return jnp.where(valid, X[r.clip(0, X.shape[0] - 1),
                              c.clip(0, X.shape[1] - 1)], 0)


def lower_band_to_dense(S, N: int):
    """Inverse of :func:`to_lower_band` (lower triangle only)."""
    D = S.shape[0]
    out = jnp.zeros((N, N), S.dtype)
    r = jnp.arange(N)[:, None]
    c = jnp.arange(N)[None, :]
    k = r - c
    valid = (k >= 0) & (k < D)
    return jnp.where(valid, S[k.clip(0, D - 1), c.clip(0, S.shape[1] - 1)],
                     0)


def herm_band_to_tridiag_banded(S, N: int, b: int):
    """Band -> tridiagonal bulge chase on O(N·b) *full-band* storage
    (both triangles, col-aligned): the same scan-compiled Givens chase
    as :func:`herm_band_to_tridiag`, with the dense row/column strips
    replaced by band-array strips. Every rotation acts at a fixed
    geometry relative to its own (i-1)-centred window, so the strip
    indices into the window are STATIC — each step is one
    dynamic_slice + static gathers. ``S`` is lower storage (>= b+1
    rows); returns (d, e) real."""
    if N <= 2 or b <= 1:
        d = jnp.real(S[0, :N])
        e = jnp.abs(S[1, :N - 1]) if N > 1 else \
            jnp.zeros((0,), jnp.real(S).dtype)
        return d, e
    sched = herm_chase_schedule(N, b)
    D = b + 2                      # band + bulge margin
    L = 2 * D + 2
    P = D + 1
    # full-band col-aligned storage F[D + off, c] = X[c + off, c] for
    # off in [-D, D], with P zero columns of margin on both sides
    H = 2 * D + 1
    Nc = N + 2 * P
    F = jnp.zeros((H, Nc), S.dtype)
    nk = min(D + 1, S.shape[0])
    F = F.at[D + jnp.arange(nk), P:P + N].set(S[:nk, :N])  # lower+diag
    for kk in range(1, nk):        # upper mirror: X[c-k, c]=conj(S[k,c-k])
        F = F.at[D - kk, P + kk:P + N].set(jnp.conj(S[kk, :N - kk]))

    # static strip geometry relative to the window at columns
    # [c0, c0+L), c0 = i-1-D:  row r=i-1+dr at col c0+t sits at band row
    # D + (i-1+dr) - (c0+t) = 2D + dr - t; col c=i-1+dc at row c0+t sits
    # at band row t - 1 - ... = D + (c0+t) - (i-1+dc) = t - dc.
    tL = np.arange(L)
    idx_r0 = 2 * D - tL
    idx_r1 = 2 * D + 1 - tL
    idx_cA = tL                    # col i-1 strip over rows [c0, c0+L)
    idx_cB = tL - 1                # col i strip
    ok_r0 = (idx_r0 >= 0) & (idx_r0 < H)
    ok_r1 = (idx_r1 >= 0) & (idx_r1 < H)
    ok_cA = (idx_cA >= 0) & (idx_cA < H)
    ok_cB = (idx_cB >= 0) & (idx_cB < H)
    j_r0 = jnp.asarray(idx_r0.clip(0, H - 1))
    j_r1 = jnp.asarray(idx_r1.clip(0, H - 1))
    j_cA = jnp.asarray(idx_cA.clip(0, H - 1))
    j_cB = jnp.asarray(idx_cB.clip(0, H - 1))
    tj = jnp.arange(L)

    def step(F, ic):
        i, c = ic[0] + P, ic[1] + P
        f = F[D + (i - 1) - c, c]
        g = F[D + i - c, c]
        cs, sn = _lartg(f, g)
        c0 = i - 1 - D
        # rows (i-1, i): A <- G A on the window's anti-diagonals
        win = lax.dynamic_slice(F, (jnp.zeros_like(c0), c0), (H, L))
        r0 = jnp.where(ok_r0, win[j_r0, tj], 0)
        r1 = jnp.where(ok_r1, win[j_r1, tj], 0)
        n0 = cs * r0 + sn * r1
        n1 = -jnp.conj(sn) * r0 + cs * r1
        win = win.at[j_r0, tj].set(jnp.where(ok_r0, n0, win[j_r0, tj]))
        win = win.at[j_r1, tj].set(jnp.where(ok_r1, n1, win[j_r1, tj]))
        F = lax.dynamic_update_slice(F, win, (jnp.zeros_like(c0), c0))
        # cols (i-1, i): A <- A G^H on the columns' contiguous offsets
        win2 = lax.dynamic_slice(F, (jnp.zeros_like(c0), i - 1), (H, 2))
        sA = jnp.where(ok_cA, win2[j_cA, 0], 0)
        sB = jnp.where(ok_cB, win2[j_cB, 1], 0)
        nA = cs * sA + jnp.conj(sn) * sB
        nB = -sn * sA + cs * sB
        win2 = win2.at[j_cA, 0].set(jnp.where(ok_cA, nA, win2[j_cA, 0]))
        win2 = win2.at[j_cB, 1].set(jnp.where(ok_cB, nB, win2[j_cB, 1]))
        F = lax.dynamic_update_slice(F, win2, (jnp.zeros_like(c0), i - 1))
        return F, None

    F, _ = lax.scan(step, F, jnp.asarray(sched))
    d = jnp.real(F[D, P:P + N])
    e = jnp.abs(F[D + 1, P:P + N - 1])
    return d, e


# ---------------------------------------------------------------------
# Upper-bidiagonal band -> bidiagonal
# ---------------------------------------------------------------------

def bidiag_chase_schedule(M: int, N: int, b: int) -> np.ndarray:
    """Schedule (K, 3) of (side, i, c): side 0 = column rotation on
    columns (i-1, i) zeroing A[c, i]; side 1 = row rotation on rows
    (i-1, i) zeroing A[i, c]."""
    steps = []
    K = min(M, N)
    for s in range(K):
        for j in range(min(b, N - 1 - s), 1, -1):
            # col rotation kills A[s, s+j]; alternating chase
            q, c = s + j, s
            while True:
                steps.append((0, q, c))          # cols (q-1, q) zero A[c, q]
                if q >= M:                        # row q does not exist
                    break
                steps.append((1, q, q - 1))       # rows (q-1, q) zero A[q, q-1]
                c, q = q - 1, q + b               # fill at (q-1, q-1+b+1)
                if q >= N:
                    break
    if not steps:
        return np.zeros((0, 3), dtype=np.int32)
    return np.asarray(steps, dtype=np.int32)


def bidiag_band_to_bidiag(X, M: int, N: int, b: int):
    """Reduce a dense-stored upper-band matrix (upper bandwidth b,
    zero below the diagonal, logical M×N) to upper bidiagonal.
    Returns (d, e) with |diagonal| and |superdiagonal|. When M < N the
    reduced form keeps a legitimate tail entry A[M-1, M] and ``e`` has
    length K (not K-1) — the Golub-Kahan tridiagonal of such a
    K×(K+1) bidiagonal simply interleaves all 2K entries (see
    ``eig.gesvd``)."""
    K = min(M, N)
    ne = K if (M < N and K >= 1) else K - 1
    rdt = jnp.zeros((), X.dtype).real.dtype
    if K == 0:
        return jnp.zeros((0,), rdt), jnp.zeros((0,), rdt)
    if b <= 1 or K == 1:
        d = jnp.abs(jnp.diagonal(X))[:K]
        e = jnp.abs(jnp.diagonal(X, offset=1))[:max(ne, 0)]
        return d, e
    sched = bidiag_chase_schedule(M, N, b)
    D = b + 2
    L = 2 * D + 2
    P = D + 1
    Xp = jnp.zeros((M + 2 * P, N + 2 * P), X.dtype)
    Xp = Xp.at[P:P + M, P:P + N].set(X[:M, :N])

    def step(Xp, sic):
        side, i, c = sic[0], sic[1], sic[2]

        def col_rot(Xp):
            # zero A[c, i] against A[c, i-1]: mix columns (i-1, i).
            # Right-side application needs the conjugated lartg so the
            # second column -sn·f + cs·g vanishes for complex entries.
            f = Xp[c + P, i - 1 + P]
            g = Xp[c + P, i + P]
            cs, sn = _lartg(jnp.conj(f), jnp.conj(g))
            r0 = i - 1 - D + P
            C = lax.dynamic_slice(Xp, (r0, i - 1 + P), (L, 2))
            Cn = jnp.stack([cs * C[:, 0] + jnp.conj(sn) * C[:, 1],
                            -sn * C[:, 0] + cs * C[:, 1]], axis=1)
            return lax.dynamic_update_slice(Xp, Cn, (r0, i - 1 + P))

        def row_rot(Xp):
            # zero A[i, c] against A[i-1, c]: mix rows (i-1, i)
            f = Xp[i - 1 + P, c + P]
            g = Xp[i + P, c + P]
            cs, sn = _lartg(f, g)
            c0 = i - 1 - D + P
            R = lax.dynamic_slice(Xp, (i - 1 + P, c0), (2, L))
            Rn = jnp.stack([cs * R[0] + sn * R[1],
                            -jnp.conj(sn) * R[0] + cs * R[1]])
            return lax.dynamic_update_slice(Xp, Rn, (i - 1 + P, c0))

        Xp = lax.cond(side == 0, col_rot, row_rot, Xp)
        return Xp, None

    Xp, _ = lax.scan(step, Xp, jnp.asarray(sched))
    body = Xp[P:P + M, P:P + N]
    d = jnp.abs(jnp.diagonal(body))[:K]
    e = jnp.abs(jnp.diagonal(body, offset=1))[:ne]
    return d, e
