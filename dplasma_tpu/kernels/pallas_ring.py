"""Explicit ICI ring transfers: Pallas async-remote-copy kernels.

The cyclic shard_map kernels (:mod:`dplasma_tpu.parallel.cyclic`)
historically emulated their panel broadcast with a masked ``psum`` —
an all-reduce that moves ``2(n-1)/n`` of the payload per rank to
implement a broadcast that only needs to cross each link once. This
module provides the explicit alternative the ROADMAP names (SNIPPETS
[3], pltpu.make_async_remote_copy): ring transfers over ICI expressed
as Pallas kernels, so the transfer schedule is *ours* — started before
the local wide matmul by the lookahead carry, waited on only at the
consume point — instead of XLA's.

Two kernels ship:

* :func:`ring_bcast` — panel broadcast along one mesh axis as a
  chunked store-and-forward ring: the owner seeds its output buffer
  and starts the send of chunk 0 down the ring; every other rank
  waits for a chunk to land and forwards it immediately, so chunk c+1
  streams into a rank while it forwards chunk c (pipelined hops).
  Wire cost: each link carries the payload ONCE — half the masked
  psum's all-reduce bytes.
* :func:`ring_shift` — the canonical neighbor shift (every rank sends
  its buffer to ``(r+1) % n``, receives from ``(r-1) % n``); the
  building block of :func:`ring_allreduce`, the cyclic LU's
  winner-row exchange (n-1 shift-and-add steps — latency-optimized
  for the small mesh axes the factorizations run on, trading
  ``(n-1)`` payload sends per rank for n-1 single-hop steps).

Execution surface (honest limits):

* **TPU (Mosaic)**: both kernels lower; this is the production path.
* **CPU interpret mode**: jax's interpret-mode DMA discharge executes
  only *uniform* single-hop programs on a *single*-named-axis mesh —
  :func:`ring_shift` runs (and is round-trip tested on a 1x4 ring in
  tests/test_pallas_ring.py); the store-and-forward bcast's
  rank-conditional waits would deadlock the lockstep interpreter, so
  on CPU the bcast is verified structurally instead: its abstract
  send/wait schedule (:func:`bcast_program`) must drain in
  :func:`dplasma_tpu.analysis.spmdcheck.simulate_ring`, its traced
  collective counts must reconcile exactly (spmdcheck recognizes the
  named pallas_call sites), and its pallas contract is
  palcheck-registered. ``ring.enable=auto`` therefore activates only
  on a TPU backend; CPU always falls back to the psum path.

Every kernel's abstract schedule is exported as a
:class:`~dplasma_tpu.analysis.spmdcheck.RingOp` program
(:func:`bcast_program` / :func:`shift_program` /
:func:`allreduce_program`); ``tools/lint_all.py``'s ``ring-smoke``
gate simulates them all before any hardware ever runs one.
"""
from __future__ import annotations

import sys
from typing import Dict, List, Optional, Tuple

from dplasma_tpu.utils import config as _cfg

_cfg.mca_register(
    "ring.enable", "auto",
    "Explicit ICI ring transfers in the cyclic factorization kernels "
    "(panel-broadcast ring + LU winner-row exchange ring, "
    "kernels/pallas_ring.py): off = the masked-psum path "
    "(bit-identical to the pre-ring kernels), on = force the ring "
    "kernels where the runtime probe passes (falls back with a "
    "warning where it cannot — CPU backends, unsupported dtypes), "
    "auto = on only when the runtime probe AND the 1-D/torus "
    "mesh-geometry gate both pass (TPU backend, ring-connected mesh "
    "axis); CPU always falls back.")
_cfg.mca_register(
    "ring.chunks", "4",
    "Pipelining depth of the panel-broadcast ring: the panel is "
    "forwarded in this many chunks so a rank streams chunk c+1 in "
    "while it forwards chunk c (clamped to a divisor of the panel "
    "rows; 1 = store-and-forward whole panels).")

#: pallas_call name prefix the verifiers key on: spmdcheck counts
#: ``dplasma_ring_{bcast|shift}_{axis}`` sites as explicit ring
#: collectives, hlocheck counts the Mosaic custom-calls carrying it
RING_NAME_PREFIX = "dplasma_ring_"

_warned: set = set()


def _warn_once(key: str, msg: str) -> None:
    if key not in _warned:
        _warned.add(key)
        sys.stderr.write(f"#! {msg}\n")


# ---------------------------------------------------------------------
# Runtime probe + mesh-geometry gate
# ---------------------------------------------------------------------

def ring_runtime_ok() -> bool:
    """Can the ring kernels actually execute here? Mosaic lowering of
    the remote-DMA primitives only exists on a TPU backend (interpret
    mode executes single-axis uniform shifts only — the test surface,
    not the production one)."""
    import jax
    return jax.default_backend() == "tpu"


def ring_geometry_ok(mesh, axis: str) -> bool:
    """1-D/torus gate: the ranks along ``axis`` must be physically
    ring-connected for neighbor transfers to ride single ICI hops.
    Best-effort from device coordinates: consecutive devices along
    the mesh axis must differ in exactly one hardware coordinate by
    ±1 (mod the torus extent). Devices without coordinate metadata
    (CPU/interpret, older runtimes) pass — the runtime probe is the
    binding gate there."""
    try:
        import numpy as np
        axes = list(mesh.axis_names)
        devs = np.asarray(mesh.devices)
        ax = axes.index(axis)
    except (ValueError, AttributeError):
        return True
    n = devs.shape[ax]
    if n <= 1:
        return False
    # walk every line of devices along the axis: consecutive hops
    # must be ±1 in exactly one hardware coordinate; the CLOSING hop
    # (last -> first) may additionally be the torus wraparound when
    # the line covers the full contiguous extent of that coordinate.
    # The extent is inferred from the participating devices only, so
    # a strict ±1 rule on the interior hops is what keeps a sparse
    # subset (e.g. chips 0 and 2 of a 4-torus — two real hops apart)
    # from masquerading as ring-connected.
    lines = np.moveaxis(devs, ax, -1).reshape(-1, n)
    for line in lines:
        coords = [getattr(d, "coords", None) for d in line]
        if any(c is None for c in coords):
            continue            # no metadata: trust the runtime probe
        dims = [max(c[i] for c in coords) + 1
                for i in range(len(coords[0]))]
        pairs = list(zip(coords, coords[1:] + [coords[0]]))
        for j, (a, b) in enumerate(pairs):
            diff = [i for i in range(len(a)) if a[i] != b[i]]
            if len(diff) != 1:
                return False
            i = diff[0]
            if abs(b[i] - a[i]) == 1:
                continue
            closing = (j == len(pairs) - 1)
            vals = sorted(c[i] for c in coords)
            full = vals == list(range(dims[i]))
            if not (closing and full
                    and (b[i] - a[i]) % max(dims[i], 1)
                    in (1, dims[i] - 1)):
                return False
    return True


_RING_DTYPES = ("float32", "bfloat16")


def ring_active(axis_size: int, dtype=None, mesh=None,
                axis: Optional[str] = None) -> bool:
    """Resolve MCA ``ring.enable`` for one broadcast/exchange axis.

    ``off`` → False (the masked-psum path, bit-identical). ``on`` →
    True wherever the runtime probe passes (a failed probe falls back
    with a one-time warning — a forced knob must not brick a CPU
    run). ``auto`` → True only when the runtime probe AND the mesh
    geometry gate pass; CPU always falls back. An axis of size 1
    never rings (there is no wire). An unrecognized mode warns once
    and resolves as ``auto`` — a typo must not silently force the
    ring past the geometry gate."""
    mode = (_cfg.mca_get("ring.enable") or "auto").lower()
    if mode not in ("auto", "on", "off"):
        _warn_once(f"mode:{mode}",
                   f"ring.enable={mode!r} is not one of auto/on/off; "
                   f"treating as auto")
        mode = "auto"
    if mode == "off" or axis_size <= 1:
        return False
    if dtype is not None:
        import numpy as np
        try:
            name = np.dtype(dtype).name
        except TypeError:
            name = str(dtype)
        if name not in _RING_DTYPES:
            if mode == "on":
                _warn_once(f"dtype:{name}",
                           f"ring.enable=on: dtype {name} has no "
                           f"ring kernel (pallas TPU reals only); "
                           f"falling back to the psum path")
            return False
    if not ring_runtime_ok():
        if mode == "on":
            _warn_once("runtime",
                       "ring.enable=on: runtime probe failed (no TPU "
                       "Mosaic lowering for remote DMA here); "
                       "falling back to the psum path")
        return False
    if mode == "auto" and mesh is not None and axis is not None \
            and not ring_geometry_ok(mesh, axis):
        return False
    return True


# ---------------------------------------------------------------------
# In-kernel helpers
# ---------------------------------------------------------------------

def _neighbor_logical(axes: Tuple[Tuple[str, int], ...], axis: str,
                      step: int = 1):
    """Logical (row-major flattened) device id of the neighbor
    ``step`` hops along ``axis``, computed from the live axis indices
    of the enclosing shard_map mesh (``axes`` = its (name, size)
    pairs in order)."""
    import jax.numpy as jnp
    from jax import lax
    lid = None
    for name, size in axes:
        # axis_index is i32; pin the literals so x64 mode cannot
        # promote one operand and break the stablehlo verifier
        i = lax.axis_index(name)
        if name == axis:
            i = lax.rem(i + jnp.int32(step), jnp.int32(size))
        lid = i if lid is None else lid * jnp.int32(size) + i
    return lid


def _resolve_chunks(rows: int, chunks: Optional[int]) -> int:
    c = chunks if chunks is not None \
        else _cfg.mca_get_int("ring.chunks", 4)
    c = max(int(c), 1)
    while c > 1 and rows % c:
        c -= 1
    return c


def _interpret_default() -> bool:
    import jax
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------

def ring_bcast(x, *, root: int, axis: str,
               axes: Tuple[Tuple[str, int], ...],
               chunks: Optional[int] = None,
               interpret: Optional[bool] = None):
    """Broadcast rank ``root``'s 2-D block ``x`` to every rank along
    mesh axis ``axis`` via a chunked store-and-forward DMA ring.

    Must be called inside a shard_map body whose mesh axes are
    exactly ``axes`` (in order; ``(name, size)`` pairs). Non-root
    ranks' ``x`` is ignored. The per-rank schedule (rank distance d
    from the root, n ranks, C chunks)::

        d == 0   : local-copy chunk c into out; start send c right
        0<d<n-1  : wait recv c;                 start send c right
        d == n-1 : wait recv c                  (consume point)

    then every sender drains its send semaphore — the no-unpaired-
    semaphore contract :func:`bcast_program` pins and simulate_ring
    proves. Each link carries the payload once (wire-optimal); the
    chunking pipelines the hops.
    """
    import jax
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = dict(axes)[axis]
    if n == 1:
        return x
    rows = x.shape[0]
    nchunks = _resolve_chunks(rows, chunks)
    csz = rows // nchunks
    if interpret is None:
        interpret = _interpret_default()

    def kern(in_ref, out_ref, send_sem, recv_sem, local_sem):
        me = lax.axis_index(axis)
        right = _neighbor_logical(axes, axis, 1)
        dist = lax.rem(me - root + n, n)

        def rc(sl):
            return pltpu.make_async_remote_copy(
                src_ref=out_ref.at[sl], dst_ref=out_ref.at[sl],
                send_sem=send_sem, recv_sem=recv_sem,
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)

        for c in range(nchunks):
            sl = pl.ds(c * csz, csz)

            @pl.when(dist == 0)
            def _seed():
                cp = pltpu.make_async_copy(in_ref.at[sl],
                                           out_ref.at[sl], local_sem)
                cp.start()
                cp.wait()

            @pl.when(dist > 0)
            def _recv():
                rc(sl).wait_recv()

            @pl.when(dist < n - 1)
            def _fwd():
                rc(sl).start()
        for c in range(nchunks):
            sl = pl.ds(c * csz, csz)

            @pl.when(dist < n - 1)
            def _drain():
                rc(sl).wait_send()

    # 32-bit trace, as the other kernels: x64 mode would make the
    # rank arithmetic i64 (every payload dtype here is 32-bit or less)
    with jax.enable_x64(False):
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA] * 3,
            interpret=interpret,
            name=f"{RING_NAME_PREFIX}bcast_{axis}")(x)


def ring_shift(x, *, axis: str, axes: Tuple[Tuple[str, int], ...],
               interpret: Optional[bool] = None):
    """One neighbor hop along ``axis``: every rank sends ``x`` to
    ``(r+1) % n`` and returns the block received from ``(r-1) % n``
    (the canonical uniform ring step — interpret-executable, and the
    building block of :func:`ring_allreduce`)."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = dict(axes)[axis]
    if n == 1:
        return x
    if interpret is None:
        interpret = _interpret_default()

    def kern(in_ref, out_ref, send_sem, recv_sem):
        right = _neighbor_logical(axes, axis, 1)
        rcopy = pltpu.make_async_remote_copy(
            src_ref=in_ref, dst_ref=out_ref,
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        rcopy.start()
        rcopy.wait()

    with jax.enable_x64(False):
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA] * 2,
            interpret=interpret,
            name=f"{RING_NAME_PREFIX}shift_{axis}")(x)


def ring_allreduce(x, *, axis: str,
                   axes: Tuple[Tuple[str, int], ...],
                   interpret: Optional[bool] = None):
    """Sum ``x`` across ``axis`` by n-1 shift-and-add ring steps (the
    cyclic LU's winner-row exchange): each rank keeps an accumulator
    and a carry; per step the carry hops one rank right and is added,
    so after n-1 steps every rank holds the full sum. The adds run in
    XLA (VPU/MXU), the hops in the DMA ring; per-rank accumulation
    order is rank-relative (r, r-1, ...), the usual reduction-order
    freedom of a distributed sum."""
    n = dict(axes)[axis]
    acc, carry = x, x
    for _ in range(n - 1):
        carry = ring_shift(carry, axis=axis, axes=axes,
                           interpret=interpret)
        acc = acc + carry
    return acc


# ---------------------------------------------------------------------
# Abstract RingOp programs (the simulate_ring contract)
# ---------------------------------------------------------------------

def bcast_program(n: int, root: int = 0, chunks: int = 1,
                  sem: str = "dma") -> Dict[int, List["object"]]:
    """The per-rank abstract schedule of :func:`ring_bcast`: sends
    signal the destination's recv semaphore, waits drain it, the
    consume point is a compute op. Must drain deadlock-free with no
    unpaired semaphore in :func:`~dplasma_tpu.analysis.spmdcheck.
    simulate_ring` — the shipped kernel's schedule IS this program."""
    from dplasma_tpu.analysis.spmdcheck import compute, send, wait
    progs: Dict[int, list] = {}
    for r in range(n):
        d = (r - root) % n
        right = (r + 1) % n
        left = (r - 1) % n
        ops: list = []
        for _ in range(chunks):
            if d == 0:
                ops.append(compute())          # local seed copy
            else:
                ops.append(wait(left, sem))    # chunk arrives
            if d < n - 1:
                ops.append(send(right, sem))   # forward down the ring
        ops.append(compute())                  # consume point
        progs[r] = ops
    return progs


def shift_program(n: int, steps: int = 1,
                  sem: str = "dma") -> Dict[int, List["object"]]:
    """The per-rank schedule of ``steps`` :func:`ring_shift` hops —
    exactly the canonical neighbor-shift schedule spmdcheck's
    simulator was built against."""
    from dplasma_tpu.analysis.spmdcheck import ring_shift_program
    return ring_shift_program(n, steps, sem)


def allreduce_program(n: int, sem: str = "dma"
                      ) -> Dict[int, List["object"]]:
    """:func:`ring_allreduce`'s schedule: n-1 uniform shift-and-add
    steps."""
    return shift_program(n, max(n - 1, 0), sem)


def kernel_programs(P: int, Q: int) -> Dict[str, Dict[int, list]]:
    """The abstract schedules of every shipped ring kernel as wired
    into the cyclic factorizations on a PxQ grid — what the
    ``ring-smoke`` lint gate (and the spmdcheck goldens) simulate.
    Panel broadcasts ring along 'q' from every possible owner column;
    the LU winner-row exchange rings along 'p'."""
    progs: Dict[str, Dict[int, list]] = {}
    if Q > 1:
        for root in range(Q):
            progs[f"panel_bcast_q{Q}_root{root}"] = \
                bcast_program(Q, root)
            progs[f"panel_bcast_q{Q}_root{root}_chunked"] = \
                bcast_program(Q, root, chunks=4)
    if P > 1:
        progs[f"row_exchange_p{P}"] = allreduce_program(P)
    return progs
