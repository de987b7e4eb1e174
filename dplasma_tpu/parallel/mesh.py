"""Device mesh management and sharding helpers.

The reference's process grid (P×Q ranks, ``parsec_init`` + MPI — ref
tests/common.c:640-723) becomes a ``jax.sharding.Mesh`` with axes
``('p', 'q')`` laid out over ICI. Matrix distribution = NamedSharding of
the padded global array; GSPMD inserts the collectives the reference's
comm engine derived from JDF ``type_remote`` annotations
(ref src/zpotrf_L.jdf:109-114).

A module-level "active grid" context plays the role of the reference's
global ``dplasma_pcomm`` communicator (ref src/dplasmaaux.c:31-43):
ops consult it to place sharding constraints; with no active grid all
constraints are no-ops (single-device execution).
"""
from __future__ import annotations

import contextlib
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ACTIVE: Optional[Mesh] = None

ROW_AXIS = "p"
COL_AXIS = "q"


def make_mesh(P_: int, Q_: int, devices: Optional[Sequence] = None) -> Mesh:
    """Create a P×Q mesh (row-major over the device list)."""
    devs = list(devices) if devices is not None else jax.devices()
    if P_ * Q_ > len(devs):
        raise ValueError(f"need {P_ * Q_} devices, have {len(devs)}")
    arr = np.array(devs[: P_ * Q_]).reshape(P_, Q_)
    return Mesh(arr, (ROW_AXIS, COL_AXIS))


def square_grid(n: int) -> tuple[int, int]:
    """Pick (P, Q) with P*Q == n, as square as possible, P <= Q — the
    reference drivers' default grid heuristic."""
    p = int(math.isqrt(n))
    while n % p:
        p -= 1
    return p, n // p


def active() -> Optional[Mesh]:
    return _ACTIVE


@contextlib.contextmanager
def use_grid(mesh: Optional[Mesh]):
    """Activate a mesh for the dynamic extent (analog of establishing the
    process grid at ``parsec_init``). On a multi-device grid the dd
    engine's Pallas recombine is off (MCA ``dd_epilogue=off``): GSPMD
    cannot partition a Mosaic kernel, so the grid takes the XLA
    recombine."""
    from dplasma_tpu.utils import config as _cfg
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = mesh
    kv = {"dd_epilogue": "off"} if mesh is not None and mesh.size > 1 \
        else {}
    try:
        with _cfg.override_scope(kv, label="use_grid"):
            yield mesh
    finally:
        _ACTIVE = prev


def sharding2d(mesh: Optional[Mesh] = None) -> Optional[NamedSharding]:
    """Row/column 2-D sharding for a matrix over the active mesh."""
    m = mesh or _ACTIVE
    if m is None:
        return None
    return NamedSharding(m, P(ROW_AXIS, COL_AXIS))


def constrain2d(x: jax.Array, mesh: Optional[Mesh] = None) -> jax.Array:
    """Apply a (rows→'p', cols→'q') sharding constraint if a grid is
    active and divides the shape; otherwise a no-op."""
    s = sharding2d(mesh)
    if s is None:
        return x
    m = mesh or _ACTIVE
    pr = m.shape[ROW_AXIS]
    qc = m.shape[COL_AXIS]
    if x.ndim != 2 or x.shape[0] % pr or x.shape[1] % qc:
        return x
    return jax.lax.with_sharding_constraint(x, s)


def constrain_rows(x: jax.Array, mesh: Optional[Mesh] = None) -> jax.Array:
    m = mesh or _ACTIVE
    if m is None or x.ndim < 1 or x.shape[0] % m.shape[ROW_AXIS]:
        return x
    spec = P(ROW_AXIS, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(m, spec))


def device_put2d(x: jax.Array, mesh: Optional[Mesh] = None) -> jax.Array:
    """Place an array with the 2-D sharding (outside jit)."""
    s = sharding2d(mesh)
    if s is None:
        return x
    m = mesh or _ACTIVE
    if x.ndim != 2 or x.shape[0] % m.shape[ROW_AXIS] or x.shape[1] % m.shape[COL_AXIS]:
        return x
    return jax.device_put(x, s)
