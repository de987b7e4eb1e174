"""Explicit ICI ring kernels (kernels/pallas_ring.py).

Execution coverage runs in interpret mode on a SINGLE-named-axis CPU
mesh — jax's interpret-mode DMA discharge executes uniform one-hop
programs only (the module docstring's honest-limits note), so the
payload round-trip rides :func:`ring_shift` on a simulated 1x4 ring
while the store-and-forward broadcast is verified structurally: its
RingOp schedule must drain in the spmdcheck simulator (goldens in
tests/test_spmdcheck.py), its traced collective counts reconcile
exactly, and its pallas contract is palcheck-registered. The
ring.enable gate's CPU-always-falls-back contract and the mesh
geometry gate are pinned here too.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dplasma_tpu.analysis import spmdcheck as sp
from dplasma_tpu.kernels import pallas_ring as pring
from dplasma_tpu.utils import config

from jax import shard_map


def _mesh1d(n, name="x"):
    return jax.make_mesh((n,), (name,))


# ---------------------------------------------------------------------
# interpret-mode execution: the 1x4 simulated ring
# ---------------------------------------------------------------------

def test_shift_one_hop_moves_payload_right():
    """One ring_shift hop: rank r's block lands on rank (r+1) % 4 —
    the send/wait pairing of the canonical ring step, executed."""
    n, rows, cols = 4, 8, 128
    mesh = _mesh1d(n)
    x = jnp.arange(n * rows * cols, dtype=jnp.float32
                   ).reshape(n * rows, cols)
    f = jax.jit(shard_map(
        lambda a: pring.ring_shift(a, axis="x", axes=(("x", n),),
                                   interpret=True),
        mesh=mesh, in_specs=P("x"), out_specs=P("x"),
        check_vma=False))
    y = np.asarray(f(x))
    xs = np.asarray(x)
    for r in range(n):
        src = (r - 1) % n
        assert np.array_equal(y[r * rows:(r + 1) * rows],
                              xs[src * rows:(src + 1) * rows])


def test_shift_round_trip_on_1x4_ring():
    """Payload round-trip: four hops around the 1x4 ring return every
    rank's block unchanged — the full-circle send/wait pairing."""
    n, rows, cols = 4, 8, 128
    mesh = _mesh1d(n)
    x = jnp.arange(n * rows * cols, dtype=jnp.float32
                   ).reshape(n * rows, cols)

    def body(a):
        for _ in range(n):
            a = pring.ring_shift(a, axis="x", axes=(("x", n),),
                                 interpret=True)
        return a

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("x"),
                          out_specs=P("x"), check_vma=False))
    assert np.array_equal(np.asarray(f(x)), np.asarray(x))


def test_allreduce_matches_sum():
    """The winner-row exchange primitive: the n-1 shift-and-add ring
    sum equals the reduction it replaces (up to the usual f32
    reduction-order rounding on dense data; the LU exchange's
    contributions are disjoint-supported, where it is exact —
    test_allreduce_disjoint_exact below)."""
    n, rows, cols = 4, 8, 128
    mesh = _mesh1d(n)
    rng = np.random.default_rng(3872)
    x = jnp.asarray(rng.standard_normal((n * rows, cols)),
                    dtype=jnp.float32)

    f = jax.jit(shard_map(
        lambda a: pring.ring_allreduce(a, axis="x", axes=(("x", n),),
                                       interpret=True),
        mesh=mesh, in_specs=P("x"), out_specs=P("x"),
        check_vma=False))
    y = np.asarray(f(x))
    want = np.asarray(x).reshape(n, rows, cols).sum(axis=0)
    for r in range(n):
        np.testing.assert_allclose(y[r * rows:(r + 1) * rows], want,
                                   rtol=2e-4, atol=1e-5)


def test_allreduce_disjoint_exact():
    """Disjoint-support contributions (each row nonzero on exactly
    one rank — the winner-row exchange's shape) sum EXACTLY: the ring
    path is bit-identical to the psum path there, every rank."""
    n, rows, cols = 4, 8, 128
    mesh = _mesh1d(n)
    rng = np.random.default_rng(2354)
    full = rng.standard_normal((rows, cols)).astype(np.float32)
    owner = rng.integers(0, n, size=rows)
    x = np.zeros((n * rows, cols), np.float32)
    for i in range(rows):
        x[owner[i] * rows + i] = full[i]

    f = jax.jit(shard_map(
        lambda a: pring.ring_allreduce(a, axis="x", axes=(("x", n),),
                                       interpret=True),
        mesh=mesh, in_specs=P("x"), out_specs=P("x"),
        check_vma=False))
    y = np.asarray(f(jnp.asarray(x)))
    for r in range(n):
        assert np.array_equal(y[r * rows:(r + 1) * rows], full)


def test_neighbor_bijection_on_the_mesh():
    """Every rank's computed right-neighbor logical id is a bijection
    on the axis (the property whose violation strands a rank waiting
    on a send that never comes — spmdcheck's ppermute rule, here for
    the ring kernels' device_id arithmetic)."""
    n = 4
    mesh = _mesh1d(n)

    def body(_):
        nb = pring._neighbor_logical((("x", n),), "x", 1)
        return nb[None]

    f = jax.jit(shard_map(body, mesh=mesh,
                          in_specs=P("x"), out_specs=P("x")))
    ids = np.asarray(f(jnp.zeros((n,), jnp.int32))).tolist()
    assert sorted(ids) == list(range(n))          # bijection
    assert ids == [(r + 1) % n for r in range(n)]  # the +1 ring


# ---------------------------------------------------------------------
# the ring.enable gate
# ---------------------------------------------------------------------

def test_ring_gate_cpu_always_falls_back():
    """CPU backends must resolve to the psum path under every mode
    (the Mosaic remote-DMA lowering only exists on TPU); ``on``
    degrades with a warning rather than bricking the run."""
    if jax.default_backend() == "tpu":
        pytest.skip("gate test targets the CPU fallback")
    for mode in ("off", "auto", "on"):
        with config.override_scope({"ring.enable": mode}):
            assert pring.ring_active(4, "float32") is False


def test_ring_gate_off_and_size1():
    with config.override_scope({"ring.enable": "on"}):
        assert pring.ring_active(1, "float32") is False
    with config.override_scope({"ring.enable": "off"}):
        assert pring.ring_active(4, "float32") is False


def test_ring_gate_dtype():
    """No ring kernel for f64/complex (pallas TPU reals only): the
    gate must fall back rather than hand the kernel an unsupported
    payload."""
    with config.override_scope({"ring.enable": "on"}):
        assert pring.ring_active(4, "float64") is False
        assert pring.ring_active(4, "complex64") is False


class _FakeDev:
    def __init__(self, coords):
        self.coords = coords


def _fake_mesh(devgrid, names):
    class _M:
        pass
    m = _M()
    m.axis_names = names
    m.devices = np.asarray(devgrid, dtype=object)
    return m


def test_geometry_gate_accepts_torus_line():
    """Devices whose coords step by ±1 (mod extent) along the mesh
    axis are ring-connected — the 1-D/torus gate passes."""
    devs = [[_FakeDev((0, i, 0)) for i in range(4)]]
    assert pring.ring_geometry_ok(_fake_mesh(devs, ("p", "q")), "q")


def test_geometry_gate_rejects_scattered_devices():
    """A mesh axis whose neighbors differ in two hardware coords (or
    jump by 2) is not a ring — auto must fall back."""
    devs = [[_FakeDev((0, 0, 0)), _FakeDev((1, 1, 0)),
             _FakeDev((0, 2, 0)), _FakeDev((1, 3, 0))]]
    assert not pring.ring_geometry_ok(_fake_mesh(devs, ("p", "q")),
                                      "q")
    devs2 = [[_FakeDev((0, 0, 0)), _FakeDev((0, 2, 0)),
              _FakeDev((0, 4, 0)), _FakeDev((0, 6, 0))]]
    assert not pring.ring_geometry_ok(_fake_mesh(devs2, ("p", "q")),
                                      "q")


def test_geometry_gate_rejects_sparse_short_line():
    """Two chips at coords 0 and 2 of a larger torus are TWO real ICI
    hops apart — the subset-inferred extent must not let the pair
    masquerade as a wraparound ring (interior hops are strictly ±1;
    wraparound is the closing hop of a full contiguous extent only)."""
    devs = [[_FakeDev((0, 0, 0)), _FakeDev((0, 2, 0))]]
    assert not pring.ring_geometry_ok(_fake_mesh(devs, ("p", "q")),
                                      "q")
    # a genuine 2-ring (coords 0 and 1) still passes
    devs2 = [[_FakeDev((0, 0, 0)), _FakeDev((0, 1, 0))]]
    assert pring.ring_geometry_ok(_fake_mesh(devs2, ("p", "q")), "q")


def test_geometry_gate_no_coords_trusts_runtime_probe():
    devs = [[object(), object()]]
    assert pring.ring_geometry_ok(_fake_mesh(devs, ("p", "q")), "q")


def test_resolve_chunks_divisibility():
    assert pring._resolve_chunks(16, 4) == 4
    assert pring._resolve_chunks(14, 4) == 2   # largest divisor <= 4
    assert pring._resolve_chunks(7, 4) == 1
    assert pring._resolve_chunks(8, None) >= 1


# ---------------------------------------------------------------------
# schedule programs exist for every shipped kernel and drain
# ---------------------------------------------------------------------

def test_kernel_programs_cover_both_kernels_and_drain():
    progs = pring.kernel_programs(2, 4)
    names = set(progs)
    assert any("panel_bcast" in n for n in names)
    assert any("row_exchange" in n for n in names)
    for name, prog in progs.items():
        assert sp.simulate_ring(name, prog) == []


def test_mca_knobs_registered():
    assert config.mca_get("ring.enable") == "auto"
    assert config.mca_get_int("ring.chunks", -1) == 4
    assert "ring.enable" in config.mca_help()


def test_ring_gate_unknown_mode_resolves_as_auto():
    """A typo'd ring.enable must not act as a forced 'on' that skips
    the geometry gate: unknown modes warn once and resolve as auto
    (which on this CPU backend falls back)."""
    for bad in ("true", "yes", "1"):
        with config.override_scope({"ring.enable": bad}):
            assert pring.ring_active(4, "float32") is False
