"""SPMD collective-schedule verification (analysis.spmdcheck).

Golden fixtures: the cyclic shard_map kernels' collective sequences
across 1x1/2x2/1x4 grids and both pipeline shapes reconcile EXACTLY
with the analytic comm model. Mutation tests: each seeded defect
class — dropped psum, rank-divergent cond, collective in a
data-dependent while, asymmetric/bad ppermute, deadlocked or
semaphore-unbalanced ring schedule — is caught with a diagnostic
naming the kernel and the offending collective/step/rank pair (the
same style as tests/test_dagcheck.py one layer up).
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map

from dplasma_tpu.analysis import spmdcheck as sp
from dplasma_tpu.descriptors import Dist
from dplasma_tpu.parallel import cyclic
from dplasma_tpu.parallel import mesh as pmesh

NB = 4
GRIDS = [(1, 1), (2, 2), (1, 4)]


def _mesh(P_, Q_, devices8):
    return pmesh.make_mesh(P_, Q_, devices8)


def _kernel(op, P_, Q_, devices8, nt=4, la=0):
    m = _mesh(P_, Q_, devices8)
    desc = cyclic.CyclicDesc(nt * NB, nt * NB, NB, NB,
                             Dist(P=P_, Q=Q_))
    data = jnp.zeros((P_, Q_, desc.MTL * NB, desc.NTL * NB),
                     jnp.float32)
    if op == "potrf":
        fn = partial(cyclic._potrf_cyclic_jit, desc=desc, mesh=m,
                     lookahead=la)
        return fn, (data,), min(desc.MT, desc.NT)
    if op == "getrf":
        fn = partial(cyclic._getrf_cyclic_jit, desc=desc, mesh=m,
                     lookahead=la)
        return fn, (data,), min(desc.MT, desc.NT)
    if op == "geqrf":
        fn = partial(cyclic._geqrf_cyclic_jit, desc=desc, mesh=m,
                     lookahead=la)
        return fn, (data,), min(desc.MT, desc.NT)
    fn = partial(cyclic._gemm_cyclic_jit, adesc=desc, bdesc=desc,
                 mesh=m)
    return fn, (data, data), desc.NT


# ------------------------------------------------- golden clean sweep

@pytest.mark.parametrize("op", ["potrf", "getrf", "geqrf", "gemm"])
@pytest.mark.parametrize("grid", GRIDS, ids=lambda g: f"{g[0]}x{g[1]}")
@pytest.mark.parametrize("la", [0, 1])
def test_cyclic_kernels_reconcile_exactly(op, grid, la, devices8):
    """Every cyclic kernel's traced collective counts equal the
    analytic model EXACTLY, on every grid, in both pipeline shapes
    (the lookahead relocates the panel broadcast but never changes
    the totals — the invariant that makes the check exact)."""
    if op == "gemm" and la == 1:
        pytest.skip("gemm has no lookahead variant")
    fn, args, KT = _kernel(op, *grid, devices8, la=la)
    res = sp.check_kernel(fn, args, f"{op}", op=op, KT=KT,
                          lookahead=la)
    assert res.ok, res.format(op)
    assert res.relation == "=="
    assert res.shard_maps == 1
    assert res.mesh_axes == {pmesh.ROW_AXIS: grid[0],
                             pmesh.COL_AXIS: grid[1]}
    assert res.counts == sp.expected_counts(op, KT, la)


def test_potrf_sequence_golden(devices8):
    """The potrf per-step collective ORDER is pinned, not just the
    counts: panel psum along 'q', diagonal psum along 'p', row-panel
    all_gather along 'p' — the zpotrf_L.jdf type_remote schedule."""
    fn, args, KT = _kernel("potrf", 2, 2, devices8, la=0)
    res = sp.extract_schedule(fn, *args, kernel="potrf")
    keys = [c.key for c in res.collectives]
    step = [f"psum@{pmesh.COL_AXIS}", f"psum@{pmesh.ROW_AXIS}",
            f"all_gather@{pmesh.ROW_AXIS}"]
    assert keys == step * KT


def test_getrf_sequence_golden(devices8):
    """getrf per step: panel psum_q, candidate+gid all_gathers along
    'p' (the tournament playoff), pivot-row exchange psum_p."""
    fn, args, KT = _kernel("getrf", 1, 4, devices8, la=0)
    res = sp.extract_schedule(fn, *args, kernel="getrf")
    keys = [c.key for c in res.collectives]
    step = [f"psum@{pmesh.COL_AXIS}",
            f"all_gather@{pmesh.ROW_AXIS}",
            f"all_gather@{pmesh.ROW_AXIS}",
            f"psum@{pmesh.ROW_AXIS}"]
    assert keys == step * KT


def test_every_cyclic_kernel_is_structurally_clean(devices8):
    """EVERY shard_map kernel in parallel/cyclic.py — not just the
    four with count models — passes the structural checks: axes
    bound, no rank-divergent collectives, permutations sound. This is
    the blanket the acceptance criterion names; new cyclic kernels
    join by construction (they trace through the same extractor)."""
    m = _mesh(2, 2, devices8)
    desc = cyclic.CyclicDesc(16, 16, NB, NB, Dist(P=2, Q=2))
    data = jnp.zeros((2, 2, desc.MTL * NB, desc.NTL * NB),
                     jnp.float32)
    perm = jnp.arange(16, dtype=jnp.int32)
    cases = [
        ("potrf_U", partial(cyclic._potrf_cyclic_upper_jit,
                            desc=desc, mesh=m), (data,)),
        ("trsm_LN", partial(cyclic._trsm_cyclic_jit, desc=desc,
                            bdesc=desc, mesh=m, uplo="L", trans="N",
                            unit=False), (data, data)),
        ("trsm_LC", partial(cyclic._trsm_cyclic_jit, desc=desc,
                            bdesc=desc, mesh=m, uplo="L", trans="C",
                            unit=False), (data, data)),
        ("trmm_LN", partial(cyclic._trmm_cyclic_jit, desc=desc,
                            bdesc=desc, mesh=m,
                            opts=("L", "N", False)), (data, data)),
        ("trmm_LC", partial(cyclic._trmm_cyclic_jit, desc=desc,
                            bdesc=desc, mesh=m,
                            opts=("L", "C", False)), (data, data)),
        ("herk", partial(cyclic._herk_cyclic_jit, desc=desc,
                         cdesc=desc, mesh=m), (data,)),
        ("her2k", partial(cyclic._her2k_cyclic_jit, desc=desc,
                          cdesc=desc, mesh=m), (data, data)),
        ("hemm", partial(cyclic._hemm_cyclic_jit, desc=desc,
                         bdesc=desc, mesh=m), (data, data)),
        ("lauum", partial(cyclic._lauum_cyclic_jit, desc=desc,
                          mesh=m), (data,)),
        ("herbt", partial(cyclic._herbt_cyclic_jit, desc=desc,
                          mesh=m), (data,)),
        ("ge2gb", partial(cyclic._ge2gb_cyclic_jit, desc=desc,
                          mesh=m), (data,)),
        ("band_extract", partial(cyclic._band_extract_cyclic_jit,
                                 desc=desc, mesh=m), (data,)),
        ("getrs", partial(cyclic._getrs_cyclic_jit, desc=desc,
                          mesh=m),
         (data, perm, jnp.zeros((16, 2), jnp.float32))),
        ("identity", partial(cyclic._identity_cyclic_jit, desc=desc,
                             mesh=m), (data,)),
    ]
    for name, fn, args in cases:
        res = sp.check_kernel(fn, args, name)
        assert res.ok, res.format(name)
        assert res.relation in ("unmodelled", "no-collectives"), name


def test_a2a_conversion_kernels_are_structurally_clean(devices8):
    """The all_to_all redistribution phases (from_tile_a2a/to_tile_a2a)
    trace clean too — their all_to_all collectives bind the mesh axes
    and sit behind no divergent control flow."""
    from dplasma_tpu.descriptors import TileMatrix
    m = _mesh(2, 2, devices8)
    d = Dist(P=2, Q=2)
    A = TileMatrix.zeros(32, 32, NB, NB, dist=d)

    def conv(x):
        return cyclic.from_tile_a2a(TileMatrix(x, A.desc), d, m).data

    res = sp.extract_schedule(conv, A.data, kernel="from_tile_a2a")
    assert res.ok, res.format()
    assert any(c.kind == "all_to_all" for c in res.collectives)


def test_expected_counts_tie_to_comm_model():
    """The count table's collective classes must be exactly the
    classes spmd_comm_model prices, per op — the two models cannot
    drift apart silently (reconcile_counts enforces this too)."""
    for op in ("potrf", "getrf", "geqrf", "gemm"):
        exp = sp.expected_counts(op, 3)
        assert exp and all(v > 0 for v in exp.values())
        assert sp.model_classes(op) == set(exp)
    assert sp.expected_counts("nosuchop", 3) is None
    assert sp.model_classes("nosuchop") is None


# ------------------------------------------------------ mutation tests

def test_mutation_dropped_psum_is_count_mismatch(devices8):
    """Drop one panel-broadcast psum from the schedule: the
    reconciliation names the kernel and the collective class."""
    fn, args, KT = _kernel("potrf", 2, 2, devices8)
    res = sp.extract_schedule(fn, *args, kernel="potrf_2x2")
    qkey = f"psum@{pmesh.COL_AXIS}"
    drop = next(i for i, c in enumerate(res.collectives)
                if c.key == qkey)
    del res.collectives[drop]
    sp.reconcile_counts(res, "potrf", KT)
    assert not res.ok and res.relation == "mismatch"
    (d,) = [d for d in res.diagnostics if d.kind == "count-mismatch"]
    assert d.kernel == "potrf_2x2"
    assert qkey in d.message and "dropped" in d.message
    assert d.detail == {"class": qkey, "traced": KT - 1,
                        "expected": KT}


def test_mutation_surplus_collective_fails_exact_passes_dominating(
        devices8):
    """An extra collective fails the exact contract (the cyclic
    kernels' own gate) but satisfies the dominating one (driver
    programs wrapping them in conversions)."""
    fn, args, KT = _kernel("potrf", 2, 2, devices8)
    res = sp.extract_schedule(fn, *args, kernel="k")
    res.collectives.append(
        sp.Collective("psum", (pmesh.ROW_AXIS,)))
    sp.reconcile_counts(res, "potrf", KT, exact=False)
    assert res.ok and res.relation == ">="
    res2 = sp.extract_schedule(fn, *args, kernel="k")
    res2.collectives.append(
        sp.Collective("psum", (pmesh.ROW_AXIS,)))
    sp.reconcile_counts(res2, "potrf", KT, exact=True)
    assert not res2.ok
    assert any("surplus" in d.message for d in res2.diagnostics)


def test_mutation_rank_divergent_cond(devices8):
    """A collective in one cond branch but not the other is an SPMD
    deadlock: ranks taking the poorer branch skip a psum the others
    enter. Diagnostic names the diverging sequences."""
    m = _mesh(2, 2, devices8)

    def body(x):
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        return jax.lax.cond(
            p == 0,
            lambda y: jax.lax.psum(y, pmesh.COL_AXIS),
            lambda y: y * 2.0, x)

    fn = shard_map(body, mesh=m, in_specs=P(pmesh.ROW_AXIS),
                   out_specs=P(pmesh.ROW_AXIS, None))
    res = sp.extract_schedule(fn, jnp.zeros((4, 4)), kernel="divk")
    assert not res.ok
    (d,) = [d for d in res.diagnostics if d.kind == "divergent-cond"]
    assert d.kernel == "divk"
    assert f"psum@{pmesh.COL_AXIS}" in d.message


def test_uniform_cond_branches_are_clean(devices8):
    """Identical collective subsequences in every branch are SPMD-safe
    (all ranks reach the same collective either way) and contribute
    exactly once to the schedule."""
    m = _mesh(2, 2, devices8)

    def body(x):
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        return jax.lax.cond(
            p == 0,
            lambda y: jax.lax.psum(y * 2.0, pmesh.COL_AXIS),
            lambda y: jax.lax.psum(y + 1.0, pmesh.COL_AXIS), x)

    fn = shard_map(body, mesh=m, in_specs=P(pmesh.ROW_AXIS),
                   out_specs=P(pmesh.ROW_AXIS, None))
    res = sp.extract_schedule(fn, jnp.zeros((4, 4)), kernel="unik")
    assert res.ok, res.format()
    assert [c.key for c in res.collectives] == \
        [f"psum@{pmesh.COL_AXIS}"]


def test_mutation_divergent_cond_same_kind_different_perm(devices8):
    """Branches whose collectives agree in kind AND axis but differ in
    the ppermute permutation are still rank-divergent: ranks taking
    different branches exchange with different partners (review r6
    finding — the perm is part of the schedule signature)."""
    m = _mesh(1, 4, devices8)
    fwd = [(i, (i + 1) % 4) for i in range(4)]
    bwd = [(i, (i - 1) % 4) for i in range(4)]

    def body(x):
        q = jax.lax.axis_index(pmesh.COL_AXIS)
        return jax.lax.cond(
            q == 0,
            lambda y: jax.lax.ppermute(y, pmesh.COL_AXIS, fwd),
            lambda y: jax.lax.ppermute(y, pmesh.COL_AXIS, bwd), x)

    fn = shard_map(body, mesh=m, in_specs=P(pmesh.COL_AXIS),
                   out_specs=P(pmesh.COL_AXIS))
    res = sp.extract_schedule(fn, jnp.zeros((8, 4)), kernel="permdiv")
    assert not res.ok
    assert any(d.kind == "divergent-cond" for d in res.diagnostics)


def test_mutation_collective_in_while(devices8):
    """A psum inside a data-dependent while loop cannot be proven
    uniform across ranks — diagnostic, not a hang at pod scale."""
    m = _mesh(2, 2, devices8)

    def body(x):
        def cond(c):
            return c[0].sum() < 10.0

        def step(c):
            y, = c
            return (jax.lax.psum(y, pmesh.COL_AXIS) + 1.0,)

        return jax.lax.while_loop(cond, step, (x,))[0]

    fn = shard_map(body, mesh=m, in_specs=P(pmesh.ROW_AXIS),
                   out_specs=P(pmesh.ROW_AXIS, None),
                   check_vma=False)  # while has no replication rule
    res = sp.extract_schedule(fn, jnp.zeros((4, 4)), kernel="whilek")
    assert not res.ok
    (d,) = [d for d in res.diagnostics
            if d.kind == "while-collective"]
    assert f"psum@{pmesh.COL_AXIS}" in d.message


@pytest.mark.parametrize("perm,why", [
    ([(0, 1), (1, 1)], "duplicate destinations"),        # asymmetric
    ([(0, 1), (1, 0), (0, 1)], "duplicate sources"),
    ([(0, 5), (1, 0)], "out-of-range"),
])
def test_mutation_bad_ppermute(perm, why, devices8):
    """Non-bijective ppermute permutations (asymmetric exchange,
    doubled rank, out-of-range rank) are named with the reason."""
    m = _mesh(1, 4, devices8)

    def body(x):
        return jax.lax.ppermute(x, pmesh.COL_AXIS, perm)

    fn = shard_map(body, mesh=m,
                   in_specs=P(pmesh.COL_AXIS),
                   out_specs=P(pmesh.COL_AXIS))
    res = sp.extract_schedule(fn, jnp.zeros((8, 4)), kernel="permk")
    assert not res.ok
    (d,) = [d for d in res.diagnostics if d.kind == "bad-permutation"]
    assert why in d.message and "bijection" in d.message


def test_bijective_ppermute_is_clean(devices8):
    m = _mesh(1, 4, devices8)
    perm = [(i, (i + 1) % 4) for i in range(4)]

    def body(x):
        return jax.lax.ppermute(x, pmesh.COL_AXIS, perm)

    fn = shard_map(body, mesh=m,
                   in_specs=P(pmesh.COL_AXIS),
                   out_specs=P(pmesh.COL_AXIS))
    res = sp.extract_schedule(fn, jnp.zeros((8, 4)), kernel="ringk")
    assert res.ok and res.collectives[0].kind == "ppermute"


def test_verify_kernel_raises(devices8):
    m = _mesh(2, 2, devices8)

    def body(x):
        p = jax.lax.axis_index(pmesh.ROW_AXIS)
        return jax.lax.cond(
            p == 0, lambda y: jax.lax.psum(y, pmesh.COL_AXIS),
            lambda y: y, x)

    fn = shard_map(body, mesh=m, in_specs=P(pmesh.ROW_AXIS),
                   out_specs=P(pmesh.ROW_AXIS, None))
    with pytest.raises(sp.SpmdCheckError, match="rank-divergent"):
        sp.verify_kernel(fn, (jnp.zeros((4, 4)),), "divk")


# ------------------------------------------- ring-schedule simulator

def test_ring_shift_schedule_drains():
    """The canonical neighbor-shift ring (the ROADMAP item 2 panel
    broadcast shape) passes the simulator on any size."""
    for n in (2, 4, 8):
        res = sp.check_ring(f"ring{n}", sp.ring_shift_program(n, 3))
        assert res.ok, res.format()


def test_ring_mutation_missing_send_deadlocks():
    """Rank 1 skips its send: rank 2's wait can never be satisfied —
    the diagnostic names the kernel, the stuck step, and the rank
    pair."""
    progs = sp.ring_shift_program(4, 1)
    progs[1] = [op for op in progs[1] if op.kind != "send"]
    diags = sp.simulate_ring("panel_bcast_ring", progs)
    assert diags
    d = next(d for d in diags if d.kind == "deadlock"
             and d.detail["rank"] == 2)
    assert "panel_bcast_ring" in d.message
    assert d.detail["peer"] == 1 and "step" in d.detail
    res = sp.check_ring("panel_bcast_ring", progs)
    assert not res.ok


def test_ring_mutation_skipped_wait_is_unpaired_semaphore():
    """Rank 0 never drains the signal it received: the leftover count
    is an unpaired-DMA-semaphore diagnostic naming rank and sem."""
    progs = sp.ring_shift_program(4, 1)
    progs[0] = [op for op in progs[0] if op.kind != "wait"]
    diags = sp.simulate_ring("row_exchange_ring", progs)
    (d,) = [d for d in diags if d.kind == "unpaired-semaphore"]
    assert d.detail == {"rank": 0, "sem": "dma", "undrained": 1}
    assert "row_exchange_ring" in d.message


def test_ring_mutation_wait_before_send_self_deadlock():
    """Both ranks wait before sending (the classic head-to-head):
    simulator reports both stuck at step 0."""
    progs = {r: [sp.wait((r + 1) % 2), sp.send((r + 1) % 2)]
             for r in range(2)}
    diags = sp.simulate_ring("headk", progs)
    assert {d.detail["rank"] for d in diags} == {0, 1}
    assert all(d.detail["step"] == 0 for d in diags)


# --------------------------------------------- integration touchpoints

def test_driver_spmdcheck_end_to_end(tmp_path, capsys, devices8):
    """--spmdcheck runs before the timed loop and lands in the
    schema-v6 run-report; a GSPMD-partitioned op (no explicit
    shard_map) reports no-collectives."""
    import json

    from dplasma_tpu.drivers import main
    rj = str(tmp_path / "r.json")
    rc = main(["-N", "64", "-t", "16", "-p", "2", "-q", "2",
               "--spmdcheck", f"--report={rj}", "-v=2"],
              prog="testing_dpotrf")
    out = capsys.readouterr().out
    assert rc == 0
    assert "spmdcheck[testing_dpotrf]" in out and "OK" in out
    doc = json.load(open(rj))
    assert doc["schema"] == 18
    (entry,) = doc["spmdcheck"]
    assert entry["ok"] and entry["op"] == "testing_dpotrf"
    assert entry["relation"] in ("no-collectives", "structural")
    assert entry["diagnostics"] == []
    assert any(m["name"] == "spmdcheck_collectives_total"
               for m in doc["metrics"])


def test_driver_spmdcheck_flag_parses():
    from dplasma_tpu.drivers.common import parse_arguments
    ip = parse_arguments(["-N", "64", "--spmdcheck"])
    assert ip.spmdcheck
    ip = parse_arguments(["-N", "64"])
    assert not ip.spmdcheck


# --------------------------------------- explicit ICI ring kernels

def test_ring_kernels_reconcile_exactly(devices8):
    """The ring-wired cyclic kernels (ring=True statics) trace to the
    ring collective classes and reconcile EXACTLY: the panel
    broadcast becomes one ring_bcast@q per step, the LU winner-row
    exchange P-1 ring_shift@p hops per step, everything else
    unchanged."""
    for op, extra in (("potrf", {f"psum@{pmesh.ROW_AXIS}": 4,
                                 f"all_gather@{pmesh.ROW_AXIS}": 4}),
                      ("getrf", {f"all_gather@{pmesh.ROW_AXIS}": 8,
                                 f"ring_shift@{pmesh.ROW_AXIS}": 4}),
                      ("geqrf", {f"psum@{pmesh.ROW_AXIS}": 16})):
        m = _mesh(2, 2, devices8)
        desc = cyclic.CyclicDesc(4 * NB, 4 * NB, NB, NB,
                                 Dist(P=2, Q=2))
        data = jnp.zeros((2, 2, desc.MTL * NB, desc.NTL * NB),
                         jnp.float32)
        KT = min(desc.MT, desc.NT)
        jit = {"potrf": cyclic._potrf_cyclic_jit,
               "getrf": cyclic._getrf_cyclic_jit,
               "geqrf": cyclic._geqrf_cyclic_jit}[op]
        kw = {"panel": "chain"} if op == "getrf" else {}
        fn = partial(jit, desc=desc, mesh=m, lookahead=1, ring=True,
                     **kw)
        res = sp.check_kernel(fn, (data,), f"{op}_ring", op=op,
                              KT=KT, lookahead=1, ring=True,
                              grid=(2, 2))
        assert res.ok, res.format(op)
        assert res.relation == "=="
        want = {f"ring_bcast@{pmesh.COL_AXIS}": KT}
        want.update(extra)
        assert res.counts == want


def test_ring_partial_fallback_on_size1_axes(devices8):
    """ring=True on a grid with a size-1 axis keeps the psum class on
    that axis (the per-axis fallback): 4x1 getrf rings 'p' (the
    winner-row exchange) while the panel broadcast stays psum@q."""
    m = _mesh(4, 1, devices8)
    desc = cyclic.CyclicDesc(4 * NB, 4 * NB, NB, NB, Dist(P=4, Q=1))
    data = jnp.zeros((4, 1, desc.MTL * NB, desc.NTL * NB),
                     jnp.float32)
    KT = min(desc.MT, desc.NT)
    fn = partial(cyclic._getrf_cyclic_jit, desc=desc, mesh=m,
                 lookahead=0, panel="chain", ring=True)
    res = sp.check_kernel(fn, (data,), "getrf_ring_4x1", op="getrf",
                          KT=KT, lookahead=0, ring=True, grid=(4, 1))
    assert res.ok, res.format("getrf 4x1 ring")
    assert res.relation == "=="
    assert res.counts[f"psum@{pmesh.COL_AXIS}"] == KT
    assert res.counts[f"ring_shift@{pmesh.ROW_AXIS}"] == KT * 3


def test_ring_expected_counts_tie_to_comm_model():
    """The ring count table's classes must be exactly what
    spmd_comm_model prices with ring=True, grid by grid — the
    drift guard extended to the ring schedule."""
    for op in ("potrf", "getrf", "geqrf"):
        for grid in ((2, 2), (1, 4), (4, 1)):
            exp = sp.expected_counts(op, 3, ring=True, grid=grid)
            assert exp and all(v > 0 for v in exp.values())
            assert sp.model_classes(op, ring=True, grid=grid) \
                == set(exp)


def test_ring_bcast_program_golden():
    """The shipped panel-broadcast ring's abstract schedule (chunked
    and unchunked, every root) drains with zero findings — the
    verify-before-first-execution contract of kernels.pallas_ring."""
    from dplasma_tpu.kernels import pallas_ring as pring
    for n in (2, 3, 4, 8):
        for root in range(n):
            for chunks in (1, 4):
                prog = pring.bcast_program(n, root, chunks)
                assert sp.simulate_ring(
                    f"bcast{n}r{root}c{chunks}", prog) == []


def test_ring_allreduce_program_golden():
    """The LU winner-row exchange's schedule (n-1 shift-and-add
    hops) drains clean for every axis size the kernels run."""
    from dplasma_tpu.kernels import pallas_ring as pring
    for n in (2, 3, 4, 8):
        assert sp.simulate_ring(f"rowsum{n}",
                                pring.allreduce_program(n)) == []


def test_ring_bcast_missing_wait_is_unpaired_semaphore():
    """Mutation: the last rank of the broadcast chain drops its recv
    wait — its inbound chunk signal is never drained, and the
    diagnostic names the rank, the semaphore, and the kernel."""
    from dplasma_tpu.kernels import pallas_ring as pring
    prog = pring.bcast_program(4, root=0, chunks=1)
    prog[3] = [op for op in prog[3] if op.kind != "wait"]
    diags = sp.simulate_ring("panel_bcast_ring_q", prog)
    (d,) = [d for d in diags if d.kind == "unpaired-semaphore"]
    assert d.detail == {"rank": 3, "sem": "dma", "undrained": 1}
    assert "panel_bcast_ring_q" in d.message


def test_ring_bcast_missing_forward_deadlocks():
    """Mutation: a middle rank refuses to forward — every rank past
    it starves, and the simulator names the stuck waiter and the
    peer whose send never comes."""
    from dplasma_tpu.kernels import pallas_ring as pring
    prog = pring.bcast_program(4, root=0, chunks=1)
    prog[1] = [op for op in prog[1] if op.kind != "send"]
    diags = sp.simulate_ring("panel_bcast_ring_q", prog)
    assert any(d.kind == "deadlock" and d.detail["rank"] == 2
               and d.detail["peer"] == 1 for d in diags)
