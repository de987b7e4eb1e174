"""The library's named scopes read from a traced window
(``benchmark/scopes.py``), on a synthetic trace with known answers, and
the readings that were there before, pinned on the committed fixture."""
import gzip
import os

import pytest

from benchmark import hlo, reduce, scopes, spec

DATA = os.path.join(os.path.dirname(__file__), "data")
STEM = os.path.join(DATA, "cholesky_f32_16384")

#: one program: the fusions take their scopes from their own op name or,
#: without one, from their fused ops'; the async collective GSPMD adds
#: from the operand it moves; the copy of the argument has no scope
HLO = """HloModule m

%fused_computation.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %multiply.9 = f32[8]{0} multiply(f32[8]{0} %p, f32[8]{0} %p), metadata={op_name="jit(solve)/dplasma.potrf/dplasma.split/mul"}
}

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p0), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(solve)/dplasma.potrf/dplasma.panel/cholesky"}
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %fusion.1), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(solve)/dplasma.potrf/dplasma.update[x]/dot_general"}
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %fusion.2), kind=kLoop, calls=%fused_computation.9
  %all-gather.4 = f32[8]{0} all-gather(f32[8]{0} %fusion.3), dimensions={0}, metadata={op_name="jit(solve)/dplasma.getrf/dplasma.redistribute/all_gather"}
  %collective-permute-start.6 = (f32[8]{0}, f32[8]{0}) collective-permute-start(f32[8]{0} %fusion.2), source_target_pairs={{0,1}}, metadata={op_name="slice.3"}
  %collective-permute-done.6 = f32[8]{0} collective-permute-done((f32[8]{0}, f32[8]{0}) %collective-permute-start.6)
  ROOT %copy.5 = f32[8]{0} copy(f32[8]{0} %p0)
}
"""
DEV = "/device:TPU:0"
#: two calls; the first is the trace's lead-in, so the window is
#: [1000, 2000] ns: busy 750 ns, idle 100 ns between update and split
#: and 150 ns after the copy
RAW = {
    "spans": [("call", 0, 10), ("wait", 10, 1000),
              ("call", 1000, 1010), ("wait", 1010, 2000)],
    "modules": {},
    "devices": {DEV: [("fusion.1", 1000, 1200), ("fusion.2", 1200, 1500),
                      ("fusion.3", 1600, 1700),
                      ("all-gather.4", 1700, 1800),
                      ("copy.5", 1800, 1850)]},
}


@pytest.fixture(scope="module")
def synthetic():
    return reduce.Trace(RAW, hlo.index([HLO])), scopes.index([HLO])


def test_scope_of_an_op_name():
    assert scopes.scope_of(
        "jit(solve)/jit(_jit_trail)/dplasma.update/dplasma.recombine"
        "/pallas_call:") == ("update", "recombine")
    assert scopes.scope_of("jit(solve)/dplasma.solve:") == ("solve",)
    assert scopes.scope_of("jit(solve)/cholesky") == ()


def test_index_reads_op_names_and_fusion_roots(synthetic):
    _, idx = synthetic
    assert idx["fusion.1"] == ("potrf", "panel")
    assert idx["fusion.2"] == ("potrf", "update")
    assert idx["fusion.3"] == ("potrf", "split")   # from its fused op
    assert idx["all-gather.4"] == ("getrf", "redistribute")
    assert idx["collective-permute-start.6"] == ("potrf", "update")
    assert idx["collective-permute-done.6"] == ("potrf", "update")
    assert idx["copy.5"] == ()


def test_busy_per_scope_and_unscoped(synthetic):
    t, idx = synthetic
    assert t.window_s == pytest.approx(1000e-9)
    assert scopes.busy_by_scope(t, idx) == pytest.approx({
        "potrf": 600e-9, "panel": 200e-9, "update": 300e-9,
        "split": 100e-9, "getrf": 100e-9, "redistribute": 100e-9})
    by_path = scopes.busy_by_path(t, idx)
    assert list(by_path)[0] == "potrf/update"
    assert by_path["(none)"] == pytest.approx(50e-9)
    assert scopes.unscoped_s(t, idx) == pytest.approx(50e-9)
    assert scopes.top_unscoped(t, idx) == [
        ["other:copy.5", pytest.approx(50e-9)]]


def test_idle_gaps_name_the_scopes_on_either_side(synthetic):
    t, idx = synthetic
    assert scopes.idle_gaps(t, idx) == [
        ["wait: (none) > end", pytest.approx(150e-9)],
        ["wait: potrf/update > potrf/split", pytest.approx(100e-9)]]


def test_shares(synthetic):
    t, idx = synthetic
    assert scopes.panel_scope_pct(t, idx) == pytest.approx(100 * 200 / 750)
    assert scopes.limb_overhead_pct(t, idx) == pytest.approx(
        100 * 100 / 750)
    # the all-gather runs alone for 100 of the window's 1000 ns
    assert scopes.layout_exposed_pct(t, idx) == pytest.approx(10.0)
    rep = scopes.report(t, idx)
    assert rep["unscoped_pct"] == pytest.approx(100 * 50 / 750)
    # the traced call: from its start at 1000 ns to its answer at 2000
    assert scopes.solve_ms(t) == [pytest.approx(1e-3)]
    assert rep["solves"] == 1
    assert rep["panel_scope_pct"] == scopes.panel_scope_pct(t, idx)


def test_a_collective_hidden_by_compute_is_not_exposed():
    raw = dict(RAW, devices={DEV: [("fusion.1", 1000, 1900),
                                   ("all-gather.4", 1700, 1800)]})
    t = reduce.Trace(raw, hlo.index([HLO]))
    assert scopes.layout_exposed_pct(t, scopes.index([HLO])) == 0.0


def test_vocabulary_is_the_librarys():
    from dplasma_tpu.observability import phases
    assert scopes.SCOPES == phases.SCOPES
    assert scopes.PREFIX == phases.SCOPE_PREFIX
    assert scopes.LIMB | scopes.LAYOUT <= set(scopes.SCOPES)


# -------------------------------------------- the committed fixture

#: every reader and the breakdown on the fixture, as they read before
#: the scopes were added (the fixture's program has none)
PINNED = {
    "collective_exposed_pct": None,
    "device_idle_pct": 1.326226600718039,
    "device_idle_pct.short_calls": 1.326226600718039,
    "host_call_pct": 0.4095783627934255,
    "host_call_pct.short_calls": 0.4095783627934255,
    "matmul_roofline_pct": 15.261601321879265,
    "matmul_roofline_pct.short_calls": 15.261601321879265,
    "panel_pct": 14.852596258266557,
    "panel_pct.short_calls": 14.852596258266557,
    # the request-serving cell's readers: a closed call has no requests
    "device_idle_pct.open": 1.326226600718039,
    "matmul_roofline_pct.open": 15.261601321879265,
    "pad_flops_pct.open": None,
    "queue_wait_pct.open": None,
}
BREAKDOWN = {
    "device_ops": [
        ["matmul:convolution_subtract_fusion.8", 0.003773381],
        ["matmul:convolution_subtract_fusion.6", 0.0037562000000000003],
        ["matmul:convolution_subtract_fusion.4", 0.0036508100000000004],
        ["matmul:convolution_subtract_fusion.10", 0.003635062],
        ["matmul:fusion.132", 0.0034806560000000004],
        ["matmul:convolution_subtract_fusion.2", 0.003381278],
        ["matmul:convolution_subtract_fusion.12", 0.003369327],
        ["matmul:convolution_subtract_fusion.14", 0.0029667170000000002],
        ["matmul:fusion.125", 0.00256128],
        ["matmul:convolution_subtract_fusion.16", 0.0024354380000000003]],
    "idle_gaps": [
        ["wait@0", 0.0010433010000000002], ["wait@0", 7.08e-07],
        ["wait@0", 7.02e-07], ["wait@0", 7.02e-07], ["wait@0", 7.02e-07],
        ["call@0", 7.01e-07], ["wait@0", 7.01e-07], ["wait@0", 7.01e-07],
        ["wait@0", 7.01e-07], ["wait@0", 7.01e-07]],
}


@pytest.fixture(scope="module")
def fixture_run():
    with gzip.open(STEM + ".hlo.txt.gz", "rt") as f:
        text = f.read()
    t = reduce.Trace(reduce.load(STEM + ".xplane.pb.gz"), hlo.index([text]))
    return t, scopes.index([text])


def test_existing_readings_are_unchanged(fixture_run):
    t, _ = fixture_run
    c = spec.cell(spec.load_spec(), "cholesky_f32.closed")
    ctx = {"trace": t, "device_kind": "TPU v5 lite", "cell": c,
           "config": c.config}
    names = sorted(f[:-3] for f in os.listdir(
        os.path.join(spec.ROOT, "benchmark", "metrics"))
        if f.endswith(".py") and f != "__init__.py")
    assert names == sorted(PINNED)
    assert {n: spec.reader(c, n).read(ctx) for n in names} == PINNED
    assert t.breakdown() == BREAKDOWN


def test_a_program_without_scopes_reads_empty(fixture_run):
    t, idx = fixture_run
    assert set(idx.values()) == {()}
    rep = scopes.report(t, idx)
    assert rep["by_scope"] == {}
    assert rep["unscoped_s"] == pytest.approx(t.busy_s(), rel=1e-12)
    for name in ("panel_scope_pct", "limb_overhead_pct",
                 "layout_exposed_pct"):
        assert rep[name] is None


#: one ``posv`` call at N = 12288, nb = 1024 (f32) on a TPU v5 lite,
#: traced by ``benchmark/scopes.py --out`` from a tree with the scopes
SCOPED = os.path.join(DATA, "cholesky_f32_12288_scoped")


def test_scoped_chip_trace():
    t, idx = scopes.load(SCOPED)
    rep = scopes.report(t, idx)
    assert set(rep["by_scope"]) == {"potrf", "panel", "far_flush",
                                    "lookahead", "assemble", "solve"}
    assert rep["unscoped_pct"] == pytest.approx(0.0074059231937612146,
                                                rel=1e-12)
    assert rep["panel_scope_pct"] == pytest.approx(21.341248771214087,
                                                   rel=1e-12)
    assert rep["limb_overhead_pct"] is None
    assert rep["layout_exposed_pct"] is None
    # the device waits between the solve's last op and the window's end
    assert rep["idle_gaps"][0] == [
        "wait: solve > end", pytest.approx(0.001092894, rel=1e-12)]
    # a fusion with a tuple root takes the scope its fused ops share
    assert idx["fusion.83"] == ("potrf", "panel")
    assert idx["fusion.69"] == ("potrf", "assemble")
    # what is left moves a constant XLA broadcasts, outside any scope
    assert rep["top_unscoped"][0][0] == "other:slice-done.71"


def test_scopes_leave_the_op_name_rule_alone():
    """``panel_pct`` reads the ``jax.lax`` op names as before: the scopes
    are further components of the same op names."""
    with gzip.open(SCOPED + ".hlo.txt.gz", "rt") as f:
        text = f.read()
    t = reduce.Trace(reduce.load(SCOPED + ".xplane.pb.gz"),
                     hlo.index([text]))
    c = spec.cell(spec.load_spec(), "cholesky_f32.closed")
    ctx = {"trace": t, "device_kind": "TPU v5 lite", "cell": c,
           "config": c.config}
    assert spec.reader(c, "panel_pct.short_calls").read(ctx) == \
        pytest.approx(23.40466565677777, rel=1e-12)
