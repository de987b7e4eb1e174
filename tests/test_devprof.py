"""devprof: per-device timeline ingestion, measured-ICI
reconciliation, and straggler attribution (schema v14).

Covers the ISSUE acceptance matrix: the synthetic backend's golden
attribution on a 2x2 grid (every spmdcheck-priced collective class
appears, categories sum to the run), an injected straggler named by
rank and dominating category, a dropped collective class flagged by a
named diagnostic, the driver ``--devprof`` end-to-end path on
dpotrf/dgetrf/dgeqrf, and the perfdiff extraction + ``--json``
verdict round-trip over devprof metrics.
"""
import gzip
import json
import pathlib
import sys

import pytest

from dplasma_tpu.analysis import spmdcheck
from dplasma_tpu.observability import devprof as dp
from dplasma_tpu.observability.report import REPORT_SCHEMA, load_report

sys.path.insert(0, str(__import__("pathlib").Path(
    __file__).resolve().parent.parent / "tools"))


def _model_inputs(op, n=64, nb=16, grid=(2, 2)):
    """spmdcheck schedule + comm-model pricing for one op on a grid."""
    from dplasma_tpu.descriptors import Dist
    from dplasma_tpu.parallel.cyclic import CyclicDesc, spmd_comm_model
    kt = -(-n // nb)
    expected = spmdcheck.expected_counts(op, kt, 0, ring=False,
                                         grid=grid)
    model = spmd_comm_model(
        CyclicDesc(n, n, nb, nb, Dist(P=grid[0], Q=grid[1])),
        op, 8, ring=False)
    return expected, dp.model_bytes_by_class(model)


# ------------------------------------------------- synthetic golden

@pytest.mark.parametrize("op", ["potrf", "getrf", "geqrf"])
def test_attribute_golden_2x2(op):
    """attribute() on a 2x2 grid reconciles ``==`` against the
    spmdcheck schedule: every priced collective class is ingested at
    its expected count and category seconds sum to the run."""
    run_s = 0.01
    entry = dp.attribute(f"golden_{op}", op, run_s, (2, 2), 64, 64, 16)
    assert entry["ok"] and entry["backend"] == "synthetic"
    rec = entry["reconciliation"]
    assert rec["relation"] == "=="
    assert rec["ingested"] == rec["expected"]
    expected, _bb = _model_inputs(op)
    assert set(rec["expected"]) == set(expected)
    # acceptance: category seconds within 10% of the timed run —
    # the synthetic lane is exact by construction
    total = sum(entry["categories"].values())
    assert total == pytest.approx(run_s, rel=0.10)
    assert entry["coverage"] == pytest.approx(1.0, rel=0.10)
    for row in entry["collectives"]:
        assert row["count"] == expected[row["cls"]]
        assert row["measured_s"] > 0
        assert row["achieved_frac"] is not None
    assert entry["skew"]["value"] == pytest.approx(0.0, abs=1e-9)
    assert entry["critical_path"]


def test_attribute_1x1_is_all_compute():
    """A 1x1 grid (no wire) attributes honestly: one compute lane,
    no reconciliation claims."""
    entry = dp.attribute("solo", "potrf", 0.005, (1, 1), 64, 64, 16)
    assert entry["reconciliation"]["relation"] == "no-collectives"
    assert entry["ok"] and entry["collectives"] == []
    assert entry["categories"]["compute"] == pytest.approx(0.005)


def test_attribute_unmodelled_op():
    """An op class outside the comm model never fabricates a
    schedule."""
    entry = dp.attribute("mystery", None, 0.005, (2, 2), 64, 64, 16)
    assert entry["reconciliation"]["relation"] == "no-collectives"
    assert entry["reconciliation"]["expected"] is None


# ------------------------------------------------ straggler naming

def test_straggler_names_injected_rank():
    """Stretching one rank's collective time 8x must name that rank as
    the straggler with a communication category dominating."""
    run_s = 0.02
    expected, bb = _model_inputs("potrf")
    tl = dp.synthesize_timeline(run_s, 4, counts=expected,
                                bytes_by_class=bb)
    skewed = dp.stretch_rank(tl, 2, 8.0)
    entry = dp.ingest(skewed, run_s, 4, expected=expected,
                      bytes_by_class=bb, op="potrf", label="skewtest")
    sk = entry["skew"]
    assert sk["slowest_rank"] == 2
    assert sk["dominating_category"] in ("collective", "ici")
    assert sk["value"] > 0
    assert sk["max_step_spread_s"] > 0
    assert sk["per_rank_s"][sk["ranks"].index(2)] == max(
        sk["per_rank_s"])


def test_straggler_compute_category():
    """A compute-stretched rank attributes to compute, not to the
    wire."""
    expected, bb = _model_inputs("potrf")
    tl = dp.synthesize_timeline(0.02, 4, counts=expected,
                                bytes_by_class=bb)
    skewed = dp.stretch_rank(tl, 1, 6.0, categories=("compute",))
    entry = dp.ingest(skewed, 0.02, 4, expected=expected,
                      bytes_by_class=bb, op="potrf")
    assert entry["skew"]["slowest_rank"] == 1
    assert entry["skew"]["dominating_category"] == "compute"


# ------------------------------------------- reconciliation failures

def test_dropped_collective_class_is_named():
    """Dropping every span of one priced class must produce a
    missing-collective diagnostic naming exactly that class."""
    run_s = 0.01
    expected, bb = _model_inputs("potrf")
    drop = sorted(expected)[0]
    tl = dp.synthesize_timeline(run_s, 4, counts=expected,
                                bytes_by_class=bb)
    mutated = [s for s in tl if s.get("cls") != drop]
    entry = dp.ingest(mutated, run_s, 4, expected=expected,
                      bytes_by_class=bb, op="potrf", label="mut")
    assert not entry["ok"]
    assert entry["reconciliation"]["relation"] == "mismatch"
    diags = [d for d in entry["diagnostics"]
             if d["kind"] == "missing-collective"]
    assert [d["op"] for d in diags] == [drop]
    assert drop in diags[0]["message"]


def test_count_mismatch_is_named():
    """Losing a single instance (not the whole class) is a
    count-mismatch, still a failure."""
    run_s = 0.01
    expected, bb = _model_inputs("potrf")
    drop = sorted(expected)[0]
    tl = dp.synthesize_timeline(run_s, 4, counts=expected,
                                bytes_by_class=bb)
    # the ingested count is the max across rank lanes, so one
    # instance must vanish from every rank to register as lost
    mutated = []
    seen = dict.fromkeys(range(4), False)
    for s in tl:
        if s.get("cls") == drop and not seen[s["rank"]]:
            seen[s["rank"]] = True
            continue
        mutated.append(s)
    entry = dp.ingest(mutated, run_s, 4, expected=expected,
                      bytes_by_class=bb, op="potrf")
    assert not entry["ok"]
    kinds = {d["kind"]: d for d in entry["diagnostics"]}
    assert "count-mismatch" in kinds
    assert kinds["count-mismatch"]["op"] == drop


def test_ici_floor_diagnostic():
    """A collective far under the achieved-ICI floor draws the
    ici-floor diagnostic (informational: ok stays True)."""
    expected, bb = _model_inputs("potrf")
    tl = dp.synthesize_timeline(0.01, 4, counts=expected,
                                bytes_by_class=bb)
    # stretch every rank's wire time so achieved bytes/s collapses
    for r in range(4):
        tl = dp.stretch_rank(tl, r, 50.0)
    entry = dp.ingest(tl, 0.5, 4, expected=expected,
                      bytes_by_class=bb, op="potrf", floor=0.5)
    assert any(d["kind"] == "ici-floor" for d in entry["diagnostics"])
    assert entry["ok"]      # floor breach alone is not a failure


# --------------------------------------------- driver end-to-end

@pytest.mark.parametrize("prog,relation", [
    ("testing_dpotrf", "=="),
    ("testing_dgeqrf", "=="),
    ("testing_dgetrf", "no-collectives"),   # getrf_1d: unmodelled
])
def test_driver_devprof_end_to_end(tmp_path, capsys, devices8,
                                   prog, relation):
    """The ISSUE acceptance path: ``--devprof`` on a 2x2 CPU mesh
    produces the schema-v14 ``"devprof"`` report section with
    category seconds within 10% of the timed run and the ingested
    collectives reconciling against the spmdcheck schedule."""
    from dplasma_tpu.drivers import main
    rj = str(tmp_path / "r.json")
    rc = main(["-N", "64", "-t", "16", "-p", "2", "-q", "2",
               "--devprof", f"--report={rj}", "-v=2"], prog=prog)
    out = capsys.readouterr().out
    assert rc == 0
    assert f"#+ devprof[{prog}]:" in out
    doc = load_report(rj)
    assert doc["schema"] == REPORT_SCHEMA == 18
    (entry,) = doc["devprof"]
    assert entry["label"] == prog and entry["ok"]
    assert entry["backend"] == "synthetic"       # CPU mesh
    assert entry["reconciliation"]["relation"] == relation
    best = doc["ops"][0]["timings"]["best_s"]
    assert sum(entry["categories"].values()) == \
        pytest.approx(best, rel=0.10)
    if relation == "==":
        assert entry["collectives"]
        assert entry["reconciliation"]["ingested"] == \
            entry["reconciliation"]["expected"]
        assert any(m["name"] == "devprof_seconds"
                   for m in doc["metrics"])
        assert any(m["name"] == "devprof_ici_achieved_frac"
                   for m in doc["metrics"])


def test_driver_devprof_flag_parses():
    from dplasma_tpu.drivers.common import parse_arguments
    ip = parse_arguments(["-N", "64", "--devprof"])
    assert ip.devprof
    assert not parse_arguments(["-N", "64"]).devprof


# ------------------------------------------------- perfdiff wiring

def _report_with_devprof(tmp_path, name, frac, skew):
    from dplasma_tpu.observability import RunReport
    rep = RunReport("testing_dpotrf")
    rep.add_op("testing_dpotrf", prec="d", flops=1e9, enq_s=0.1,
               warmup_s=0.1, dest_s=0.0, runs_s=[0.01], gflops=100.0)
    entry = dp.attribute("testing_dpotrf", "potrf", 0.01, (2, 2),
                         64, 64, 16)
    for row in entry["collectives"]:
        if row["achieved_frac"] is not None:
            row["achieved_frac"] = frac
    entry["skew"]["value"] = skew
    rep.add_devprof(entry)
    path = str(tmp_path / name)
    rep.write(path)
    return path


def test_perfdiff_extracts_and_gates_devprof(tmp_path):
    """perfdiff sees devprof metrics: a collapsed achieved-ICI
    fraction in the candidate is a regression; skew rides its own
    lower-is-better default threshold."""
    import perfdiff
    base = _report_with_devprof(tmp_path, "base.json", 0.9, 0.0)
    cand = _report_with_devprof(tmp_path, "cand.json", 0.3, 0.0)
    mb = perfdiff.extract_metrics(json.load(open(base)))
    assert "testing_dpotrf.devprof.ici_achieved_frac" in mb
    assert "testing_dpotrf.devprof.skew" in mb
    assert mb["testing_dpotrf.devprof.ici_achieved_frac"]["better"] \
        == "higher"
    assert mb["testing_dpotrf.devprof.skew"]["better"] == "lower"
    rc = perfdiff.main([base, cand, "--threshold", "0.10"])
    assert rc == 1        # 0.9 -> 0.3 achieved frac regresses
    assert perfdiff.main([base, base, "--threshold", "0.10"]) == 0


def test_perfdiff_json_verdict_round_trips(tmp_path, capsys):
    """--json emits the machine-readable verdict mirroring the exit
    code, naming the regressing metrics."""
    import perfdiff
    base = _report_with_devprof(tmp_path, "base.json", 0.9, 0.0)
    cand = _report_with_devprof(tmp_path, "cand.json", 0.2, 0.5)
    out = str(tmp_path / "verdict.json")
    rc = perfdiff.main([base, cand, "--threshold", "0.10",
                        f"--json={out}"])
    capsys.readouterr()
    doc = json.load(open(out))
    assert doc["perfdiff"] == 1
    assert doc["exit_code"] == rc == 1 and doc["ok"] is False
    assert "testing_dpotrf.devprof.ici_achieved_frac" in \
        doc["regressions"]
    assert doc["worst"] is not None
    assert doc["baseline"].endswith("base.json")
    # stdout spelling: --json=- (and the clean self-compare is ok)
    rc = perfdiff.main([base, base, "--json"])
    captured = capsys.readouterr().out
    doc2 = json.loads(captured[captured.index("{"):])
    assert rc == 0 and doc2["ok"] is True and doc2["exit_code"] == 0
    assert doc2["regressions"] == []


def test_perfdiff_json_on_load_error(tmp_path, capsys):
    import perfdiff
    good = _report_with_devprof(tmp_path, "g.json", 0.9, 0.0)
    out = str(tmp_path / "v.json")
    rc = perfdiff.main([good, str(tmp_path / "missing.json"),
                        f"--json={out}"])
    capsys.readouterr()
    assert rc == 2
    doc = json.load(open(out))
    assert doc["exit_code"] == 2 and doc["ok"] is False


# ------------------------------------------------ report round-trip

def test_report_devprof_section_round_trips(tmp_path):
    from dplasma_tpu.observability import RunReport
    rep = RunReport("testing_dpotrf")
    entry = dp.attribute("rt", "potrf", 0.01, (2, 2), 64, 64, 16)
    rep.add_devprof(entry)
    path = str(tmp_path / "r.json")
    rep.write(path)
    doc = load_report(path)
    assert doc["schema"] == REPORT_SCHEMA
    assert doc["devprof"] == [entry]
    assert json.loads(json.dumps(doc["devprof"])) == doc["devprof"]


def test_capture_synthetic_on_cpu():
    """DevprofCapture's auto backend never pretends the CPU mesh has
    a hardware profiler: it resolves to the synthetic backend."""
    with dp.DevprofCapture() as cap:
        pass
    assert cap.used == "synthetic"
    assert cap.events == []


# ---------------------------------------------- the jax backend's reader

FIXTURE = pathlib.Path(__file__).resolve().parent.parent / "benchmark" / \
    "tests" / "data" / "cholesky_f32_16384.xplane.pb.gz"


def _capture_dir(tmp_path, fixture=FIXTURE):
    """A profiler log directory holding the fixture as jax writes it."""
    d = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(gzip.open(fixture).read())
    return str(tmp_path)


def test_jax_timeline_reads_the_chips_xplane(tmp_path):
    """A capture from a TPU v5 lite (one posv call at 16384, f32):
    every XLA op of the device plane becomes a timeline op on rank 0,
    named by its HLO instruction, binned by the shared vocabulary."""
    ops = dp._jax_timeline(_capture_dir(tmp_path))
    assert len(ops) == 4416
    assert {o["rank"] for o in ops} == {0}
    assert ops[0]["name"] == "copy.2376"
    assert ops[0]["category"] == "host"
    assert ops[2]["name"] == "custom-call.309"
    assert ops[2]["end_ns"] - ops[2]["begin_ns"] == 13967
    assert all(o["end_ns"] >= o["begin_ns"] for o in ops)
    cats = {}
    for o in ops:
        cats[o["category"]] = cats.get(o["category"], 0) + 1
    assert cats == {"compute": 4226, "host": 190}
    # the fixture's program predates the library's named scopes
    assert {o["scope"] for o in ops} == {()}
    entry = dp.ingest(ops, 0.0824, 1, backend="jax")
    assert entry["backend"] == "jax" and entry["timeline_ops"] == 4416


def test_xplane_op_names_and_scopes():
    """The op name of each device op comes from its event metadata's
    ``tf_op`` stat; its ``dplasma.*`` components are its scopes."""
    names = dp.xplane_op_names(gzip.open(FIXTURE).read())
    assert list(names) == ["/device:TPU:0"]
    ops = names["/device:TPU:0"]
    assert len(ops) == 2432
    assert sum(1 for v in ops.values()
               if v.startswith("jit(solve)/cholesky")) > 0
    assert dp.op_scopes("jit(solve)/dplasma.potrf/dplasma.panel/"
                        "cholesky:") == ("potrf", "panel")
    assert dp.op_scopes("jit(solve)/jit(_jit_trail)/dplasma.update/"
                        "dplasma.recombine[x]/pallas_call") == (
        "update", "recombine")
    assert dp.op_scopes("jit(solve)/cholesky:") == ()


def test_capture_without_device_ops_says_so(tmp_path):
    """A jax capture that holds no device ops (the CPU has no TPU
    plane) falls back to the synthetic timeline, and says why."""
    import jax.numpy as jnp
    with dp.DevprofCapture(backend="jax", logdir=str(tmp_path)) as cap:
        jnp.ones(8).block_until_ready()
    assert cap.events == []
    assert cap.used == "synthetic"
    assert "no device ops" in cap.note


def test_jax_timeline_carries_named_scopes(tmp_path):
    """A capture of one posv call at 12288 (f32, TPU v5 lite) from a
    tree whose spans open named scopes: each op carries the
    ``dplasma.*`` scopes of its own op name."""
    fixture = FIXTURE.parent / "cholesky_f32_12288_scoped.xplane.pb.gz"
    ops = dp._jax_timeline(_capture_dir(tmp_path, fixture))
    counts = {}
    for o in ops:
        counts[o["scope"]] = counts.get(o["scope"], 0) + 1
    assert counts == {("solve",): 1280, (): 814, ("potrf", "panel"): 812,
                      ("potrf", "far_flush"): 25,
                      ("potrf", "assemble"): 23,
                      ("potrf", "lookahead"): 11}
