"""Phase-level performance attribution: the scoped phase-timer engine
(observability.phases), the roofline efficiency ledger
(observability.roofline), the driver's --phase-profile/--peaks-file
acceptance path, and the tools/perfdiff.py regression gate."""
import contextlib
import json
import re

import jax.numpy as jnp
import pytest

from dplasma_tpu.observability import phases, roofline
from dplasma_tpu.ops import generators
from dplasma_tpu.ops import lu as lu_mod
from tools import perfdiff


# -------------------------------------------------------- phase timers

def test_span_noop_when_inactive(monkeypatch):
    fenced = []
    monkeypatch.setattr(phases, "_fence", fenced.append)
    assert phases.active() is None
    with phases.span("panel") as f:
        assert f(42) == 42          # identity sink, retains nothing
    assert not fenced               # no ledger -> no fencing, no timing


def test_profiling_scope_fence_and_accumulation(monkeypatch):
    fenced = []
    monkeypatch.setattr(phases, "_fence", fenced.append)
    with phases.profiling() as led:
        assert phases.active() is led
        with phases.span("panel") as f:
            assert f("x") == "x"
        with phases.span("panel"):
            pass                    # nothing registered -> no fence
        with phases.span("far_flush") as f:
            f("y")
            f("z")
    assert phases.active() is None  # restored
    assert led.phases["panel"]["count"] == 2
    assert led.phases["far_flush"]["count"] == 1
    assert led.total() == pytest.approx(
        sum(e["seconds"] for e in led.phases.values()))
    assert fenced == [["x"], ["y", "z"]]
    rows = led.summary()
    assert {r["phase"] for r in rows} == {"panel", "far_flush"}
    assert json.loads(json.dumps(rows)) == rows


def test_profiling_nests_and_restores():
    with phases.profiling() as outer:
        with phases.span("a"):
            pass
        with phases.profiling() as inner:
            with phases.span("b"):
                pass
        assert phases.active() is outer
    assert "b" in inner.phases and "b" not in outer.phases
    assert "a" in outer.phases


def test_nested_spans_self_vs_inclusive_time():
    """An enclosing span (the IR solvers' ``factor`` wrapping the
    inner sweep) records SELF time disjoint from its children —
    the ledger still sums to at most the wall time — while its
    ``total_s`` keeps the inclusive elapsed, which is what rates for
    the whole region must divide by."""
    import time as _time
    with phases.profiling() as led:
        with phases.span("factor"):
            with phases.span("panel"):
                _time.sleep(0.02)
            with phases.span("panel"):
                _time.sleep(0.02)
    fac, pan = led.phases["factor"], led.phases["panel"]
    assert pan["count"] == 2
    # child time subtracted from the parent: self < inclusive
    assert fac["seconds"] < fac["total"]
    assert fac["total"] >= pan["seconds"] >= 0.04 - 1e-3
    # ledger stays disjoint: self seconds sum to <= inclusive elapsed
    assert led.total() <= fac["total"] + 1e-6
    rows = {r["phase"]: r for r in led.summary()}
    assert rows["factor"]["measured_s"] == pytest.approx(
        fac["seconds"])
    assert rows["factor"]["total_s"] == pytest.approx(fac["total"])
    # leaf spans: inclusive == self
    assert rows["panel"]["total_s"] == pytest.approx(
        rows["panel"]["measured_s"])


def test_span_fence_failure_keeps_nest_balanced(monkeypatch):
    """A raising fence (poisoned array's block_until_ready — the
    failure --phase-profile degrades to a warning) must not leak the
    nested-span child-time stack: later spans in the same process
    still attribute self-time correctly."""
    def boom(values):
        raise RuntimeError("poisoned")
    monkeypatch.setattr(phases, "_fence", boom)
    with phases.profiling() as led:
        with pytest.raises(RuntimeError):
            with phases.span("factor"):
                with phases.span("panel") as f:
                    f("x")          # registered value -> fence fires
    assert not phases._nest          # stack fully unwound
    # the raising span and its parent still landed in the ledger
    assert led.phases["panel"]["count"] == 1
    assert led.phases["factor"]["count"] == 1
    monkeypatch.setattr(phases, "_fence", lambda values: None)
    with phases.profiling() as led2:
        with phases.span("a"):
            with phases.span("b") as f:
                f("y")
    assert not phases._nest
    assert led2.phases["a"]["total"] >= led2.phases["a"]["seconds"]


def test_sweep_engine_spans_match_phase_model(monkeypatch):
    """Eager getrf_nopiv under an active ledger emits exactly the
    span counts the analytic roofline model predicts (the model
    mirrors pipelined_sweep's control flow), and fences each one."""
    fences = []
    monkeypatch.setattr(phases, "_fence", fences.append)
    A = generators.plghe(128.0, 128, 32, seed=5, dtype=jnp.float32)
    lu_mod.getrf_nopiv(A, lookahead=1)     # default path: no ledger
    assert not fences                      # -> never fences
    with phases.profiling() as led:
        lu_mod.getrf_nopiv(A, lookahead=1)
    assert fences                          # profiled path fences
    model = roofline.phase_model("getrf", 128, 128, 32, 4,
                                 lookahead=1, agg_depth=1)
    for name in ("panel", "lookahead", "far_flush", "assemble"):
        assert led.phases[name]["count"] == model[name][2], name


# ------------------------------------------------------------ roofline

def test_expected_seconds_bounds():
    p = dict(roofline.DEFAULT_PEAKS)
    s, b, comp = roofline.expected_seconds(flops=1e12, peaks=p)
    assert b == "mxu"
    assert s == pytest.approx(1e12 / (p["mxu_gflops"] * 1e9))
    assert s == comp["mxu"] >= comp["hbm"]
    assert roofline.expected_seconds(hbm_bytes=1e12, peaks=p)[1] == "hbm"
    assert roofline.expected_seconds(ici_bytes=1e12, peaks=p)[1] == "ici"
    assert roofline.expected_seconds(dispatches=100,
                                     peaks=p)[1] == "latency"
    # all-zero demands: the tie breaks to the first label, not a crash
    s0, b0, _ = roofline.expected_seconds(peaks=p)
    assert s0 == 0.0 and b0 in roofline.BOUNDS


def test_resolve_peaks_sources(tmp_path):
    p, src = roofline.resolve_peaks(None, prec="s")
    assert p == roofline.DEFAULT_PEAKS and src == "default"
    # bench doc shape: precision maps to the probed peak
    bench = {"peaks": {"f32_highest_gflops": 20000.0,
                       "f64equiv_bound_gflops": 5000.0,
                       "hbm_gbps": 800.0}}
    f = tmp_path / "bench.json"
    f.write_text(json.dumps(bench))
    p, src = roofline.resolve_peaks(str(f), prec="s")
    assert p["mxu_gflops"] == 20000.0 and p["hbm_gbps"] == 800.0
    assert p["ici_gbps"] == roofline.DEFAULT_PEAKS["ici_gbps"]
    assert src == f"file:{f}"
    assert roofline.resolve_peaks(str(f), prec="d")[0][
        "mxu_gflops"] == 5000.0
    # run-report shape: peaks under extra.peaks
    g = tmp_path / "report.json"
    g.write_text(json.dumps(
        {"schema": 5, "extra": {"peaks": {"mxu_gflops": 123.0}}}))
    assert roofline.resolve_peaks(str(g))[0]["mxu_gflops"] == 123.0
    # raw peaks dict
    h = tmp_path / "raw.json"
    h.write_text(json.dumps({"mxu_gflops": 7.0, "latency_us": 1.0}))
    p, _ = roofline.resolve_peaks(str(h))
    assert p["mxu_gflops"] == 7.0 and p["latency_us"] == 1.0
    # malformed peaks sections raise ValueError (which the driver's
    # degrade-to-defaults handler catches), never AttributeError
    for bad in ({"peaks": [1, 2]}, [1, 2]):
        j = tmp_path / "bad.json"
        j.write_text(json.dumps(bad))
        with pytest.raises(ValueError):
            roofline.resolve_peaks(str(j))


def test_phase_model_flops_invariant_in_pipeline_shape():
    """The pipeline split moves update work between phases but never
    creates or loses flops; unmodelled classes return None."""
    tot = lambda m: sum(v[0] for v in m.values())  # noqa: E731
    base = roofline.phase_model("getrf", 256, 256, 64, 4,
                                lookahead=0, agg_depth=1)
    for la in (1, 2, 3):
        m = roofline.phase_model("getrf", 256, 256, 64, 4,
                                 lookahead=la, agg_depth=1)
        assert tot(m) == pytest.approx(tot(base))
        assert "lookahead" in m
    assert "lookahead" not in base and "far_flush" in base
    qb = roofline.phase_model("geqrf", 256, 256, 64, 4,
                              lookahead=1, agg_depth=1)
    qa = roofline.phase_model("geqrf", 256, 256, 64, 4,
                              lookahead=1, agg_depth=4)
    # aggregation reduces far-flush dispatches, not panel count
    assert qa["panel"][2] == qb["panel"][2]
    assert qa.get("far_flush", [0, 0, 0])[2] <= qb["far_flush"][2]
    assert roofline.phase_model("potrf", 128, 128, 32, 8,
                                lookahead=1)["panel"][2] == 4
    assert roofline.phase_model("gemm", 256, 256, 64, 4) is None
    assert roofline.phase_model(None, 256, 256, 64, 4) is None


def test_attribute_phases_and_op_roofline():
    led = phases.PhaseLedger()
    led.add("panel", 0.5)
    led.add("mystery", 0.1)
    model = {"panel": [1e9, 1e6, 1]}
    spans = roofline.attribute_phases(led, model,
                                      dict(roofline.DEFAULT_PEAKS))
    by = {s["phase"]: s for s in spans}
    assert by["panel"]["expected_s"] > 0
    assert by["panel"]["achieved_frac"] == pytest.approx(
        by["panel"]["expected_s"] / 0.5)
    assert by["panel"]["bound"] in roofline.BOUNDS
    # unknown phases still get a (latency) bound, never a crash
    assert by["mystery"]["bound"] == "latency"
    comm = {"dag_model": {"bytes_total": 1e9}, "spmd_model": None}
    rl = roofline.op_roofline("testing_dgetrf", "getrf", 512, 512, 1,
                              8, 1e9, comm, measured_s=1.0,
                              peaks=dict(roofline.DEFAULT_PEAKS))
    assert rl["bound"] in roofline.BOUNDS
    assert rl["components_s"]["ici"] == pytest.approx(
        1e9 / (roofline.DEFAULT_PEAKS["ici_gbps"] * 1e9))
    assert 0 < rl["achieved_frac"] <= 1.0 or rl["expected_s"] > 1.0
    assert json.loads(json.dumps(rl)) == rl


# ----------------------------------------- driver acceptance (e2e CPU)

def _phase_run(tmp_path, prog, extra=()):
    from dplasma_tpu.drivers import main
    rj = str(tmp_path / "r.json")
    rc = main(["-N", "96", "-t", "32", "--phase-profile",
               f"--report={rj}", "-v=2", *extra], prog=prog)
    assert rc == 0
    return json.load(open(rj))


@pytest.mark.parametrize("prog", ["testing_dgetrf", "testing_dgeqrf"])
def test_driver_phase_profile_acceptance(tmp_path, capsys, prog):
    """The ISSUE acceptance: with --phase-profile a dgetrf/dgeqrf
    run-report carries per-phase {measured_s, expected_s,
    achieved_frac, bound} summing (within fencing/out-of-span
    overhead) to the attributed run time."""
    doc = _phase_run(tmp_path, prog)
    out = capsys.readouterr().out
    assert doc["schema"] == 18
    (op,) = doc["ops"]
    ph = op["phases"]
    spans = ph["spans"]
    assert spans
    names = {s["phase"] for s in spans}
    assert "panel" in names
    for s in spans:
        assert {"phase", "count", "measured_s", "expected_s",
                "achieved_frac", "bound"} <= set(s)
        assert s["bound"] in ("mxu", "hbm", "ici", "latency")
        assert s["measured_s"] > 0 and s["expected_s"] >= 0
    assert ph["sum_s"] == pytest.approx(
        sum(s["measured_s"] for s in spans))
    # phases sum to the attributed run time, modulo the out-of-span
    # harness work (slicing, sync) and fencing overhead
    assert ph["sum_s"] <= ph["attributed_run_s"]
    assert ph["coverage"] == pytest.approx(
        ph["sum_s"] / ph["attributed_run_s"])
    assert ph["coverage"] > 0.25
    # whole-op roofline entry rides along
    (rl,) = doc["roofline"]
    assert rl["op"] == prog and rl["bound"] in roofline.BOUNDS
    assert rl["measured_s"] > 0 and rl["achieved_frac"] is not None
    # per-phase table + roofline line print at -v>=2
    assert f"#+ phases[{prog}]" in out and f"#+ roofline[{prog}]" in out
    # metrics carry the attribution too
    assert any(m["name"] == "phase_seconds" for m in doc["metrics"])
    assert any(m["name"] == "roofline_achieved_frac"
               for m in doc["metrics"])


def test_driver_phase_profile_off_no_fencing(tmp_path, monkeypatch):
    """With the flag off the default path never fences (fusion/overlap
    untouched) and the op entry carries an explicit phases null."""
    fences = []
    monkeypatch.setattr(phases, "_fence", fences.append)
    from dplasma_tpu.drivers import main
    rj = str(tmp_path / "r.json")
    rc = main(["-N", "96", "-t", "32", f"--report={rj}", "--nruns",
               "2"], prog="testing_dgetrf")
    assert rc == 0 and not fences
    doc = json.load(open(rj))
    (op,) = doc["ops"]
    assert op["phases"] is None
    assert op["timings"]["nruns"] == 2
    assert op["timings"]["best_s"] > 0
    # the roofline ledger still prices the op (it needs no fencing)
    (rl,) = doc["roofline"]
    assert rl["peaks_source"] == "default"


def test_driver_peaks_file(tmp_path, capsys):
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"mxu_gflops": 1e6, "hbm_gbps": 1e5,
                                 "latency_us": 0.001}))
    doc = _phase_run(tmp_path, "testing_dgetrf",
                     extra=[f"--peaks-file={peaks}"])
    (rl,) = doc["roofline"]
    assert rl["peaks"]["mxu_gflops"] == 1e6
    assert rl["peaks_source"].startswith("file:")
    # absurdly fast peaks -> tiny expectations -> tiny achieved_frac
    assert rl["achieved_frac"] < 1.0


def test_driver_peaks_file_unreadable_degrades(tmp_path, capsys):
    doc = _phase_run(tmp_path, "testing_dgetrf",
                     extra=["--peaks-file=/nonexistent/peaks.json"])
    (rl,) = doc["roofline"]
    assert rl["peaks_source"] == "default"   # warned, not failed
    assert doc["ops"][0]["phases"] is not None
    # a malformed (non-dict) peaks section degrades the same way
    bad = tmp_path / "bad_peaks.json"
    bad.write_text(json.dumps({"peaks": [1, 2]}))
    doc = _phase_run(tmp_path, "testing_dgetrf",
                     extra=[f"--peaks-file={bad}"])
    assert doc["roofline"][0]["peaks_source"] == "default"


# ------------------------------------------------------------ perfdiff

def _report_doc(median=0.010, best=0.009, gflops=100.0,
                label="testing_dgetrf"):
    return {"schema": 5, "name": label,
            "ops": [{"label": label, "prec": "d", "gflops": gflops,
                     "timings": {"nruns": 3, "median_s": median,
                                 "best_s": best}}],
            "metrics": []}


def _write(tmp_path, name, doc):
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


def test_perfdiff_self_compare_exits_zero(tmp_path, capsys):
    a = _write(tmp_path, "a.json", _report_doc())
    assert perfdiff.main([a, a]) == 0
    assert "OK" in capsys.readouterr().out


def test_perfdiff_regression_named_nonzero(tmp_path, capsys):
    a = _write(tmp_path, "a.json", _report_doc(median=0.010))
    b = _write(tmp_path, "b.json", _report_doc(median=0.015))
    assert perfdiff.main([a, b]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out
    assert "testing_dgetrf.median_s" in out
    assert "worst offender" in out


def test_perfdiff_improvement_and_threshold(tmp_path, capsys):
    a = _write(tmp_path, "a.json", _report_doc(median=0.010))
    b = _write(tmp_path, "b.json", _report_doc(median=0.006,
                                               best=0.005,
                                               gflops=150.0))
    assert perfdiff.main([a, b]) == 0            # faster is fine
    c = _write(tmp_path, "c.json", _report_doc(median=0.012))
    assert perfdiff.main([a, c]) == 1            # +20% > default 10%
    capsys.readouterr()
    assert perfdiff.main([a, c, "--threshold", "0.5"]) == 0
    # per-metric override: only median_s is relaxed
    assert perfdiff.main([a, c, "--metric-threshold",
                          "median_s=0.5"]) == 0


def test_perfdiff_gflops_drop_is_regression(tmp_path, capsys):
    a = _write(tmp_path, "a.json", _report_doc())
    b = _write(tmp_path, "b.json",
               _report_doc(median=0.010, best=0.009, gflops=50.0))
    assert perfdiff.main([a, b]) == 1
    assert "testing_dgetrf.gflops" in capsys.readouterr().out


def test_perfdiff_bench_ledger_newest_entry(tmp_path, capsys):
    bench_old = {"metric": "x", "family": "bench", "ladder": [
        {"metric": "spotrf_gflops_n2048", "value": 100.0,
         "unit": "GFlop/s", "vs_baseline": 1.0}]}
    bench_new = {"metric": "x", "family": "bench", "ladder": [
        {"metric": "spotrf_gflops_n2048", "value": 200.0,
         "unit": "GFlop/s", "vs_baseline": 2.0}]}
    ledger = tmp_path / "bench_history.jsonl"
    perfdiff.append_ledger(str(ledger), bench_old)
    perfdiff.append_ledger(str(ledger), bench_new)
    assert perfdiff.latest_ledger_entry(str(ledger)) == bench_new
    # candidate regressed vs the NEWEST entry (200 -> 120 = -40%)
    cand = _write(tmp_path, "cand.json", {"metric": "x", "ladder": [
        {"metric": "spotrf_gflops_n2048", "value": 120.0,
         "unit": "GFlop/s", "vs_baseline": 1.2}]})
    assert perfdiff.main([str(ledger), cand]) == 1
    assert "spotrf_gflops_n2048" in capsys.readouterr().out


def test_perfdiff_reports_vanished_baseline_metrics(tmp_path, capsys):
    """An op that regressed into failure records no timing at all —
    its baseline metrics must be surfaced as absent, not silently
    dropped from the comparison."""
    old = _report_doc()
    old["ops"].append({"label": "testing_dpotrf", "prec": "d",
                       "gflops": 50.0,
                       "timings": {"nruns": 1, "median_s": 0.02,
                                   "best_s": 0.02}})
    new = _report_doc()                      # dpotrf vanished
    res = perfdiff.compare(old, new)
    assert res["missing"] == ["testing_dpotrf.best_s",
                              "testing_dpotrf.gflops",
                              "testing_dpotrf.median_s"]
    a = _write(tmp_path, "a.json", old)
    b = _write(tmp_path, "b.json", new)
    perfdiff.main([a, b])
    out = capsys.readouterr().out
    assert "absent from candidate" in out
    assert "testing_dpotrf.median_s" in out


def test_perfdiff_unusable_inputs(tmp_path, capsys):
    a = _write(tmp_path, "a.json", _report_doc())
    bare = _write(tmp_path, "bare.json",
                  {"schema": 1, "ops": [], "metrics": []})
    assert perfdiff.main([a, bare]) == 2         # nothing extractable
    assert perfdiff.main([a, str(tmp_path / "missing.json")]) == 2
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert perfdiff.main([str(empty), a]) == 2
    assert perfdiff.main([a, a, "--metric-threshold", "oops"]) == 2


def test_perfdiff_new_metrics_are_informational(tmp_path, capsys):
    """Candidate metrics with no baseline counterpart exit 0 with a
    note — the FIRST entry of a new metric family (e.g. the serving
    layer's first v8 ledger entry against a pre-serving baseline)
    seeds the baseline; it cannot regress, and it must not break
    ``bench.py --gate`` / ``servebench --gate``."""
    a = _write(tmp_path, "a.json", _report_doc())
    other = _write(tmp_path, "o.json", _report_doc(label="elsewhere"))
    assert perfdiff.main([a, other]) == 0
    out = capsys.readouterr().out
    assert "not in baseline" in out and "elsewhere.median_s" in out
    # disjoint-but-new metrics alongside a common one still gate the
    # common one
    serving = _report_doc()
    serving["entries"] = [{"metric": "serving.p50_ms", "value": 3.0,
                           "better": "lower"}]
    res = perfdiff.compare(_report_doc(), serving)
    assert res["new"] == ["serving.p50_ms"] and res["ok"]


def test_perfdiff_latest_comparable_entry(tmp_path):
    """Gates sharing one ledger across bench families must baseline
    against the newest SAME-FAMILY entry, or interleaved bench.py /
    servebench runs would compare cross-family forever (compared==0,
    informational pass) and never gate a real regression."""
    ledger = str(tmp_path / "h.jsonl")
    e1 = {"family": "bench",
          "ladder": [{"metric": "a_gflops", "value": 10.0}]}
    e2 = {"family": "servebench",
          "entries": [{"metric": "serving.p50_ms", "value": 5.0,
                       "better": "lower"}]}
    e3 = {"family": "bench",
          "ladder": [{"metric": "a_gflops", "value": 11.0}]}
    for e in (e1, e2, e3):
        perfdiff.append_ledger(ledger, e)
    cand = {"entries": [{"metric": "serving.p50_ms", "value": 6.0,
                         "better": "lower"}]}
    assert perfdiff.latest_comparable_entry(ledger, cand) == e2
    candl = {"ladder": [{"metric": "a_gflops", "value": 9.0}]}
    assert perfdiff.latest_comparable_entry(ledger, candl) == e3
    # nothing comparable (or no metrics at all): newest raw entry,
    # so the callers' vacuous-gate handling still engages
    assert perfdiff.latest_comparable_entry(ledger, {"ops": []}) == e3


def test_perfdiff_skips_envelope_less_fragments(tmp_path, capsys):
    """The ledger envelope contract (schema v18): entries carrying
    neither a ``"family"`` key nor a run-report ``"schema"`` are
    fragments from pre-contract writers — they are skipped as
    baselines with a note naming the ledger line, never silently
    compared."""
    ledger = str(tmp_path / "h.jsonl")
    frag = {"ladder": [{"metric": "a_gflops", "value": 10.0}]}
    good = {"family": "bench",
            "ladder": [{"metric": "a_gflops", "value": 11.0}]}
    perfdiff.append_ledger(ledger, frag)
    perfdiff.append_ledger(ledger, good)
    perfdiff.append_ledger(ledger, frag)  # newest entry: a fragment
    cand = {"family": "bench",
            "ladder": [{"metric": "a_gflops", "value": 12.0}]}
    base = perfdiff.latest_comparable_entry(ledger, cand)
    assert base == good  # the fragment after it was skipped
    err = capsys.readouterr().err
    assert "envelope-less ledger fragment" in err
    assert f"{ledger}:3:" in err
    # a ledger of ONLY fragments yields no baseline at all
    ledger2 = str(tmp_path / "frags.jsonl")
    perfdiff.append_ledger(ledger2, frag)
    assert perfdiff.latest_comparable_entry(ledger2, cand) is None


def test_perfdiff_baseline_prefers_same_pipeline(tmp_path):
    """Same-family baselining keys on the recorded pipeline section
    (panel-engine strategy included): a chain-panel rerun interleaved
    after a tree-panel entry must not become the next tree run's
    baseline; with no same-strategy entry the newest same-family
    entry still serves (the r05 -> r06 first-comparison case)."""
    ledger = str(tmp_path / "h.jsonl")
    tree = {"sweep.lookahead": 1, "qr.agg_depth": 4,
            "panel.kernel": "auto", "panel.qr": "tree",
            "panel.lu": "rec"}
    chain = dict(tree, **{"panel.qr": "chain", "panel.lu": "chain"})
    e_tree = {"family": "bench", "pipeline": tree,
              "ladder": [{"metric": "a_gflops", "value": 10.0}]}
    e_chain = {"family": "bench", "pipeline": chain,
               "ladder": [{"metric": "a_gflops", "value": 7.0}]}
    for e in (e_tree, e_chain):
        perfdiff.append_ledger(ledger, e)
    cand = {"pipeline": dict(tree),
            "ladder": [{"metric": "a_gflops", "value": 11.0}]}
    assert perfdiff.latest_comparable_entry(ledger, cand) == e_tree
    # no same-pipeline prior (e.g. pre-panel-key vintages): newest
    # same-family entry remains the baseline
    cand2 = {"pipeline": dict(tree, **{"panel.qr": "pallas"}),
             "ladder": [{"metric": "a_gflops", "value": 11.0}]}
    assert perfdiff.latest_comparable_entry(ledger, cand2) == e_chain


def test_perfdiff_compare_api_old_schema_docs():
    """v1-vintage docs (no nruns, no phases) compare fine — the
    extractor only touches always-present keys."""
    old = {"schema": 1, "ops": [{"label": "op",
                                 "timings": {"median_s": 1.0}}]}
    new = {"schema": 5, "ops": [{"label": "op",
                                 "timings": {"nruns": 1,
                                             "median_s": 2.0}}]}
    res = perfdiff.compare(old, new)
    assert not res["ok"] and res["worst"]["metric"] == "op.median_s"
    assert res["worst"]["regression"] == pytest.approx(1.0)


# ------------------------------------------- the ici roofline component

def test_ring_span_makes_ici_bound_reachable():
    """The satellite this closes: roofline.expected_seconds' ``ici``
    component was never validated against a measured span — no phase
    table ever showed ``bound == "ici"``. With the ``ring`` span
    (the cyclic wrappers' panel-broadcast microprogram) priced by
    ring_phase_demand, the ici bound is reachable: at this shape the
    panel-broadcast wire bytes dominate both the latency floor and
    the (zero) flop/HBM demand."""
    led = phases.PhaseLedger()
    led.add("ring", 0.05)
    model = roofline.phase_model("potrf", 512, 512, 64, 8,
                                 lookahead=1, grid=(2, 2))
    assert isinstance(model.get("ring"), dict)
    assert model["ring"]["ici_bytes"] > 0
    spans = roofline.attribute_phases(led, model)
    (row,) = [r for r in spans if r["phase"] == "ring"]
    assert row["bound"] == "ici"
    assert 0 < row["expected_s"]
    assert row["achieved_frac"] == pytest.approx(
        row["expected_s"] / 0.05)


def test_ring_phase_demand_gating():
    """No ring demand on 1x1 grids or unmodelled classes; the priced
    bytes follow the ring.enable resolution's schedule (psum on CPU
    auto — both are valid lower bounds for the probe)."""
    assert roofline.ring_phase_demand("potrf", 256, 256, 32, 8,
                                      (1, 1)) is None
    assert roofline.ring_phase_demand("gemm", 256, 256, 32, 8,
                                      (2, 2)) is None
    d = roofline.ring_phase_demand("getrf", 256, 256, 32, 8, (2, 2))
    assert d["ici_bytes"] > 0
    assert roofline.phase_model("potrf", 256, 256, 32, 8,
                                grid=(1, 1)) is not None


def test_cyclic_wrappers_emit_ring_span(devices8):
    """potrf_cyclic under an active ledger runs the panel-broadcast
    microprogram in a ``ring`` span (and never otherwise — the span
    only fires while profiling is on, keeping the default path
    untouched)."""
    import numpy as np

    from dplasma_tpu.descriptors import Dist
    from dplasma_tpu.parallel import cyclic
    from dplasma_tpu.parallel import mesh as pmesh

    nb, nt = 4, 3
    m = pmesh.make_mesh(2, 2, devices8)
    with pmesh.use_grid(m):
        A0 = generators.plghe(float(nt * nb), nt * nb, nb, seed=3872,
                              dtype="float32")
        C = cyclic.CyclicMatrix.from_tile(A0, Dist(P=2, Q=2))
        with phases.profiling() as led:
            out = cyclic.potrf_cyclic(C, "L")
        assert np.isfinite(np.asarray(out.data)).all()
    rows = {r["phase"]: r for r in led.summary()}
    assert "ring" in rows and rows["ring"]["count"] == 1
    assert rows["ring"]["measured_s"] > 0


# ---------------------------------------------- named scopes (compiled)

#: the three programs the chip benchmark times, at a small size:
#: program -> (dtype, MCA overrides, grid or None)
SCOPED = {"posv_f32": ("float32", {}, None),
          "posv_dd": ("float64", {"dd_gemm": "always"}, None),
          "getrf_getrs_2x2": ("float32", {}, (2, 2))}
#: the scopes each program's trace opens (grid: the ICI ring is off on
#: the CPU, so its ``ring`` span never runs inside the program; getrs
#: solves on the cyclic slabs, so no ``laswp``)
SCOPES_OPENED = {
    "posv_f32": {"potrf", "panel", "lookahead", "far_flush", "assemble",
                 "solve"},
    "posv_dd": {"potrf", "update", "panel", "split", "recombine",
                "assemble", "solve"},
    "getrf_getrs_2x2": {"getrf", "redistribute", "panel", "bcast",
                        "elect", "playoff", "exchange", "update",
                        "lookahead", "solve"},
}
#: the --phase-profile ledger of one eager call, as it was before the
#: spans opened named scopes (scope-only spans are never timed)
LEDGER_COUNTS = {
    "posv_f32": {"panel": 4, "far_flush": 2, "assemble": 1,
                 "lookahead": 3},
    "posv_dd": {},
    "getrf_getrs_2x2": {"ring": 1},
}
_SCOPE_N, _SCOPE_NB = 128, 32


@contextlib.contextmanager
def _scoped_program(name, devices):
    """(fn, args) of one program inside its MCA and grid context."""
    from tests.conftest import mca_overrides
    from dplasma_tpu.ops import potrf as potrf_mod
    from dplasma_tpu.parallel import mesh as pmesh
    dtype, mca, grid = SCOPED[name]
    n, nb = _SCOPE_N, _SCOPE_NB
    B = generators.plrnt(n, 1, nb, 1, seed=4, dtype=dtype)
    if grid is None:
        A = generators.plghe(float(n), n, nb, seed=3, dtype=dtype)

        def fn(a, b):
            return potrf_mod.posv(A.like(a), B.like(b))[1].data
    else:
        A = generators.plrnt(n, n, nb, nb, seed=3, dtype=dtype)

        def fn(a, b):
            F, perm = lu_mod.getrf_ptgpanel(A.like(a))
            return lu_mod.getrs("N", F, perm, B.like(b)).data
    grid_cm = (pmesh.use_grid(pmesh.make_mesh(*grid, devices))
               if grid else contextlib.nullcontext())
    with mca_overrides(mca), grid_cm:
        yield fn, (A.data, B.data)


def _compiled_text(fn, args) -> str:
    """Compiled HLO text of a fresh trace: JAX's in-memory caches are
    cleared (an inner jit's cached jaxpr keeps the scopes of its first
    trace), and the persistent cache keys on metadata, so a program
    that differs only in its scopes is compiled, not loaded."""
    import jax
    jax.clear_caches()
    prev = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    try:
        return jax.jit(fn).lower(*args).compile().as_text()
    finally:
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", prev)
        jax.clear_caches()


def _strip_metadata(text: str) -> str:
    """The HLO text without its debug information: each instruction's
    ``metadata={...}`` and the source tables (file names, functions,
    locations, stack frames) the metadata points into."""
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    return "\n".join(line for line in text.splitlines()
                     if not re.match(r'\d+ [{"]', line))


@pytest.fixture(scope="module")
def scoped_hlo(devices8):
    """{program: (scopes opened while tracing, HLO with scopes, HLO
    with phases' named scope replaced by a null context)}."""
    out = {}
    real = phases._named_scope
    for name in SCOPED:
        opened = set()

        def recording(scope):
            opened.add(scope)
            return real(scope)

        with _scoped_program(name, devices8) as (fn, args):
            phases._named_scope = recording
            try:
                scoped = _compiled_text(fn, args)
                phases._named_scope = (
                    lambda scope: contextlib.nullcontext())
                plain = _compiled_text(fn, args)
            finally:
                phases._named_scope = real
        out[name] = (opened, scoped, plain)
    return out


@pytest.mark.parametrize("prog", sorted(SCOPED))
def test_span_scopes_reach_compiled_op_names(prog, scoped_hlo):
    """Every span the program's trace opens names its ``dplasma.<name>``
    scope, and each appears in the compiled HLO's op_name metadata."""
    opened, scoped, _ = scoped_hlo[prog]
    assert opened == SCOPES_OPENED[prog]
    assert opened <= set(phases.SCOPES)
    op_names = " ".join(re.findall(r'op_name="([^"]*)"', scoped))
    for name in opened:
        assert f"{phases.SCOPE_PREFIX}{name}/" in op_names, name


@pytest.mark.parametrize("prog", sorted(SCOPED))
def test_scopes_leave_compiled_hlo_unchanged(prog, scoped_hlo):
    """The scopes are metadata only: with the metadata stripped, the
    compiled program is the same instruction for instruction."""
    _, scoped, plain = scoped_hlo[prog]
    assert phases.SCOPE_PREFIX in scoped
    assert phases.SCOPE_PREFIX not in plain
    assert _strip_metadata(scoped) == _strip_metadata(plain)


@pytest.mark.parametrize("prog", sorted(SCOPED))
def test_phase_profile_ledger_counts_unchanged(prog, devices8,
                                               monkeypatch):
    """Under --phase-profile (an eager call with a ledger active) the
    ledger counts what it counted before the spans opened scopes: the
    scope-only spans are neither timed nor fenced."""
    import jax
    monkeypatch.setattr(phases, "_fence", jax.block_until_ready)
    with _scoped_program(prog, devices8) as (fn, args):
        with phases.profiling() as led:
            jax.block_until_ready(fn(*args))
    assert {r["phase"]: r["count"] for r in led.summary()} == \
        LEDGER_COUNTS[prog]


def _span_names(source: str, filename: str = "<src>"):
    """(name or None, line) of every ``phases.span(...)`` call in a
    module's source; None where the name is not a string literal."""
    import ast
    out = []
    for node in ast.walk(ast.parse(source, filename)):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        direct = isinstance(f, ast.Name) and f.id == "span"
        attr = (isinstance(f, ast.Attribute) and f.attr == "span"
                and isinstance(f.value, ast.Name)
                and f.value.id in ("phases", "_phases"))
        if not (direct or attr):
            continue
        arg = node.args[0] if node.args else None
        lit = (arg.value if isinstance(arg, ast.Constant)
               and isinstance(arg.value, str) else None)
        out.append((lit, node.lineno))
    return out


def test_span_names_are_in_the_scope_vocabulary():
    """Every span the package opens names a literal in
    ``phases.SCOPES`` (one vocabulary for the ledger, the compiled
    programs' scopes and the benchmark's readers); an unknown or
    computed name fails here."""
    import pathlib
    pkg = pathlib.Path(phases.__file__).resolve().parent.parent
    bad, seen = [], set()
    for path in sorted(pkg.rglob("*.py")):
        src = path.read_text()
        if "span(" not in src:
            continue
        for name, line in _span_names(src, str(path)):
            seen.add(name)
            if name not in phases.SCOPES:
                bad.append(f"{path.relative_to(pkg)}:{line} {name!r}")
    assert not bad, bad
    assert {"potrf", "panel", "recombine", "redistribute"} <= seen
    assert len(set(phases.SCOPES)) == len(phases.SCOPES)
    # the sweep itself catches an unknown and a computed name
    probe = ("with phases.span('pannel'):\n    pass\n"
             "with span(f'x{k}', timed=False):\n    pass\n")
    assert [n for n, _ in _span_names(probe)] == ["pannel", None]
