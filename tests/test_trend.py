"""The perf observatory: longitudinal series extraction, the
noise-calibrated changepoint detector, provenance stamping (schema
v18), the perfboard dashboard/CI gate, and perfdiff's
--auto-threshold integration."""
import glob
import json
import os
import random
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "tools"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from dplasma_tpu.observability import trend  # noqa: E402
from dplasma_tpu.observability.report import (REPORT_SCHEMA,  # noqa: E402
                                              RunReport, load_report)
import perfboard  # noqa: E402
from tools import perfdiff  # noqa: E402

_ROOT = os.path.join(os.path.dirname(__file__), "..")
#: fixture ledger and artifacts (shapes of the BENCH_r04/r05 and
#: SERVEBENCH_r0* records; the bench series carries sgetrf rows)
_DATA = os.path.join(os.path.dirname(__file__), "data")
_LEDGER = os.path.join(_DATA, "ledger.jsonl")


def _noisy(base, n, frac, seed, step_at=None, step=0.0):
    """A synthetic perf series: relative noise ``frac``, optional
    multiplicative step from ``step_at`` on."""
    rng = random.Random(seed)
    out = []
    for i in range(n):
        v = base * (1.0 + step if step_at is not None
                    and i >= step_at else 1.0)
        out.append(v * (1.0 + rng.uniform(-frac, frac)))
    return out


# ------------------------------------------------ changepoint detector

def test_step_detected_at_exact_index():
    """A clean 20% downward step at index 12 yields EXACTLY one
    changepoint, at index 12 — not 11, not 13, not two."""
    values = _noisy(100.0, 12, 0.004, seed=7) \
        + _noisy(80.0, 8, 0.004, seed=8)
    cps = trend.changepoints(values)
    assert [c["index"] for c in cps] == [12]
    (cp,) = cps
    assert cp["shift"] == pytest.approx(-0.20, abs=0.02)
    assert cp["score"] >= trend.Z_SIGMA


def test_pure_noise_stays_quiet_across_seeds():
    """2% relative noise with NO real shift: zero changepoints and a
    quiet gate across >= 5 seeds — the false-positive budget of the
    CI gate is zero at this noise level."""
    for seed in range(8):
        values = _noisy(1000.0, 20, 0.02, seed=seed)
        assert trend.changepoints(values) == [], f"seed {seed}"
        series = {"key": f"t/s{seed}", "family": "bench",
                  "metric": "m", "knobs": "", "platform": "tpu",
                  "placeholder": False, "better": "higher",
                  "unit": None,
                  "points": [{"value": v} for v in values]}
        v = trend.gate_series(series)
        assert v is not None and v["regression"] is None


def test_single_point_outlier_needs_double_shift():
    """An isolated endpoint excursion below 2x MIN_SHIFT must NOT
    fire (the single-outlier guard), while a genuine fresh 20% drop
    at the series end still does."""
    base = [100.0, 100.2, 99.8, 100.1, 99.9, 100.0]
    assert trend.changepoints(base + [93.0]) == []  # -7% blip: quiet
    cps = trend.changepoints(base + [80.0])         # -20%: fires
    assert [c["index"] for c in cps] == [len(base)]


def test_noise_sigma_calibration():
    """The rolling-MAD noise model: None below MIN_HISTORY, floored
    at NOISE_FLOOR, and tracking the actual noise scale above it."""
    assert trend.noise_sigma([1.0] * (trend.MIN_HISTORY - 1)) is None
    flat = [100.0] * 10
    assert trend.noise_sigma(flat) == trend.NOISE_FLOOR
    noisy = _noisy(100.0, 30, 0.05, seed=3)
    sig = trend.noise_sigma(noisy)
    assert 0.01 < sig < 0.12


# ------------------------------------------------------ series model

def test_placeholder_series_never_gate():
    """PR 16 contract: placeholder-labelled measurements render but
    never gate, even with a huge step."""
    docs = [{"family": "multichip", "placeholder": True,
             "ladder": [{"metric": "m_gflops", "value": v}]}
            for v in (100.0, 100.0, 100.0, 50.0)]
    series = trend.build_series(docs)
    (s,) = series.values()
    assert s["placeholder"] is True
    assert "[placeholder]" in s["key"]
    assert trend.gate_series(s) is None


def test_knob_split_isolates_series():
    """Different resolved knob vectors are different experiments:
    points land in different series, so a tree-vs-chain panel flip
    can never masquerade as a regression."""
    tree = {"panel.qr": "tree", "sweep.lookahead": 2}
    chain = {"panel.qr": "chain", "sweep.lookahead": 2}
    docs = []
    for v, pipe in ((100.0, tree), (99.0, tree), (70.0, chain),
                    (71.0, chain)):
        docs.append({"family": "bench", "pipeline": pipe,
                     "ladder": [{"metric": "m_gflops", "value": v}]})
    series = trend.build_series(docs)
    assert len(series) == 2
    by_len = sorted(series.values(),
                    key=lambda s: s["points"][0]["value"])
    assert [p["value"] for p in by_len[1]["points"]] == [100.0, 99.0]
    assert [p["value"] for p in by_len[0]["points"]] == [70.0, 71.0]


def test_ledger_fragments_are_named_not_fatal(tmp_path):
    """Envelope-less fragments and unparseable lines become NAMED
    notes (path:line); well-formed entries still ingest."""
    p = tmp_path / "h.jsonl"
    p.write_text(
        json.dumps({"family": "bench",
                    "ladder": [{"metric": "a", "value": 1.0}]})
        + "\n"
        + json.dumps({"ladder": [{"metric": "a", "value": 2.0}]})
        + "\n"
        + "{not json\n")
    series, notes = trend.ingest_ledger(p)
    assert len(series) == 1
    assert len(notes) == 2
    assert any(":2:" in n and "envelope-less" in n for n in notes)
    assert any(":3:" in n and "unparseable" in n for n in notes)


def test_repo_ledger_and_artifacts_ingest():
    """The fixture ledger and every fixture and committed artifact
    load through the observatory without error."""
    series, notes = trend.ingest_ledger(_LEDGER)
    assert series
    assert all("family" in s for s in
               (v for v in series.values()))
    paths = sorted(glob.glob(os.path.join(_DATA, "*.json"))) + [
        os.path.join(_ROOT, name) for name in (
            "BENCH_r04.json", "BENCH_r05.json", "MULTICHIP_SCALING.json",
            "SERVEBENCH_r01.json", "SERVEBENCH_r02.json")]
    skipped = 0
    for path in paths:
        docs, art_notes = trend.load_artifact(path)
        assert docs or art_notes  # loaded or skipped WITH a note
        skipped += not docs
    assert skipped == 2  # the timed-out wrapper and the smoke bit


# ------------------------------------------------------- provenance

def test_provenance_stamp_and_report_roundtrip(tmp_path):
    """schema v18: the provenance section survives a report
    write/load round-trip and records the attribution facts."""
    assert REPORT_SCHEMA == 18
    rep = RunReport("bench")
    prov = rep.stamp_provenance(family="bench", mesh_shape=[2, 4],
                                peaks_source="bench")
    assert prov["schema"] == trend.PROVENANCE_SCHEMA
    assert prov["family"] == "bench"
    assert prov["mesh_shape"] == [2, 4]
    assert prov["peaks_source"] == "bench"
    assert "jax" in prov and "backend" in prov
    assert isinstance(prov.get("mca"), dict) or prov.get("mca") is None
    git = prov.get("git")
    if git is not None:  # repo checkouts carry the SHA + dirty bit
        assert isinstance(git["sha"], str) and len(git["sha"]) >= 7
        assert isinstance(git["dirty"], bool)
    p = str(tmp_path / "r.json")
    rep.write(p)
    back = load_report(p)
    assert back["schema"] == 18
    assert back["provenance"] == prov


def test_provenance_rides_series_points(tmp_path):
    """build_series keeps each point's provenance so dashboards can
    answer 'what changed here' per point."""
    doc = {"family": "bench",
           "provenance": {"schema": 1, "backend": "tpu",
                          "git": {"sha": "deadbeef", "dirty": False}},
           "ladder": [{"metric": "m_gflops", "value": 5.0}]}
    series = trend.build_series([doc])
    (s,) = series.values()
    assert s["platform"] == "tpu"  # provenance backend wins
    assert s["points"][0]["provenance"]["git"]["sha"] == "deadbeef"


def test_mca_snapshot_is_the_active_override_set(monkeypatch):
    from dplasma_tpu.utils import config as cfg
    cfg.mca_set("sweep.lookahead", 3)
    try:
        snap = cfg.mca_snapshot()
        assert snap.get("sweep.lookahead") == "3"  # stored as str
    finally:
        cfg.mca_unset("sweep.lookahead")
    assert "sweep.lookahead" not in cfg.mca_snapshot()


# -------------------------------------------------------- perfboard

def test_perfboard_renders_and_checks_green(tmp_path):
    """The dashboard renders from the fixture ledger (sparklines,
    provenance tooltips) and the CI gate is green on it."""
    out = str(tmp_path / "pb.html")
    rc = perfboard.main(["--ledger", _LEDGER, "--check", "--out", out])
    assert rc == 0
    text = open(out).read()
    assert "<svg" in text and "perfboard" in text
    assert "placeholder" in text  # the CPU-mesh series are marked


def test_perfboard_injected_regression_flips_gate(tmp_path, capsys):
    """Acceptance: copy the fixture ledger, append a synthetic 20%
    regression on one bench series -> exit 1 naming the series AND
    the changepoint index."""
    led = str(tmp_path / "h.jsonl")
    lines = open(_LEDGER).read().splitlines()
    target = None
    for ln in lines:
        d = json.loads(ln)
        if d.get("family") == "bench" and d.get("ladder"):
            for e in d["ladder"]:
                if e.get("metric", "").startswith("sgetrf") \
                        and isinstance(e.get("value"), (int, float)):
                    target = (d, e)
    assert target is not None
    doc, row = target
    inject = {"family": "bench", "pipeline": doc.get("pipeline"),
              "provenance": {"schema": 1, "backend": "tpu"},
              "ladder": [{"metric": row["metric"],
                          "value": round(row["value"] * 0.8, 3),
                          "unit": row.get("unit"),
                          "nb": row.get("nb")}]}
    with open(led, "w") as f:
        f.write("\n".join(lines) + "\n")
        f.write(json.dumps(inject) + "\n")
    rc = perfboard.main(["--ledger", led, "--check"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "REGRESSION" in out and "sgetrf" in out
    assert "changepoint @" in out


def test_perfboard_unusable_input_is_exit_2(tmp_path, capsys):
    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    assert perfboard.main(["--ledger", empty, "--check"]) == 2
    assert perfboard.main(["--ledger",
                           str(tmp_path / "missing.jsonl"),
                           "--check"]) == 2


# ------------------------------------------- perfdiff auto-threshold

def _ledger_of(tmp_path, values, metric="a_gflops"):
    led = str(tmp_path / "h.jsonl")
    with open(led, "w") as f:
        for v in values:
            f.write(json.dumps(
                {"family": "bench",
                 "ladder": [{"metric": metric, "value": v}]}) + "\n")
    return led


def test_auto_threshold_equals_fixed_below_min_history(tmp_path):
    """With fewer than MIN_HISTORY ledger points the noise model is
    undefined: --auto-threshold must produce the IDENTICAL verdict
    rows as the fixed-fraction gate (the fallback contract)."""
    led = _ledger_of(tmp_path, [100.0, 101.0, 99.0])
    cand = {"family": "bench",
            "ladder": [{"metric": "a_gflops", "value": 90.0}]}
    base = perfdiff.latest_comparable_entry(led, cand)
    auto = perfdiff.auto_thresholds(led, cand)
    assert auto == {}  # nothing calibratable below MIN_HISTORY
    fixed = perfdiff.compare(base, cand, threshold=0.10)
    auto_res = perfdiff.compare(base, cand, threshold=0.10, auto=auto)
    assert [r["metric"] for r in fixed["regressions"]] \
        == [r["metric"] for r in auto_res["regressions"]]
    for rf, ra in zip(fixed["rows"], auto_res["rows"]):
        assert rf["threshold"] == ra["threshold"]
        assert ra["auto_threshold"] is False


def test_auto_threshold_calibrates_from_history(tmp_path):
    """With enough quiet history the auto threshold comes from the
    series' own noise (z * sigma, floored), and the verdict rows
    carry sigma / effect_sigma / the changepoint index."""
    values = _noisy(100.0, 10, 0.004, seed=11)
    led = _ledger_of(tmp_path, values)
    cand = {"family": "bench",
            "ladder": [{"metric": "a_gflops", "value": 80.0}]}
    auto = perfdiff.auto_thresholds(led, cand)
    assert "a_gflops" in auto
    entry = auto["a_gflops"]
    assert entry["threshold"] == pytest.approx(
        max(trend.Z_SIGMA * entry["sigma"], trend.AUTO_FLOOR))
    assert entry["changepoint"] == len(values)  # the candidate itself
    base = perfdiff.latest_comparable_entry(led, cand)
    res = perfdiff.compare(base, cand, threshold=0.10, auto=auto)
    (reg,) = res["regressions"]
    assert reg["auto_threshold"] is True
    assert reg["sigma"] == pytest.approx(entry["sigma"])
    assert reg["effect_sigma"] > trend.Z_SIGMA
    doc = perfdiff.verdict_doc(res, 1, 0.10, "old", "new")
    row = [r for r in doc["rows"] if r["metric"] == "a_gflops"][0]
    assert {"sigma", "effect_sigma", "auto_threshold"} <= set(row)


def test_perfdiff_cli_auto_threshold(tmp_path, capsys):
    """End to end through main(): --auto-threshold on a quiet ledger
    + regressed candidate exits 1 and names sigma and changepoint in
    the human output."""
    values = _noisy(100.0, 10, 0.004, seed=13)
    led = _ledger_of(tmp_path, values)
    cand = str(tmp_path / "cand.json")
    with open(cand, "w") as f:
        json.dump({"family": "bench",
                   "ladder": [{"metric": "a_gflops",
                               "value": 80.0}]}, f)
    rc = perfdiff.main([led, cand, "--auto-threshold"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "sigma" in out and "changepoint" in out
