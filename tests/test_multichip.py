"""Per-chip-count scaling harness (tools/multichip.py).

The MULTICHIP artifact's upgrade from smoke bit to measurement: the
scaling sweep runs the realized block-cyclic kernels at every chip
count, lands a ``"scaling"`` section (added in schema v12) +
higher-better ledger entries with CPU-mesh runs explicitly labelled
``"placeholder"``, optionally attributes every point with devprof,
and self-gates through perfdiff (informational on the CPU
host-platform mesh, binding on accelerators — the plumbing is
identical).
"""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "tools"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import multichip  # noqa: E402
from tools import perfdiff  # noqa: E402


def test_run_scaling_points_and_efficiency(devices8):
    """One op over 1/2 chips: per point grid/median/gflops recorded,
    parallel efficiency = T1/(chips*Tc), == 1.0 at one chip."""
    scaling = multichip.run_scaling(["potrf"], 32, 8, [1, 2],
                                    nruns=1, log=lambda s: None)
    (sec,) = scaling
    assert sec["op"] == "potrf" and sec["prec"] == "d"
    assert sec["ring"] in ("auto", "on", "off")
    pts = sec["points"]
    assert [p["chips"] for p in pts] == [1, 2]
    assert pts[0]["grid"] == [1, 1] and pts[1]["grid"] == [1, 2]
    assert pts[0]["parallel_efficiency"] == 1.0
    t1 = pts[0]["median_s"]
    # the efficiency is stored rounded to 4 decimals
    assert pts[1]["parallel_efficiency"] == pytest.approx(
        t1 / (2 * pts[1]["median_s"]), abs=5e-5)
    assert all(p["median_s"] > 0 and p["gflops"] > 0 for p in pts)


def test_ledger_doc_higher_better_entries():
    scaling = [{"op": "potrf", "prec": "d", "n": 32, "nb": 8,
                "ring": "auto",
                "points": [{"chips": 1, "grid": [1, 1],
                            "median_s": 0.1, "gflops": 2.0,
                            "parallel_efficiency": 1.0},
                           {"chips": 8, "grid": [2, 4],
                            "median_s": 0.05, "gflops": 4.0,
                            "parallel_efficiency": 0.25}]}]
    doc = multichip.ledger_doc(scaling, 32)
    metrics = perfdiff.extract_metrics(doc)
    assert metrics["multichip_dpotrf_n32_c8_gflops"] == {
        "value": 4.0, "better": "higher"}
    assert metrics["multichip_dpotrf_n32_c8_eff"] == {
        "value": 0.25, "better": "higher"}
    assert metrics["multichip_dpotrf_n32_c1_gflops"]["value"] == 2.0
    # the knob vector rides along for same-vector baselining
    assert "ring.enable" in doc["pipeline"]


def test_main_end_to_end_report_ledger_and_gate(tmp_path, capsys,
                                                devices8):
    """The full tool: scaling section in a schema-12 report, ledger
    entries appended, and the self-gate runs against the prior entry
    (informational on the CPU mesh — a synthetic 10x-better baseline
    must NOT fail the run, but must print the regression)."""
    rj = str(tmp_path / "scaling.json")
    hist = str(tmp_path / "hist.jsonl")
    rc = multichip.main(["--ops", "potrf", "--n", "32", "--nb", "8",
                         "--chips", "1,2", "--nruns", "1",
                         "--report", rj, "--history", hist])
    assert rc == 0
    doc = json.load(open(rj))
    assert doc["schema"] == 18
    (sec,) = doc["scaling"]
    assert [p["chips"] for p in sec["points"]] == [1, 2]
    assert doc["ops"] and doc["entries"]
    with open(hist) as f:
        entries = [json.loads(ln) for ln in f if ln.strip()]
    assert len(entries) == 1
    # v18 ledger envelope + attribution stamp
    assert entries[0]["family"] == "multichip"
    prov = entries[0]["provenance"]
    assert prov["schema"] == 1 and prov["family"] == "multichip"
    assert prov["mesh_shape"] and doc["provenance"] == prov
    # seed an impossible baseline: the second run regresses on every
    # metric — on the CPU mesh the gate is informational (exit 0)
    boosted = json.loads(json.dumps(entries[0]))
    for e in boosted["ladder"]:
        e["value"] = e["value"] * 10
    perfdiff.append_ledger(hist, boosted)
    rc2 = multichip.main(["--ops", "potrf", "--n", "32", "--nb", "8",
                          "--chips", "1,2", "--nruns", "1",
                          "--history", hist])
    out = capsys.readouterr().out
    assert rc2 == 0
    assert "REGRESSION" in out and "informational" in out


def test_cpu_mesh_runs_are_labelled_placeholder(devices8):
    """A host-platform (CPU) mesh can exercise the plumbing but not
    the hardware claim: every scaling section and ledger entry must
    carry ``"placeholder": true`` so downstream dashboards never
    mistake the numbers for accelerator measurements."""
    scaling = multichip.run_scaling(["potrf"], 32, 8, [1, 2],
                                    nruns=1, log=lambda s: None)
    (sec,) = scaling
    assert sec["placeholder"] is True
    doc = multichip.ledger_doc(scaling, 32)
    assert doc["placeholder"] is True
    assert all(row.get("placeholder") is True for row in doc["ladder"])
    # a non-placeholder section stays unlabelled end to end
    clean = json.loads(json.dumps(scaling))
    for s in clean:
        s.pop("placeholder", None)
    doc2 = multichip.ledger_doc(clean, 32)
    assert "placeholder" not in doc2
    assert all("placeholder" not in row for row in doc2["ladder"])


def test_run_scaling_devprof_attribution(devices8):
    """--devprof attributes every scaling point: the 1-chip point is
    honestly unmodelled, the multi-chip point reconciles against the
    spmdcheck schedule."""
    scaling = multichip.run_scaling(["potrf"], 32, 8, [1, 4],
                                    nruns=1, log=lambda s: None,
                                    devprof=True)
    (sec,) = scaling
    by_chips = {p["chips"]: p["devprof"] for p in sec["points"]}
    assert by_chips[1]["reconciliation"]["relation"] == \
        "no-collectives"
    e4 = by_chips[4]
    assert e4["reconciliation"]["relation"] == "=="
    assert e4["ok"] and e4["nranks"] == 4
    assert e4["label"] == "multichip_dpotrf_n32_c4"
    assert sum(e4["categories"].values()) == pytest.approx(
        e4["run_s"], rel=0.10)


def test_main_rejects_unknown_op(capsys):
    assert multichip.main(["--ops", "nosuch", "--chips", "1"]) == 2
