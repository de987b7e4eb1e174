"""Observability subsystem: metrics registry, run-report schema, XLA
capture, comm-volume model, DAG analytics, Chrome-trace pipeline, and
the driver acceptance path (--report/--profile end to end on CPU)."""
import json
import os
import subprocess
import sys

import pytest

from dplasma_tpu.descriptors import Dist, TileMatrix
from dplasma_tpu.observability import (MetricsRegistry, RunReport,
                                       capture_compiled,
                                       comm_volume_model, dag_stats,
                                       profile_to_chrome)
from dplasma_tpu.observability.report import REPORT_SCHEMA, load_report
from dplasma_tpu.utils import profiling


# ------------------------------------------------------------- metrics

def test_metrics_registry_instruments():
    reg = MetricsRegistry()
    reg.counter("runs_total", op="dpotrf").inc()
    reg.counter("runs_total", op="dpotrf").inc(2)
    reg.counter("runs_total", op="dgemm").inc()
    reg.gauge("gflops", op="dpotrf").set(812.5)
    h = reg.histogram("run_seconds", op="dpotrf")
    for t in (0.1, 0.3, 0.2):
        h.observe(t)
    snap = reg.snapshot()
    by = {(e["name"], e["labels"].get("op")): e for e in snap}
    assert by[("runs_total", "dpotrf")]["value"] == 3
    assert by[("runs_total", "dgemm")]["value"] == 1
    assert by[("gflops", "dpotrf")]["value"] == 812.5
    hs = by[("run_seconds", "dpotrf")]
    assert hs["count"] == 3 and hs["min"] == 0.1 and hs["max"] == 0.3
    assert hs["median"] == 0.2
    assert json.loads(json.dumps(snap)) == snap   # JSON-able


def test_metrics_registry_guards():
    reg = MetricsRegistry()
    reg.counter("x").inc()
    with pytest.raises(TypeError):
        reg.gauge("x")                       # family type conflict
    with pytest.raises(ValueError):
        reg.counter("y").inc(-1)             # counters only go up
    assert reg.get("nope") is None


# ---------------------------------------------------------- run-report

def test_run_report_schema_and_stats(tmp_path):
    rep = RunReport("testing_dpotrf")
    rep.metrics.gauge("gflops_best", op="testing_dpotrf").set(7.0)
    entry = rep.add_op("testing_dpotrf", prec="d", flops=1e9,
                       enq_s=1.5, warmup_s=0.2, dest_s=0.0,
                       runs_s=[0.4, 0.2, 0.3], gflops=5.0)
    t = entry["timings"]
    assert t["best_s"] == 0.2 and t["min_s"] == 0.2
    assert t["median_s"] == 0.3 and t["max_s"] == 0.4
    assert t["stddev_s"] == pytest.approx(0.0816496580927726)
    p = str(tmp_path / "r.json")
    rep.write(p)
    doc = load_report(p)
    assert doc["schema"] == REPORT_SCHEMA == 18
    assert doc["ops"][0]["timings"]["runs_s"] == [0.4, 0.2, 0.3]
    assert doc["metrics"][0]["value"] == 7.0
    assert doc["env"]["backend"] == "cpu"


def test_run_report_rejects_newer_schema(tmp_path):
    p = str(tmp_path / "future.json")
    with open(p, "w") as f:
        json.dump({"schema": REPORT_SCHEMA + 1}, f)
    with pytest.raises(ValueError):
        load_report(p)


def test_run_report_no_runs_entry_roundtrip(tmp_path):
    """A dry run (nruns=0, no timed executions, no warmup) must
    serialize cleanly: explicit nulls for every statistic, and the
    doc round-trips through write/load_report byte-honestly."""
    rep = RunReport("testing_dpotrf")
    entry = rep.add_op("testing_dpotrf", prec="d", runs_s=[])
    t = entry["timings"]
    assert t["nruns"] == 0 and t["runs_s"] == []
    assert t["warmup_s"] is None
    for k in ("best_s", "min_s", "median_s", "max_s", "mean_s",
              "stddev_s"):
        assert t[k] is None
    p = str(tmp_path / "dry.json")
    rep.write(p)
    doc = load_report(p)
    back = doc["ops"][0]["timings"]
    assert back["nruns"] == 0 and back["median_s"] is None
    assert json.loads(json.dumps(doc)) == doc
    # a no-runs doc is inert for the regression gate, not a crash
    from tools import perfdiff
    assert perfdiff.extract_metrics(doc) == {}


def test_load_report_tolerates_v1_to_current(tmp_path):
    """The schema history is additive: every older vintage loads, and
    the always-present keys are filled so consumers iterate them
    unconditionally. Only newer-than-reader rejects."""
    vintages = {
        1: {"schema": 1, "name": "v1",
            "ops": [{"label": "op", "timings": {"median_s": 0.5}}]},
        2: {"schema": 2, "name": "v2", "ops": [], "metrics": [],
            "checks": [], "resilience": []},
        3: {"schema": 3, "name": "v3", "ops": [], "metrics": [],
            "dagcheck": []},
        4: {"schema": 4, "name": "v4", "ops": [], "metrics": [],
            "pipeline": {"sweep.lookahead": 1, "qr.agg_depth": 4}},
        5: {"schema": 5, "name": "v5", "ops": [], "metrics": [],
            "roofline": []},
        6: {"schema": 6, "name": "v6", "ops": [], "metrics": [],
            "spmdcheck": []},
        7: {"schema": 7, "name": "v7", "ops": [], "metrics": [],
            "refine": [{"op": "testing_dposv_ir", "precision": "f32",
                        "iterations": 2, "backward_errors": [1e-8],
                        "converged": True, "escalated": False,
                        "tol": 2.2e-14}]},
        8: {"schema": 8, "name": "v8", "ops": [], "metrics": [],
            "serving": [{"requests": 64, "batches": 6,
                         "mean_batch": 10.7,
                         "latency_s": {"p50": 0.004, "p99": 0.009,
                                       "max": 0.01},
                         "cache": {"entries": 6, "capacity": 32,
                                   "hits": 12, "misses": 6,
                                   "evictions": 0, "invalidations": 0,
                                   "hit_rate": 0.667,
                                   "compile_s": 1.5},
                         "remediated": 0, "failed": 0, "retries": 0,
                         "escalations": 0}]},
        9: {"schema": 9, "name": "v9", "ops": [], "metrics": [],
            "pipeline": {"sweep.lookahead": 1, "qr.agg_depth": 4,
                         "panel.kernel": "auto", "panel.qr": "tree",
                         "panel.lu": "rec"}},
        10: {"schema": 10, "name": "v10", "ops": [], "metrics": [],
             "hlocheck": [{"op": "testing_dpotrf", "ok": True,
                           "kernel": "testing_dpotrf",
                           "counts": {"all-reduce": 8,
                                      "all-gather": 4},
                           "expected": {"all-reduce": 8,
                                        "all-gather": 4},
                           "relation": "==", "donated": 0,
                           "aliased": 0, "hbm_peak_bytes": 2704,
                           "hbm_budget": 0, "copy_bytes": 3584,
                           "total_bytes": 68940,
                           "diagnostics": []}]},
        11: {"schema": 11, "name": "v11", "ops": [], "metrics": [],
             "pipeline": {"sweep.lookahead": 1, "qr.agg_depth": 4,
                          "lu.agg_depth": 4, "panel.kernel": "auto",
                          "panel.qr": "tree", "panel.lu": "rec",
                          "panel.tree_leaf": 2, "panel.rec_base": 8,
                          "tuning.source": "db"},
             "tuning": [{"op": "potrf",
                         "key": "potrf|n=8192|float32|g1x1",
                         "source": "db", "db": "tune_db.json",
                         "knobs": {"nb": 512, "sweep.lookahead": 2},
                         "applied": {"sweep.lookahead": 2},
                         "nb": 512, "measured_s": 0.84,
                         "entry_key": "potrf|n=8192|float32|g1x1"}]},
        12: {"schema": 12, "name": "v12", "ops": [], "metrics": [],
             "pipeline": {"sweep.lookahead": 1, "qr.agg_depth": 4,
                          "lu.agg_depth": 4, "panel.kernel": "auto",
                          "panel.qr": "tree", "panel.lu": "rec",
                          "panel.tree_leaf": 2, "panel.rec_base": 8,
                          "ring.enable": "auto"},
             "scaling": [{"op": "potrf", "prec": "d", "n": 256,
                          "nb": 32, "ring": "auto",
                          "points": [
                              {"chips": 1, "grid": [1, 1],
                               "median_s": 0.42, "gflops": 13.3,
                               "parallel_efficiency": 1.0},
                              {"chips": 8, "grid": [2, 4],
                               "median_s": 0.09, "gflops": 62.1,
                               "parallel_efficiency": 0.58}]}]},
        13: {"schema": 13, "name": "v13", "ops": [], "metrics": [],
             "telemetry": {
                 "spans": {"enabled": True, "opened": 42,
                           "closed": 42, "recorded": 42,
                           "dropped": 0, "balanced": True},
                 "exporter": {"path": "telemetry.prom",
                              "interval_s": 10.0, "flushes": 3},
                 "flight_recorder": {
                     "capacity": 256, "recorded": 5, "dropped": 0,
                     "events": [
                         {"seq": 0, "t_ns": 1, "kind": "submit",
                          "request": 1, "op": "posv", "n": 12,
                          "nrhs": 1},
                         {"seq": 1, "t_ns": 2, "kind": "dispatch",
                          "op": "posv", "batch": 1, "requests": [1],
                          "bucket": [12, 4, 1], "cache": "miss"},
                         {"seq": 2, "t_ns": 3, "kind": "gate_fail",
                          "request": 1, "op": "posv",
                          "verdict": {"ok": False}},
                         {"seq": 3, "t_ns": 4, "kind": "ladder",
                          "request": 1, "op": "posv",
                          "action": "retry", "label": "posv",
                          "ok": True},
                         {"seq": 4, "t_ns": 5, "kind": "remediation",
                          "request": 1, "op": "posv",
                          "outcome": "remediated",
                          "winner": "posv", "attempts": 2}]}}},
        14: {"schema": 14, "name": "v14", "ops": [], "metrics": [],
             "devprof": [{
                 "label": "testing_dpotrf", "op": "potrf",
                 "backend": "synthetic", "nranks": 4,
                 "run_s": 0.01,
                 "categories": {"compute": 0.0085,
                                "collective": 0.0012,
                                "ici": 0.0003, "host": 0.0},
                 "coverage": 1.0, "timeline_ops": 52,
                 "collectives": [
                     {"cls": "psum@q", "hlo": "all-reduce",
                      "count": 4, "measured_s": 0.0009,
                      "model_bytes": 32768.0,
                      "achieved_bytes_per_s": 9.1e6,
                      "achieved_frac": 0.91}],
                 "reconciliation": {"relation": "==",
                                    "expected": {"psum@q": 4},
                                    "ingested": {"psum@q": 4}},
                 "skew": {"value": 0.02, "slowest_rank": 2,
                          "dominating_category": "collective",
                          "per_rank_s": [0.0098, 0.0099, 0.01,
                                         0.0097],
                          "ranks": [0, 1, 2, 3],
                          "max_step_spread_s": 0.0002},
                 "critical_path": [{"name": "fusion.0", "rank": 2,
                                    "seconds": 0.004}],
                 "diagnostics": [], "ok": True}]},
        15: {"schema": 15, "name": "v15", "ops": [], "metrics": [],
             "admission": {
                 "enabled": True, "max_queue": 256, "max_inflight": 0,
                 "slo_p99_ms": 0.0, "ewma_p99_ms": 0.0,
                 "admitted": 63, "shed": 1, "degraded": 0,
                 "deadline_expired": 0, "breaker_opens": 1,
                 "breakers": {"posv:retry": {
                     "state": "open", "failures": 3, "opens": 1,
                     "probes": 0}},
                 "retry_budget": {"limit": 0, "used": 2},
                 "audit": {"submitted": 64, "admitted": 63,
                           "shed": 1, "resolved": 63, "lost": 0,
                           "flight_shed_seen": 1, "flight_dropped": 0,
                           "balanced": True}}},
        16: {"schema": 16, "name": "v16", "ops": [], "metrics": [],
             "memcheck": [{
                 "op": "testing_dpotrf", "ok": True,
                 "kernel": "potrf", "tasks": 14, "tiles": 6,
                 "steps": 14, "itemsize": 8.0, "tile_bytes": 128.0,
                 "peak_by_rank": {"0": 768},
                 "peak_bytes": 768,
                 "predicted_hbm_peak_bytes": 6144,
                 "staging_factor": 8.0,
                 "peak_rank": 0, "peak_step": 3,
                 "peak_task": "trsm(2,0)",
                 "live_at_peak": 6,
                 "peak_live_preview": ["A[0,0]", "A[1,0]", "A[2,0]"],
                 "input_bytes": 768, "output_bytes": 768,
                 "reuse_writes": 8, "donated_bytes": 1024,
                 "budget": 0,
                 "stream": {"kernel": "potrf", "budget": 512,
                            "window": 1, "steps": 14, "ops": 18,
                            "fetches": 8, "peak_bytes": 512,
                            "streamed_bytes": 2048, "refetches": 2,
                            "feasible": True},
                 "skipped": False,
                 "counts": {}, "diagnostics": []}]},
        17: {"schema": 17, "name": "v17", "ops": [], "metrics": [],
             "autopilot": [{
                 "op": "posv_ir", "n": 4096, "dtype": "float32",
                 "cond_estimate": 312.4, "cond_class": "well",
                 "precision": "int8", "source": "db",
                 "key": "posv_ir|n=4096|float32|g1x1|cond=well",
                 "db": "tune_db.json"}]},
        18: {"schema": 18, "name": "v18", "ops": [], "metrics": [],
             "provenance": {
                 "schema": 1, "family": "bench",
                 "git": {"sha": "0123abcd" * 5, "dirty": False},
                 "jax": "0.4.35", "jaxlib": "0.4.35",
                 "backend": "tpu", "device_count": 8,
                 "mesh_shape": [2, 4], "peaks_source": "bench",
                 "mca": {"sweep.lookahead": "2"}}},
    }
    assert set(vintages) == set(range(1, REPORT_SCHEMA + 1))
    for v, doc in vintages.items():
        p = str(tmp_path / f"v{v}.json")
        with open(p, "w") as f:
            json.dump(doc, f)
        back = load_report(p)
        assert back["schema"] == v
        assert isinstance(back["ops"], list)
        assert isinstance(back["metrics"], list)
    # a schema-less pre-versioning doc reads as v1
    p = str(tmp_path / "v0.json")
    with open(p, "w") as f:
        json.dump({"name": "ancient"}, f)
    back = load_report(p)
    assert back["schema"] == 1 and back["ops"] == []
    # non-object docs are rejected, not mangled
    p = str(tmp_path / "list.json")
    with open(p, "w") as f:
        json.dump([1, 2], f)
    with pytest.raises(ValueError):
        load_report(p)


def test_metrics_snapshot_insertion_order_independent():
    """Two runs recording the same figures in different orders must
    produce byte-identical metric sections (perfdiff/report diffing
    depends on it)."""
    specs = [("runs_total", "counter", {"op": "a", "prec": "d"}, 1),
             ("runs_total", "counter", {"prec": "s", "op": "b"}, 2),
             ("gflops_best", "gauge", {"op": "a"}, 3.5),
             ("run_seconds", "histogram", {"op": "a"}, 0.25)]

    def build(order):
        reg = MetricsRegistry()
        for name, kind, labels, val in order:
            if kind == "counter":
                reg.counter(name, **labels).inc(val)
            elif kind == "gauge":
                reg.gauge(name, **labels).set(val)
            else:
                reg.histogram(name, **labels).observe(val)
        return reg.snapshot()

    fwd, rev = build(specs), build(specs[::-1])
    assert json.dumps(fwd) == json.dumps(rev)
    # label kwarg order is immaterial too (sorted label pairs)
    reg = MetricsRegistry()
    reg.counter("runs_total", prec="d", op="a").inc()
    snap = reg.snapshot()
    assert snap[0]["labels"] == {"op": "a", "prec": "d"}
    assert json.dumps(snap[0]["labels"]) == \
        json.dumps(dict(sorted({"prec": "d", "op": "a"}.items())))


# --------------------------------------------------------- XLA capture

def test_capture_compiled_fields():
    import jax
    import jax.numpy as jnp
    c = jax.jit(lambda a: a @ a).lower(jnp.ones((32, 32))).compile()
    info = capture_compiled(c)
    # CPU backend answers both analyses; fields are floats/ints
    assert info["flops"] and info["flops"] > 2 * 32 ** 3 / 2
    assert info["bytes_accessed"] > 0
    assert info["memory"]["argument_size_in_bytes"] == 32 * 32 * 8
    assert info["peak_bytes"] > 0
    assert json.loads(json.dumps(info)) == info


@pytest.mark.parametrize("platform,peak", [("tpu", 7), ("cpu", 1 + 2 + 4)])
def test_capture_compiled_peak_by_platform(platform, peak):
    """Off the CPU the peak is XLA's own figure; the CPU's leaves the
    temps out, so there it is args + outputs + temps."""
    class _Dev:
        pass

    class _Sharding:
        device_set = {_Dev()}

    class _Mem:
        argument_size_in_bytes, output_size_in_bytes = 1, 2
        temp_size_in_bytes, peak_memory_in_bytes = 4, 7

    class _Compiled:
        input_shardings = ((_Sharding(),), {})
        output_shardings = _Sharding()

        def cost_analysis(self):
            return None

        def memory_analysis(self):
            return _Mem()
    next(iter(_Sharding.device_set)).platform = platform
    assert capture_compiled(_Compiled())["peak_bytes"] == peak


def test_capture_compiled_never_raises():
    class Broken:
        def cost_analysis(self):
            raise RuntimeError("no analysis on this backend")

        def memory_analysis(self):
            return None
    info = capture_compiled(Broken())
    # a RAISING analysis records the structured reason (a declining
    # backend that returns None stays an explicit null — see
    # tests/test_hlocheck.py for the full round-trip)
    assert info["flops"] is None
    assert info["cost"] == {"error": repr(RuntimeError(
        "no analysis on this backend"))}
    assert info["memory"] is None and info["peak_bytes"] is None


# ----------------------------------------------------------- comm model

def test_comm_volume_model_grid():
    d = Dist(P=2, Q=2)
    cv = comm_volume_model("potrf", 512, 512, 1, 64, 64, 8, d)
    assert cv["op_class"] == "potrf"
    dm, sm = cv["dag_model"], cv["spmd_model"]
    assert dm["messages"] > 0
    assert dm["bytes_total"] == dm["messages"] * cv["tile_bytes"]
    assert set(dm["messages_by_flow"]) == {"Lkk", "panel"}
    assert sm["bytes_total"] > 0 and sm["steps"] == 8
    # single device: everything is rank-local
    cv1 = comm_volume_model("potrf", 512, 512, 1, 64, 64, 8, Dist())
    assert cv1["dag_model"]["bytes_total"] == 0.0
    assert cv1["spmd_model"]["bytes_total"] == 0.0


def test_comm_volume_model_classes_and_unknown():
    d = Dist(P=2, Q=4)
    for op in ("getrf_1d", "geqrf", "gemm", "heev"):
        cv = comm_volume_model(op, 256, 256, 256, 32, 32, 4, d)
        assert cv["op_class"] is not None
        assert cv["spmd_model"] is None or \
            cv["spmd_model"]["bytes_total"] > 0
        if cv["dag_model"] is not None:
            assert cv["dag_model"]["messages"] > 0
    cv = comm_volume_model("print", 64, 64, 1, 32, 32, 4, d)
    assert cv["op_class"] is None and cv["dag_model"] is None


def test_comm_model_supertile_owner_counting():
    # kp=2 halves the distinct row owners a short column span sees
    from dplasma_tpu.observability.comm import _owners
    assert _owners(0, 0, 4, 1, 0) == {0}
    assert _owners(0, 3, 4, 1, 0) == {0, 1, 2, 3}
    assert _owners(0, 3, 4, 2, 0) == {0, 1}
    assert _owners(2, 5, 4, 2, 1) == {2, 3}      # offset shifts owners
    assert _owners(3, 1, 4, 1, 0) == set()       # empty range


# ---------------------------------------------------------- DAG stats

def test_dag_stats_potrf():
    from dplasma_tpu.ops import potrf as potrf_mod
    A = TileMatrix.zeros(16, 16, 4, 4, dist=Dist(P=2, Q=2))
    rec = profiling.DagRecorder(enabled=True)
    potrf_mod.dag(A, "L", rec, lookahead=0)   # classic structure
    st = dag_stats(rec)
    NT = 4
    assert st["tasks"] == len(rec.tasks)
    assert st["task_counts"]["potrf"] == NT
    # right-looking Cholesky critical path: potrf/trsm/herk per panel
    assert st["critical_path"] == 3 * (NT - 1) + 1
    assert st["max_width"] >= NT - 1
    assert st["parallelism_ceiling"] == pytest.approx(
        st["tasks"] / st["critical_path"])
    assert sum(st["wavefronts"]) == st["tasks"]
    from dplasma_tpu.observability.dag import format_dag_stats
    txt = format_dag_stats(st, "potrf")
    assert "critical path" in txt and "wavefront" in txt


def test_dag_stats_empty_and_cycle():
    rec = profiling.DagRecorder(enabled=True)
    assert dag_stats(rec)["tasks"] == 0
    rec.task("a", 0)
    rec.task("b", 0)
    rec.edge(0, 1)
    rec.edge(1, 0)
    with pytest.raises(ValueError):
        dag_stats(rec)


def test_recorder_clear_and_recording_scope():
    rec = profiling.DagRecorder(enabled=True)
    rec.task("t", 0)
    rec.edge(0, 0)
    rec.clear()
    assert not rec.tasks and not rec.edges
    assert rec.task("t", 1) == 0        # name table cleared too
    g = profiling.recorder
    g.clear()
    assert not g.enabled
    with profiling.recording() as r:
        assert r is g and r.enabled
        r.task("x", 0)
    assert not g.enabled and len(g.tasks) == 1
    with profiling.recording() as r:    # scoped: cleared on entry
        assert not r.tasks
    g.clear()


# --------------------------------------------------------- printlog fix

def test_printlog_reads_env_at_call_time(monkeypatch, capsys):
    monkeypatch.delenv("DPLASMA_TRACE_KERNELS", raising=False)
    profiling.printlog("hidden %d", 1)
    assert capsys.readouterr().out == ""
    # set AFTER import: must take effect (was frozen at import before)
    monkeypatch.setenv("DPLASMA_TRACE_KERNELS", "1")
    profiling.printlog("shown %d", 2)
    assert "shown 2" in capsys.readouterr().out
    monkeypatch.setenv("DPLASMA_TRACE_KERNELS", "0")
    profiling.printlog("hidden again")
    assert capsys.readouterr().out == ""
    profiling.set_trace_kernels(True)   # programmatic override wins
    try:
        profiling.printlog("forced")
        assert "forced" in capsys.readouterr().out
    finally:
        profiling.set_trace_kernels(None)


# ------------------------------------------------------- Chrome traces

def test_profile_to_chrome_document():
    events = [("enq:op", 1000, 3000, 0.0, 0),
              ("run[0]:op", 3000, 9000, 1e9, 1)]
    doc = profile_to_chrome(events, {"rank": "2", "SCHED": "wavefront"})
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [e["tid"] for e in spans] == [0, 1]
    assert all(e["pid"] == 2 for e in spans)
    assert spans[0]["ts"] == 0.0 and spans[0]["dur"] == 2.0   # µs
    assert spans[1]["args"]["flops"] == 1e9
    assert doc["otherData"]["SCHED"] == "wavefront"
    names = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in names)
    assert json.loads(json.dumps(doc)) == doc


def test_tracecat_cli_roundtrip(tmp_path):
    prof = profiling.Profile(rank=1)
    with prof.span("enq:x"):
        pass
    with prof.span("run[0]:x", flops=5e6, track=1):
        pass
    src = str(tmp_path / "x.prof")
    out = str(tmp_path / "x.trace.json")
    prof.write(src)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "tracecat.py"),
         src, "-o", out],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr
    doc = json.load(open(out))
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in spans} == {"enq:x", "run[0]:x"}
    assert {e["tid"] for e in spans} == {0, 1}


# --------------------------------------- driver end-to-end (acceptance)

def test_driver_report_and_profile_end_to_end(tmp_path, capsys):
    """The ISSUE acceptance path: testing_dpotrf -N 512 --report
    --profile produces (a) a run-report with timings, GFlop/s, XLA
    cost/memory (or explicit nulls), comm model and DAG stats, and
    (b) a DTPUPROF1 trace that tracecat converts to Chrome trace-event
    JSON that json.loads cleanly — all on CPU."""
    from dplasma_tpu.drivers import main
    rj = str(tmp_path / "r.json")
    rp = str(tmp_path / "r.prof")
    rc = main(["-N", "512", f"--report={rj}", f"--profile={rp}",
               "--nruns", "2"], prog="testing_dpotrf")
    capsys.readouterr()
    assert rc == 0
    doc = load_report(rj)
    assert doc["schema"] == 18
    assert doc["iparam"]["N"] == 512 and doc["iparam"]["prec"] == "d"
    (op,) = doc["ops"]
    t = op["timings"]
    assert t["enq_s"] > 0 and t["warmup_s"] > 0
    assert len(t["runs_s"]) == 2 and t["best_s"] == min(t["runs_s"])
    for k in ("min_s", "median_s", "max_s", "mean_s", "stddev_s"):
        assert t[k] is not None
    assert op["gflops"] > 0 and op["model_flops"] > 0
    # XLA analysis present or explicit nulls — never missing keys
    assert "flops" in op["xla"] and "memory" in op["xla"]
    assert op["comm"]["op_class"] == "potrf"
    assert op["comm"]["dag_model"]["bytes_total"] == 0.0  # 1x1 grid
    assert op["dag"]["tasks"] > 0 and op["dag"]["critical_path"] > 0
    assert doc["metrics"]
    # (b) binary trace -> chrome trace-event JSON
    events, info = __import__(
        "dplasma_tpu.native", fromlist=["native"]).read_trace(rp)
    assert any(e[0].startswith("enq:") for e in events)
    from tools.tracecat import convert
    chrome = convert(rp)
    text = json.dumps(chrome)
    back = json.loads(text)
    spans = [e for e in back["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == len(events)
    assert float(info["GFLOPS:testing_dpotrf"]) == \
        pytest.approx(op["gflops"])


def test_driver_dag_stats_at_v3(capsys):
    from dplasma_tpu.drivers import main
    rc = main(["-N", "64", "-t", "16", "-v=3"], prog="testing_dpotrf")
    out = capsys.readouterr().out
    assert rc == 0
    assert "#+ DAG[testing_dpotrf]:" in out
    assert "parallelism ceiling" in out and "wavefront widths" in out


def test_qr_dag_cross_panel_dependence():
    """tsmqr(m,n,k) -> tsmqr(m,n,k+1): successive panels' updates of
    the same trailing tile must be ordered (write-after-write on
    A(m,n)); the linearization must respect it."""
    from dplasma_tpu.ops import qr
    A = TileMatrix.zeros(24, 24, 8, 8, dist=Dist(P=2, Q=2))
    rec = profiling.DagRecorder(enabled=True)
    qr.dag(A, rec, lookahead=0, agg_depth=1)  # classic structure
    by = {(t.cls, t.index): t.tid for t in rec.tasks}
    edges = {(s, d) for s, d, _ in rec.edges}
    assert (by[("tsmqr", (2, 2, 0))], by[("tsmqr", (2, 2, 1))]) in edges
    order = rec.order()              # acyclic and schedulable
    pos = {int(v): i for i, v in enumerate(order)}
    for s, d, _ in rec.edges:
        assert pos[s] < pos[d]


def test_comm_model_dag_walk_cap():
    """Absurd K (gemm) skips the Python dependence walk — explicit
    null, not a multi-minute stall; the closed-form fields remain."""
    cv = comm_volume_model("gemm", 1024, 1024, 1 << 22, 64, 64, 4,
                           Dist(P=2, Q=2))
    assert cv["op_class"] == "gemm" and cv["dag_model"] is None
