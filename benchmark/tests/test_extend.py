"""A later cell, traffic mix and per-layer metric are added as new
files and new entries only: the harness finds them by name."""
import json
import os
import shutil
import time

from benchmark import harness, spec


def test_added_cell_mix_and_metric_run(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(spec.ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "benchmark"
    cfg = json.loads((b / "configs" / "cholesky_f32.json").read_text())
    cfg.update(name="cholesky_f32_small", N=384, nb=128)
    (b / "configs" / "cholesky_f32_small.json").write_text(json.dumps(cfg))
    (b / "traffic" / "paced.json").write_text(json.dumps(
        {"loop": "closed", "nrhs": 2, "rhs_pool": 3}))
    (b / "metrics" / "calls_in_trace.py").write_text(
        "def read(ctx):\n"
        "    return float(sum(1 for s in ctx['trace'].spans"
        " if s[0] == 'call'))\n")
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["configs"].append({
        "name": "cholesky_f32_small", "source": "https://example.org/x",
        "file": "benchmark/configs/cholesky_f32_small.json",
        "reduced": ["N"], "why": "test"})
    doc["workloads"].append({
        "name": "cholesky_f32_small.paced", "config": "cholesky_f32_small",
        "traffic": "paced", "chips": 1, "why": "test"})
    doc["per_layer"].append({
        "name": "calls_in_trace", "unit": "1", "better": "higher",
        "source": "program_span", "layer": "Dispatch (host)",
        "moves": "gflops", "workloads": ["cholesky_f32_small.paced"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    for trace in (False, True):
        line = harness.run("cholesky_f32_small.paced", 3, 0.3, trace,
                           t_process=time.perf_counter(),
                           require_chip=False, root=str(tmp_path))
        assert line["correct"] is True
    assert line["metrics"]["calls_in_trace"]["value"] >= 1
