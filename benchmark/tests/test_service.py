"""A request-serving cell on the CPU, at n 16-64 and nb 8: added from
files in a copy of the benchmark (``data/service_cell.json``: its
entries of ``BENCHMARK.json``, its configuration and its traffic), as a
later cell would be, and run through the harness. A sound run is correct and compiles nothing in its
window; a stall in the service's dispatch shows in the p95 from due
time; a program left unwarmed, the control and answers broken where
they are produced each make the run not correct."""
import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark import control, harness, hlo, reduce, serve, spec

CELL = "solver_service.open_mixed"
SEED = 2 ** 31 + 11


SERVICE_CELL = os.path.join(os.path.dirname(__file__), "data",
                            "service_cell.json")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with the service cell added."""
    tmp = tmp_path_factory.mktemp("bench")
    shutil.copytree(os.path.join(spec.ROOT, "benchmark"), tmp / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(SERVICE_CELL) as f:
        cell = json.load(f)
    doc = spec.load_spec()
    for section, entries in cell["benchmark"].items():
        doc[section] += entries
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    b = tmp / "benchmark"
    (b / "configs" / "solver_service.json").write_text(
        json.dumps(cell["config"]))
    (b / "traffic" / "open_mixed.json").write_text(
        json.dumps(cell["traffic"]))
    return str(tmp)


def _run(root, trace=False, seed=SEED, **kw):
    line = harness.run(CELL, seed, 0.6, trace,
                       t_process=time.perf_counter(), require_chip=False,
                       root=root, **kw)
    return json.loads(json.dumps(line))


def test_sound_run_is_correct(root):
    line = _run(root)
    assert line["correct"] is True and line["failed"] == 0
    assert line["window_compiles"] == 0 and line["attempted"] == 24
    assert list(line)[-1] == "compare"
    c = spec.cell(spec.load_spec(root), CELL, root)
    assert set(line["metrics"]) == {m["name"] for m in c.end_to_end}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    for name in ("backward_error.posv", "backward_error.gesv"):
        cmp = line["compare"][name]
        assert 0 < cmp["value"] <= cmp["limit"]


def test_traced_run_reads_the_serving_layer(root):
    line = _run(root, trace=True)
    assert line["correct"] is True
    # the CPU has no device trace: only the server's own readings
    assert set(line["metrics"]) == {"queue_wait_pct.open",
                                    "pad_flops_pct.open"}
    assert 0 < line["metrics"]["queue_wait_pct.open"]["value"] < 100
    assert 0 < line["metrics"]["pad_flops_pct.open"]["value"] < 100


def test_stall_in_dispatch_raises_p95(root, monkeypatch):
    from dplasma_tpu.serving import service
    stall = 0.25
    base = _run(root)["metrics"]["solve_ms_p95.open"]["value"]
    run = service.SolverService._run

    def stalled(self, key, reqs):
        time.sleep(stall)
        return run(self, key, reqs)
    monkeypatch.setattr(service.SolverService, "_run", stalled)
    line = _run(root)
    assert line["correct"] is True
    assert line["metrics"]["solve_ms_p95.open"]["value"] >= \
        base + stall * 1e3


def test_unwarmed_program_is_not_correct(root, monkeypatch):
    """The shapes of the largest n bucket (33-64) left out of the
    warm-up: their requests compile in the window."""
    every = serve.warm_shapes
    monkeypatch.setattr(serve, "warm_shapes", lambda operands: [
        s for s in every(operands) if s[1] <= 32])
    line = _run(root)
    assert line["window_compiles"] > 0 and line["correct"] is False


def test_control_is_not_correct(root):
    c = spec.cell(spec.load_spec(root), CELL, root)
    line = harness.run(CELL, SEED, 0.6, False,
                       t_process=time.perf_counter(), require_chip=False,
                       root=root, wrap=control.wrap_for(c.config))
    over = [v for v in line["compare"].values() if v["value"] > v["limit"]]
    assert line["correct"] is False and over, line["compare"]


def _altered(fn):
    """Each problem's largest answer entry off by 5e-5 of itself: within
    the service's own gate (60 eps n of max|A| max|x|), far outside the
    benchmark's limits, so the judge and not the gate has to see it."""
    def f(op, a, b):
        import jax.numpy as jnp
        x = fn(op, a, b)
        top = jnp.abs(x) == jnp.max(jnp.abs(x), axis=(1, 2), keepdims=True)
        return jnp.where(top, x * (1 + 5e-5), x)
    return f


def _unchanged(fn):
    return lambda op, a, b: b


def _half(fn):
    def f(op, a, b):
        x = fn(op, a, b)
        return x.at[::2].set(0)     # every other problem left out
    return f


@pytest.mark.parametrize("fault", [_altered, _unchanged, _half])
def test_fault_is_not_correct(root, fault):
    line = _run(root, wrap=fault)
    assert line["correct"] is False


def test_altered_answer_fails_the_judge(root):
    line = _run(root, wrap=_altered)
    assert line["window_compiles"] == 0 and line["failed"] >= 1


def test_clock_shift_ignores_submits_that_launch_nothing():
    """Submits (``call``) close before modules that the server's own
    dispatches (``launch``) started: no shift from the submits."""
    raw = {"spans": [("call", 100.0, 101.0), ("call", 1000.0, 1001.0),
                     ("launch", 120.0, 130.0), ("launch", 990.0, 999.0)],
           "modules": {"/device:TPU:0": [(125.0, 400.0), (995.0, 1300.0)]},
           "devices": {}}
    assert reduce.clock_shift(raw, launch="launch") == 0.0
    # read as launches, the same submits would have moved the clock
    assert reduce.clock_shift(raw) == 5.0


def test_programs_are_named_by_their_run():
    """Two programs that differ only in their NRHS bucket run ops of the
    same names and types: only the module's name tells them apart. A
    module name that two programs share, or that no program has, names
    no program."""
    raw = {"spans": [],
           "modules": {"/device:TPU:0": [
               (0.0, 10.0, "jit_gesv_n64_r4_b1(11)"),
               (20.0, 30.0, "jit_gesv_n64_r8_b1(12)"),
               (40.0, 50.0, "jit_fn(13)"),
               (60.0, 70.0, "jit_other(14)")]},
           "devices": {"/device:TPU:0": [
               ("fusion.1", 1.0, 2.0), ("fusion.1", 21.0, 22.0),
               ("fusion.2", 23.0, 24.0), ("fusion.1", 41.0, 42.0),
               ("fusion.1", 61.0, 62.0), ("copy.3", 80.0, 81.0)]}}
    modules = {"gesv_n64_r4_b1": "jit_gesv_n64_r4_b1",
               "gesv_n64_r8_b1": "jit_gesv_n64_r8_b1",
               "a": "jit_fn", "b": "jit_fn"}
    out = reduce.name_by_program(raw, modules)
    assert [e[0] for e in out["devices"]["/device:TPU:0"]] == [
        "gesv_n64_r4_b1/fusion.1", "gesv_n64_r8_b1/fusion.1",
        "gesv_n64_r8_b1/fusion.2", "fusion.1", "fusion.1", "copy.3"]
    assert out["modules"]["/device:TPU:0"][0] == (0.0, 10.0)


def test_each_program_has_a_module_name_of_its_own(root):
    import jax
    c = spec.cell(spec.load_spec(root), CELL, root)
    server = spec.entry(c).build(c.config, jax.devices()[:1]).start(None)
    try:
        programs = server.warm([("posv", 20, 1), ("posv", 20, 5),
                                ("gesv", 20, 1)])
    finally:
        server.close()
    assert sorted(hlo.module_name(p.as_text()) for p in programs.values()) \
        == sorted(f"jit_{name}" for name in programs)
    assert len(programs) == 6      # 3 keys x batch buckets 1, 2


def test_pool_shapes_are_the_same_for_every_seed(root):
    from benchmark import pool
    small = spec.cell(spec.load_spec(root), CELL, root).traffic
    shapes = pool.shapes(dict(small, pool=128, block=16,
                              n_range=[256, 4096]))
    assert len(shapes) == 128
    assert sum(op == "posv" for op, _, _ in shapes) == 64
    ns = [n for _, n, _ in shapes]
    assert min(ns) >= 256 and max(ns) <= 4096
    assert sorted({r for _, _, r in shapes}) == list(range(1, 9))
    a = pool.make(1, small, "float32")
    b = pool.make(2 ** 31 + 3, small, "float32")
    assert sorted((o.op, o.n, o.nrhs) for o in a) == \
        sorted((o.op, o.n, o.nrhs) for o in b)
    assert not np.array_equal(a[0].a[:4, :4], b[0].a[:4, :4])
    spd = next(o for o in a if o.op == "posv")
    assert np.array_equal(spd.a, spd.a.T)
    assert np.all(np.linalg.eigvalsh(spd.a.astype(np.float64)) > 0)
