"""The reduction from a trace recorded on the chip to the per-layer
metrics and the breakdown gives fixed values.

The data: one call of ``posv`` at N = 16384, nb = 1024 (f32) on a TPU v5
lite, traced by the harness (``harness.run(..., trace=True,
trace_dir=...)``, ``*.xplane.pb.gz``), and the text of the compiled
program (``compiled.as_text()``, ``*.hlo.txt.gz``)."""
import gzip
import os

import pytest

from benchmark import hlo, reduce, spec

DATA = os.path.join(os.path.dirname(__file__), "data")
STEM = os.path.join(DATA, "cholesky_f32_16384")

EXPECTED = {
    "host_call_pct.short_calls": 0.4095783627934255,
    "device_idle_pct.short_calls": 1.326226600718039,
    "matmul_roofline_pct.short_calls": 15.261601321879265,
    "panel_pct.short_calls": 14.852596258266557,
}


@pytest.fixture(scope="module")
def trace():
    with gzip.open(STEM + ".hlo.txt.gz", "rt") as f:
        idx = hlo.index([f.read()])
    return reduce.Trace(reduce.load(STEM + ".xplane.pb.gz"), idx)


def test_window_and_busy(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    assert trace.shift_ns == 1134770.0
    assert trace.window_s == pytest.approx(0.08244820300000001, rel=1e-12)
    assert trace.busy_s() == pytest.approx(0.081354753, rel=1e-12)


def test_per_layer_metrics(trace):
    c = spec.cell(spec.load_spec(), "cholesky_f32.closed")
    ctx = {"trace": trace, "device_kind": "TPU v5 lite", "cell": c,
           "config": c.config}
    got = {m["name"]: spec.reader(c, m["name"]).read(ctx)
           for m in c.per_layer}
    assert got == pytest.approx(EXPECTED, rel=1e-12)
    # one chip runs no collective: that reader finds nothing to read
    m = spec.reader(c, "collective_exposed_pct").read(ctx)
    assert m is None


def test_breakdown(trace):
    b = trace.breakdown()
    assert [n for n, _ in b["device_ops"][:3]] == [
        "matmul:convolution_subtract_fusion.8",
        "matmul:convolution_subtract_fusion.6",
        "matmul:convolution_subtract_fusion.4"]
    assert b["device_ops"][0][1] == pytest.approx(0.003773381, rel=1e-12)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["idle_gaps"][0] == ["wait@0", pytest.approx(0.0010433010000000002,
                                                         rel=1e-12)]


def test_unknown_device_kind_is_an_error(trace):
    c = spec.cell(spec.load_spec(), "cholesky_f32.closed")
    ctx = {"trace": trace, "device_kind": "TPU v9 imaginary", "cell": c,
           "config": c.config}
    with pytest.raises(KeyError):
        spec.reader(c, "matmul_roofline_pct").read(ctx)


def test_interval_arithmetic():
    u = reduce.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)]
    assert reduce.length(u) == 7
    assert reduce.subtract([(0, 10)], u) == [(3, 5), (9, 10)]
    assert reduce.subtract([(0, 4), (6, 8)], [(1, 2), (3, 7)]) == [
        (0, 1), (2, 3), (7, 8)]


def test_clock_shift_moves_programs_after_their_calls():
    raw = {"spans": [("call", 100.0, 110.0), ("wait", 110.0, 500.0),
                     ("call", 1000.0, 1010.0), ("wait", 1010.0, 1400.0)],
           "modules": {"/device:TPU:0": [(90.0, 400.0), (995.0, 1300.0)]},
           "devices": {}}
    assert reduce.clock_shift(raw) == 10.0
