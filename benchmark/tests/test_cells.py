"""Every cell's code runs end to end at a small size on the CPU (the
grid cell on four virtual devices), and its line has the contract's
keys, with the compared numbers last."""
import pytest

from benchmark import spec
from benchmark.tests.helpers import CELLS, run_small

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_cells_are_the_benchmarks():
    assert [w["name"] for w in spec.load_spec()["workloads"]] == list(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_line(cell):
    line = run_small(cell)
    assert all(k in line for k in KEYS)
    assert list(line)[-1] == "compare"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and line["window_compiles"] == 0
    c = spec.cell(spec.load_spec(), cell)
    assert set(line["metrics"]) == {m["name"] for m in c.end_to_end}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["count"] == c.chips
    cmp = line["compare"]["backward_error"]
    assert cmp["value"] <= cmp["limit"]


def test_traced_line():
    line = run_small("cholesky_f32.closed", trace=True)
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert "breakdown" in line and list(line)[-1] == "compare"
    # the CPU has no device trace: only the host spans' metric is read
    assert set(line["metrics"]) == {"host_call_pct.short_calls"}
