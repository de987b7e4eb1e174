"""The control, the plain reference in the precision below each
configuration's put in the program's place, makes a run not correct; the
reference at the stated precision in the same place makes it correct.
On the CPU at a size a test run holds; the chip readings at the cells'
own sizes are in PERF.md."""
import json

import pytest

from benchmark import control
from benchmark.tests.helpers import CELLS

SIZE = {"N": 1024, "nb": 256}
SEEDS = [5, 2 ** 31 + 9]


def _line(cell, seed, stated):
    line = control.run(cell, seed, 0.3, stated=stated, sizes=SIZE,
                       require_chip=False)
    return json.loads(json.dumps(line))


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, seed):
    line = _line(cell, seed, stated=False)
    cmp = line["compare"]["backward_error"]
    assert line["correct"] is False and cmp["value"] > cmp["limit"], cmp


@pytest.mark.parametrize("cell", CELLS)
def test_stated_precision_is_correct(cell):
    line = _line(cell, SEEDS[1], stated=True)
    assert line["correct"] is True, line["compare"]
