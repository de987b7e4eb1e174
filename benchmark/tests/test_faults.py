"""With the timed path broken underneath, a run reads not correct: once
for each fault a solve can have."""
import jax
import pytest

from benchmark.tests.helpers import SMALL, run_small


def _unchanged(fn):
    return lambda a, b: b                     # returns its input as is


def _half(fn):
    def f(a, b):
        x = fn(a, b)
        return x.at[x.shape[0] // 2:].set(0)  # half of the answer left out
    return f


def _altered(fn):
    def f(a, b):
        x = fn(a, b)
        return x.at[SMALL["N"] // 3].multiply(2)  # one entry altered
    return f


@pytest.mark.parametrize("cell", ["cholesky_f64.closed",
                                  "cholesky_f32.closed",
                                  "lu_f32_grid2x2.closed"])
@pytest.mark.parametrize("fault", [_unchanged, _half, _altered])
def test_fault_is_not_correct(cell, fault):
    line = run_small(cell, wrap=fault)
    assert line["correct"] is False and line["failed"] >= 1


@pytest.fixture
def fresh_traces():
    """Traces cached by earlier runs would hide a patched primitive."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_grid_without_exchange_is_not_correct(monkeypatch, fresh_traces):
    """The 2x2 LU with its psum exchanges between chips left out."""
    monkeypatch.setattr(jax.lax, "psum", lambda x, axis_name, **kw: x)
    line = run_small("lu_f32_grid2x2.closed")
    assert line["correct"] is False
