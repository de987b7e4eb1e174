"""chip_smoke.py's own logic on the CPU: it refuses to run without a
TPU, and its grid comparison's helpers are right."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import chip_smoke  # noqa: E402


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_refuses_without_tpu(argv, capsys):
    assert chip_smoke.main(argv) == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "no TPU" in out.err


@pytest.mark.parametrize("n,nb", [(300, 128), (256, 64)])
def test_lu_nopiv_is_the_unpivoted_lu(n, nb):
    rng = np.random.default_rng(n)
    a = rng.standard_normal((n, n)) + n * np.eye(n)
    f = chip_smoke._lu_nopiv(a, nb)
    lo = np.tril(f, -1) + np.eye(n)
    assert np.abs(lo @ np.triu(f) - a).max() < 1e-12 * np.abs(a).max()


def test_ring_calls_counts_named_mosaic_calls():
    class _Compiled:
        def as_text(self):
            return (
                '%dplasma_ring_bcast_q.8 = f32[8,128] custom-call(%a), '
                'custom_call_target="tpu_custom_call"\n'
                '%dplasma_ring_shift_p = f32[8,128] custom-call(%b), '
                'custom_call_target="tpu_custom_call"\n'
                '%other.1 = f32[8,128] custom-call(%c), '
                'custom_call_target="tpu_custom_call"\n'
                '%dplasma_ring_bcast_q.9 = f32[8,128] add(%a, %b)\n')
    assert chip_smoke._ring_calls(_Compiled()) == 2
