"""LAWN-41 operation counts of the solves the benchmark times.

Copied from ``dplasma_tpu/utils/flops.py`` (DPLASMA ``src/flops.h``),
real arithmetic only: a flop count is FMULS + FADDS.
"""
from __future__ import annotations


def potrf(n):
    return (n ** 3 / 6 + n ** 2 / 2 + n / 3) + (n ** 3 / 6 - n / 6)


def potrs(n, nrhs):
    return nrhs * n * (n + 1) + nrhs * n * (n - 1)


def getrf(m, n):
    mn = min(m, n)
    fmuls = 0.5 * m * n * mn - mn ** 3 / 6 + 0.5 * m * mn \
        - 0.5 * mn * n + 2 * mn / 3
    fadds = 0.5 * m * n * mn - mn ** 3 / 6 - 0.5 * m * mn + mn / 6
    return fmuls + fadds


def getrs(n, nrhs):
    return nrhs * n * n + nrhs * n * (n - 1)
