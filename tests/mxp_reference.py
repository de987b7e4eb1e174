"""Plain reference for HPL-MxP's solve: the right-looking LU without
pivoting of a dense matrix and the direct solve, in NumPy float64, with
no blocking, tiling, grid or refinement. ``tests/test_mxp.py`` holds
the library to it."""
import numpy as np


def lu_nopiv(a):
    """``(L, U)`` with ``a = L U``, L unit lower, without pivoting:
    column by column, each step a rank-1 update of the trailing
    matrix."""
    u = np.array(a, np.float64)
    n = u.shape[0]
    lo = np.eye(n)
    for k in range(n - 1):
        lo[k + 1:, k] = u[k + 1:, k] / u[k, k]
        u[k + 1:, k:] -= np.outer(lo[k + 1:, k], u[k, k:])
    return lo, np.triu(u)


def solve(a, b):
    """The answer of ``a x = b`` by LAPACK's pivoted LU in float64."""
    return np.linalg.solve(np.asarray(a, np.float64),
                           np.asarray(b, np.float64))


def hpl_scaled_residual(a, x, b):
    """HPL's test quantity ``||b - A x||_inf / (eps (||A||_inf ||x||_inf
    + ||b||_inf) N)``, eps the float64 epsilon; HPL passes below 16."""
    a, x, b = (np.asarray(v, np.float64) for v in (a, x, b))
    r = np.abs(b - a @ x).max()
    den = (np.abs(a).sum(axis=1).max() * np.abs(x).max()
           + np.abs(b).max())
    return r / (np.finfo(np.float64).eps * den * a.shape[0])
