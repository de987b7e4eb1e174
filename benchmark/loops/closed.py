"""Closed loop: one caller issues the next call only once the previous
answer is ready, for as long as the window lasts.

Traffic key read: ``rhs_pool`` (the right-hand sides, drawn from the
seed, are used in turn: call ``i`` solves for ``b[i % rhs_pool]``).
"""
from __future__ import annotations

import time


def drive(issue, seconds: float, traffic: dict):
    """Run the window. ``issue(k)`` makes one call with right-hand side
    ``k`` and returns ``(answer, t_call, t_ready)``. Returns
    ``(records, t_window_start, t_window_end)``, each record
    ``(k, t_call, t_ready, answer)``; the window closes with the answer
    of the last call issued before ``seconds`` had passed."""
    pool = int(traffic["rhs_pool"])
    recs = []
    t_start = time.perf_counter()
    while not recs or time.perf_counter() - t_start < seconds:
        k = len(recs) % pool
        x, t0, t1 = issue(k)
        recs.append((k, t0, t1, x))
    return recs, t_start, recs[-1][2]
