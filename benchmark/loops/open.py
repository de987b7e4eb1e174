"""Open loop: requests are submitted on a schedule drawn from the seed,
whatever the server is doing, and each is timed from the moment it was
due, so a host or a server that falls behind counts against the system
(no coordinated omission).

Traffic keys read: ``rate`` (requests/s, the mean over the window),
``bursts`` (``every_s``, ``length_s``, ``factor``: bursts of
``length_s`` at ``factor`` times the base rate, one in each stretch of
``every_s`` on average, the base rate set so that the mean stays at
``rate``), ``block`` (the request order's stratum count, below).

The schedule is a Poisson process conditioned on its counts: a window
of ``seconds`` holds ``round(rate * seconds)`` requests, the bursts
hold their expected share of them, split evenly, and every arrival
lies uniformly in its part of the window. So every seed offers the
same number of requests, in another order. Each request draws one of
the pool's operands, uniformly: the pool is cut by cost into ``block``
strata, and every ``block`` consecutive requests take one operand of
each stratum, without replacement until the stratum is used up. So a
window of any length offers nearly the same work for every seed.
"""
from __future__ import annotations

import time

import numpy as np

#: a request still unanswered this long after the last one was due
#: counts as failed
GRACE_S = 60.0
#: how often the close of the window looks for answers
POLL_S = 0.0005


def schedule(seed: int, seconds: float, traffic: dict, costs):
    """``(t_due, operand)``: due times in seconds from the window's
    start, ascending, and the pool index each request draws."""
    rng = np.random.default_rng([seed % 2 ** 63, 11])
    count = max(int(round(float(traffic["rate"]) * seconds)), 1)
    b = traffic["bursts"]
    nb = max(int(round(seconds / float(b["every_s"]))), 1)
    blen = min(float(b["length_s"]), seconds / nb)
    extra = (float(b["factor"]) - 1.0) * blen * nb
    in_bursts = int(round(count * extra / (seconds + extra)))
    slot = seconds / nb
    starts = [j * slot + rng.uniform(0.0, slot - blen) for j in range(nb)]
    t = list(rng.uniform(0.0, seconds, count - in_bursts))
    for j in range(in_bursts):
        s = starts[j % nb]
        t.append(rng.uniform(s, s + blen))
    t_due = np.sort(np.asarray(t))

    costs = np.asarray(costs, float)
    block = int(traffic["block"])
    strata = np.argsort(costs, kind="stable").reshape(block, -1)
    order = []
    while len(order) < count:
        picks = np.stack([rng.permutation(s) for s in strata])
        for col in picks.T:
            order.extend(rng.permutation(col))
    return t_due, np.asarray(order[:count])


def drive(client, seconds: float, traffic: dict, seed: int, costs):
    """Run the window. ``client.submit(k)`` queues a request for pool
    operand ``k`` and returns its handle without waiting;
    ``client.done(handle)`` says whether it has been answered. Each
    submit is a ``call`` span, the wait for the last answers a ``wait``
    span. Returns ``(records, t_start, t_closed)``, each record
    ``(k, t_due, t_submit, t_submit_ns, handle)`` on
    ``time.perf_counter`` (``t_submit_ns`` on ``time.time_ns``);
    ``handle`` is the exception where the submit itself raised."""
    import jax
    t_due, ops = schedule(seed, seconds, traffic, costs)
    recs = []
    t_start = time.perf_counter()
    for k, due in zip(ops.tolist(), (t_start + t_due).tolist()):
        lag = due - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        with jax.profiler.TraceAnnotation("call"):
            t_sub, t_ns = time.perf_counter(), time.time_ns()
            try:
                h = client.submit(k)
            except Exception as exc:   # shed: counted as failed
                h = exc
        recs.append((k, due, t_sub, t_ns, h))
    deadline = t_start + seconds + GRACE_S
    pending = [r[4] for r in recs if not isinstance(r[4], Exception)]
    with jax.profiler.TraceAnnotation("wait"):
        while pending and time.perf_counter() < deadline:
            pending = [h for h in pending if not client.done(h)]
            if pending:
                time.sleep(POLL_S)
    return recs, t_start, time.perf_counter()
