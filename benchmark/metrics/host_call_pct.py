"""Dispatch (host): share of the traced window spent inside the library
call, from the call to its return (before the wait for the answer), by
the harness's ``call`` spans. A host synchronisation inside the call
path shows here."""
from benchmark import reduce


def read(ctx):
    t = ctx["trace"]
    calls = reduce.union((a, b) for n, a, b in t.spans if n == "call")
    return 100.0 * reduce.length(calls) * 1e-9 / t.window_s
