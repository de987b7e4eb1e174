"""Refinement: share of device busy time in the operations under the
``dplasma.residual`` or ``dplasma.correct`` scope, at any depth (the
f64-equivalent residuals and the GMRES cycles with their preconditioner
solves), found by ``benchmark/scopes.index``; the union per device, over
the devices' busy time, as ``panel_pct`` reads its share."""
from benchmark import reduce, scopes

#: the scopes of the refinement's own work
REFINE = frozenset({"residual", "correct"})


def read(ctx):
    t = ctx["trace"]
    sc = scopes.index(ctx.get("hlo_texts", ()))
    part = busy = 0.0
    for plane, evs in t.devices.items():
        part += reduce.length(reduce.union(
            (a, b) for n, a, b in evs if REFINE & set(sc.get(n, ()))))
        busy += reduce.length(t.busy(plane))
    if part <= 0 or busy <= 0:
        return None
    return 100.0 * part / busy
