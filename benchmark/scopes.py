#!/usr/bin/env python3
"""The library's own phases in a traced window, read from its named
scopes.

Every phase span of ``dplasma_tpu`` opens the named scope
``dplasma.<name>``, so each HLO instruction emitted inside carries those
scopes in its ``op_name`` metadata, outermost first
(``jit(solve)/dplasma.potrf/dplasma.panel/cholesky``). ``index`` reads them from the text of the
compiled programs, by instruction name, as ``hlo.index`` reads the rest;
the readings below join them to a ``reduce.Trace``:

* ``busy_by_scope``: device-busy seconds under each scope (at any depth),
  and ``busy_by_path`` under each whole scope path;
* ``unscoped_s`` and ``top_unscoped``: the busy time of ops under no scope;
* ``idle_gaps``: each idle gap with the harness span it falls in and
  the scope paths of the ops on either side (``wait: panel>update``);
* ``panel_scope_pct``, ``limb_overhead_pct`` and ``layout_exposed_pct``:
  the shares that later per-layer metrics are to report.

A program without scopes (the library before they were added) reads
empty: every share is None. The vocabulary is copied from
``dplasma_tpu/observability/phases.py`` (``SCOPES``).

    python3 benchmark/scopes.py --workload <cell> --seed <n> [--out DIR]

runs one traced window of the cell (``--seconds`` as ``run.py`` takes
it, traced up to ``harness.TRACE_SECONDS``), prints the result line with
a ``scopes`` section added, and with ``--out`` keeps the trace and the
program text there as ``<cell>.xplane.pb.gz`` and ``<cell>.hlo.txt.gz``.

    python3 benchmark/scopes.py --stem DIR/<cell>

reads such a pair again.
"""
from __future__ import annotations

import os
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import hlo, reduce  # noqa: E402

#: the span names of ``dplasma_tpu.observability.phases.SCOPES``
SCOPES = (
    "potrf", "getrf", "panel", "update", "lookahead", "far_flush",
    "catchup", "assemble", "split", "recombine", "solve", "laswp",
    "redistribute", "bcast", "elect", "playoff", "exchange", "ring",
    "factor", "residual", "correct", "escalate", "quantize", "dequantize",
)
PREFIX = "dplasma."
#: scopes of the dd engine's limb work around its int8 products
LIMB = frozenset({"split", "recombine"})
#: scopes of layout changes: to and from cyclic storage, row gathers
LAYOUT = frozenset({"redistribute", "laswp"})


def scope_of(op_name: str) -> tuple:
    """The ``dplasma.*`` components of an op name, outermost first,
    without the prefix."""
    out = []
    for part in op_name.split("/"):
        if part.startswith(PREFIX):
            out.append(part[len(PREFIX):].split("[")[0].rstrip(":"))
    return tuple(out)


def _common(scopes) -> tuple:
    """The longest scope prefix shared by every tuple in ``scopes``."""
    out = ()
    for parts in zip(*scopes):
        if len(set(parts)) > 1:
            break
        out += (parts[0],)
    return out


def index(texts) -> dict:
    """{instruction name: scope tuple} over the modules in ``texts``.

    An instruction's own op name decides. One without (XLA's fusions
    with a tuple root, the instructions it makes for a multi-output
    fusion) takes the scopes its fused instructions share: the longest
    common prefix of the scoped op names in the computation it calls.
    One that still has none (what XLA inserts to move data: the ``-done``
    half of an async pair, copies, the collectives GSPMD adds, a Mosaic
    kernel whose name replaced its op name) takes the scope of its first
    operand that has one: it moves or finishes that operand's data."""
    out: dict = {}
    for text in texts:
        comps = hlo.parse(text)
        inner: dict = {}   # computation -> scope tuples of its ops

        def scoped(cname, seen=()):
            if cname not in inner:
                found = []
                for ins in comps.get(cname, ()):
                    m = hlo._OPNAME.search(ins["attrs"])
                    if m:
                        sc = scope_of(m.group(1))
                        if sc:
                            found.append(sc)
                    elif ins["opcode"] == "fusion":
                        called = (hlo._attr(ins["attrs"], "calls")
                                  or "").lstrip("%")
                        if called and called not in seen:
                            found.extend(scoped(called, seen + (cname,)))
                inner[cname] = found
            return inner[cname]

        for cname, inss in comps.items():
            # operands are printed before their users: one pass follows
            # a chain of unscoped instructions back to a scoped one
            for ins in inss:
                m = hlo._OPNAME.search(ins["attrs"])
                sc = scope_of(m.group(1)) if m else ()
                if not m:
                    called = (hlo._attr(ins["attrs"], "calls")
                              or "").lstrip("%")
                    found = scoped(called, (cname,)) if called else []
                    sc = _common(found) if found else ()
                if not sc:
                    sc = next((out[n] for n in (
                        op.split()[-1].lstrip("%") for op in ins["operands"]
                        if op.strip()) if out.get(n)), ())
                out[ins["name"]] = sc
    return out


def path(scope: tuple) -> str:
    """``potrf/panel``: a scope tuple as one name, ``(none)`` if empty."""
    return "/".join(scope) if scope else "(none)"


def _per_device(trace, keep) -> float:
    """Seconds, averaged over devices, of the union of the ops for
    which ``keep(op name)`` holds."""
    if not trace.devices:
        return 0.0
    total = sum(reduce.length(reduce.union(
        (a, b) for n, a, b in evs if keep(n)))
        for evs in trace.devices.values())
    return total * 1e-9 / len(trace.devices)


def busy_by_scope(trace, scopes: dict) -> dict:
    """{scope: device-busy seconds of the ops under it at any depth}."""
    found = {s for sc in scopes.values() for s in sc}
    return {s: _per_device(trace, lambda n, s=s: s in scopes.get(n, ()))
            for s in sorted(found)}


def busy_by_path(trace, scopes: dict) -> dict:
    """{scope path: device-busy seconds of the ops whose innermost scope
    path it is}, heaviest first; ``(none)`` holds the unscoped ops."""
    paths = {path(scopes.get(n, ())) for evs in trace.devices.values()
             for n, _, _ in evs}
    out = {p: _per_device(trace, lambda n, p=p: path(scopes.get(n, ())) == p)
           for p in paths}
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def unscoped_s(trace, scopes: dict) -> float:
    return _per_device(trace, lambda n: not scopes.get(n, ()))


def top_unscoped(trace, scopes: dict, top: int = 10) -> list:
    """[[category:op name, seconds per device], ...] of the heaviest
    ops under no scope."""
    ops = [(n, s) for n, s in trace.op_seconds().items()
           if not scopes.get(n, ())]
    ops.sort(key=lambda kv: -kv[1])
    return [[f"{trace.info(n)['category']}:{n}", s] for n, s in ops[:top]]


def idle_gaps(trace, scopes: dict, top: int = 10) -> list:
    """[["<span>: <scope path before> > <scope path after>", seconds],
    ...] of the longest idle gaps over all devices: the harness span the
    gap falls in and the scopes of the last op to end before it and the
    first to start after it (``start`` and ``end`` at the window's
    edges)."""
    import bisect
    gaps = [(g1 - g0, plane, g0, g1) for plane in trace.devices
            for g0, g1 in trace.idle_gaps(plane)]
    gaps.sort(key=lambda g: -g[0])
    out = []
    for d, plane, g0, g1 in gaps[:top]:
        evs = trace.devices[plane]
        ends = sorted((b, n) for n, a, b in evs)
        starts = sorted((a, n) for n, a, b in evs)
        i = bisect.bisect_right([b for b, _ in ends], g0)
        j = bisect.bisect_left([a for a, _ in starts], g1)
        prev = path(scopes.get(ends[i - 1][1], ())) if i else "start"
        nxt = (path(scopes.get(starts[j][1], ())) if j < len(starts)
               else "end")
        out.append([f"{trace.span_at((g0 + g1) / 2)}: {prev} > {nxt}",
                    d * 1e-9])
    return out


def _share_of_busy(trace, keep):
    part = busy = 0.0
    for plane, evs in trace.devices.items():
        part += reduce.length(reduce.union(
            (a, b) for n, a, b in evs if keep(n)))
        busy += reduce.length(trace.busy(plane))
    if part <= 0 or busy <= 0:
        return None
    return 100.0 * part / busy


def panel_scope_pct(trace, scopes: dict):
    """Share of device busy time in ops under any ``dplasma.panel``."""
    return _share_of_busy(trace, lambda n: "panel" in scopes.get(n, ()))


def limb_overhead_pct(trace, scopes: dict):
    """Share of device busy time under ``dplasma.split`` or
    ``dplasma.recombine``: the work the int8-limb scheme adds around its
    products."""
    return _share_of_busy(trace,
                          lambda n: bool(LIMB & set(scopes.get(n, ()))))


def layout_exposed_pct(trace, scopes: dict):
    """``collective_exposed_pct``'s rule (a collective or ring kernel
    running on a device while nothing else does), kept only where the
    collective is under ``dplasma.redistribute`` or ``dplasma.laswp``;
    share of the window, averaged over the devices."""
    if not trace.devices:
        return None
    total, found = 0.0, False
    for evs in trace.devices.values():
        coll = [(a, b) for n, a, b in evs
                if trace.info(n)["category"] == "collective"
                and LAYOUT & set(scopes.get(n, ()))]
        found = found or bool(coll)
        rest = reduce.union((a, b) for n, a, b in evs
                            if trace.info(n)["category"] != "collective")
        total += reduce.length(reduce.subtract(reduce.union(coll), rest))
    if not found:
        return None
    return 100.0 * total * 1e-9 / trace.window_s / len(trace.devices)


def solve_ms(trace) -> list:
    """Milliseconds from each traced call to its answer being ready
    (a ``call`` span's start to the end of the ``wait`` after it)."""
    out, start = [], None
    for name, a, b in sorted(trace.spans, key=lambda s: s[1]):
        if name == "call":
            start = a
        elif name == "wait" and start is not None:
            out.append((b - start) * 1e-6)
            start = None
    return out


def report(trace, scopes: dict, top: int = 10) -> dict:
    """Everything above for one traced window, as JSON-able values."""
    busy = trace.busy_s()
    ms = solve_ms(trace)
    return {
        "solves": len(ms),
        "solve_ms_mean": sum(ms) / len(ms) if ms else None,
        "busy_s": busy,
        "unscoped_s": unscoped_s(trace, scopes),
        "unscoped_pct": (100.0 * unscoped_s(trace, scopes) / busy
                         if busy > 0 else None),
        "by_scope": busy_by_scope(trace, scopes),
        "by_path": dict(list(busy_by_path(trace, scopes).items())[:top]),
        "top_unscoped": top_unscoped(trace, scopes, top),
        "idle_gaps": idle_gaps(trace, scopes, top),
        "panel_scope_pct": panel_scope_pct(trace, scopes),
        "limb_overhead_pct": limb_overhead_pct(trace, scopes),
        "layout_exposed_pct": layout_exposed_pct(trace, scopes),
    }


def load(stem: str):
    """(Trace, scope index) of a kept pair ``<stem>.xplane.pb.gz`` and
    ``<stem>.hlo.txt.gz``."""
    import gzip
    with gzip.open(stem + ".hlo.txt.gz", "rt") as f:
        text = f.read()
    trace = reduce.Trace(reduce.load(stem + ".xplane.pb.gz"),
                         hlo.index([text]))
    return trace, index([text])


def _traced_run(ns) -> dict:
    """One traced run of the harness, the program text and trace kept
    for ``report`` (and under ``--out``)."""
    import gzip
    import shutil
    import tempfile
    import time

    t_process = time.perf_counter()
    # the compile cache and the TPU runtime's logs as run.py sets them
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cache = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["TPU_LOG_DIR"] = os.path.join(tempfile.gettempdir(),
                                             "tpu_logs")
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from benchmark import harness
    texts = []
    index_of = hlo.index

    def keep(t):
        texts.extend(t)
        return index_of(t)

    tdir = tempfile.mkdtemp(prefix="bench_scopes_")
    hlo.index = keep
    try:
        line = harness.run(ns.workload, ns.seed, ns.seconds, True,
                           t_process=t_process, trace_dir=tdir)
        xplane = reduce.find_xplane(tdir)
        trace = reduce.Trace(reduce.load(xplane), index_of(texts))
        line["scopes"] = report(trace, index(texts))
        if ns.out:
            os.makedirs(ns.out, exist_ok=True)
            stem = os.path.join(ns.out, ns.workload)
            with open(xplane, "rb") as src, \
                    gzip.open(stem + ".xplane.pb.gz", "wb") as dst:
                shutil.copyfileobj(src, dst)
            with gzip.open(stem + ".hlo.txt.gz", "wt") as dst:
                dst.write("\n".join(texts))
    finally:
        hlo.index = index_of
        shutil.rmtree(tdir, ignore_errors=True)
    return line


def main(argv=None) -> int:
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--out", help="keep the trace and program text here")
    ap.add_argument("--stem", help="read a kept pair instead of running")
    ns = ap.parse_args(argv)
    if ns.stem:
        line = {"scopes": report(*load(ns.stem))}
    else:
        if not ns.workload or ns.seed is None:
            ap.error("--workload and --seed, or --stem")
        line = _traced_run(ns)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
