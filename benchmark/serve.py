"""One run of a request-serving cell (an entry that returns
``benchmark.ops.Service``): set-up, the open window, the check, the
line.

Set-up makes the traffic's pool of operands from the seed
(``benchmark/pool.py``), starts the server and has it warm every
program the pool's shapes can reach; the harness's compile counter then
requires that nothing compiles in the window. The window submits
requests on the traffic's schedule (``benchmark/loops/<loop>.py``), and
closes when the last request due in it is answered. Each request's
latency runs from its due time to the answer: ``t_submit`` plus the
latency the server reports from its submit. After the window: the
device's peak memory, then every answer (or a sample of 256 drawn from
the seed) judged against its own ``A`` and ``b`` by the plain
reference's backward error, with a limit per op.

End-to-end values: ``gflops`` (LAWN-41 operations of the requests
answered within the schedule's window, each at its own n and NRHS, over
the window's length) and ``solve_ms_p95`` (95th percentile of the
due-to-answer times of all its requests). A traced
run gives the readers, besides the trace, ``ctx["hlo_texts"]`` (every
program the server warmed), ``ctx["requests"]`` (one dict per request:
op, n, nrhs, flops, t_due, t_submit, t_ready, meta, id, the dispatched
program and its bucket) and ``ctx["server_spans"]``.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time

import numpy as np

from benchmark import hlo, pool as pool_mod, reduce, reference, spec


def run(c, cfg: dict, prog, seed: int, seconds: float, trace: bool, *,
        t_process: float, parts: dict, devices, wrap, trace_dir,
        max_compared: int, trace_seconds: float,
        compile_counter) -> dict:
    import jax

    from benchmark.harness import _program_bytes
    dev0 = devices[0]
    t0 = time.perf_counter()
    operands = pool_mod.make(seed, c.traffic, cfg["dtype"])
    parts["inputs"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    server = prog.start(wrap)
    try:
        programs = server.warm(warm_shapes(operands))
        parts["compile_or_load"] = time.perf_counter() - t0
        parts["programs"] = len(programs)
        program_bytes = max((_program_bytes(p) for p in programs.values()),
                            default=0)
        client = _Client(server, operands)
        loop = spec.loop(c)
        window = min(seconds, trace_seconds) if trace else seconds
        compiles = compile_counter(jax)
        if trace:
            tdir = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(tdir)
        setup_s = time.perf_counter() - t_process
        compiles["on"] = True
        recs, w0, w_closed = loop.drive(
            client, window, c.traffic, seed, [o.flops for o in operands])
        compiles["on"] = False
        if trace:
            jax.profiler.stop_trace()
        in_use = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices)
        reqs = _requests(server, operands, recs)
        spans = server.spans() if trace else []
        texts = ({name: p.as_text() for name, p in sorted(programs.items())}
                 if trace else {})
    finally:
        server.close()
    del programs, client
    done = [r for r in reqs if r["t_ready"] is not None]
    # the rate's window is the schedule's: the work answered in it over
    # its length (the loop still waits for every answer, for the p95
    # and the check)
    in_window = [r for r in done if r["t_ready"] <= w0 + window]
    lat_ms = [(r["t_ready"] - r["t_due"]) * 1e3 for r in done]
    late = [r["t_submit"] - r["t_due"] for r in reqs]

    line = {"correct": None, "attempted": len(reqs), "failed": None,
            "metrics": {},
            "device": {"platform": dev0.platform, "kind": dev0.device_kind,
                       "count": len(devices),
                       "memory_peak_bytes": max(in_use, program_bytes)},
            "memory": {"peak_bytes_in_use": in_use,
                       "program_bytes": program_bytes},
            "compile_s": parts["compile_or_load"], "setup_parts": parts,
            "window_compiles": compiles["n"],
            "answered_in_window": len(in_window),
            "last_answer_s": max([r["t_ready"] for r in done],
                                 default=w_closed) - w0,
            "latency_ms": {q: _pct(lat_ms, q) for q in (50, 90, 95, 99)},
            "submit_late_ms": {"p50": _pct(late, 50) * 1e3,
                               "p95": _pct(late, 95) * 1e3,
                               "max": max(late, default=0.0) * 1e3}}
    line["latency_ms"]["max"] = max(lat_ms, default=0.0)
    compare = judge(cfg, seed, operands, reqs, max_compared)
    line["failed"] = compare["failed"]
    line["answers_compared"] = compare["compared"]
    line["correct"] = bool(compare["ok"] and line["failed"] == 0
                           and compiles["n"] == 0)
    for r in reqs:
        r.pop("x", None)

    if trace:
        try:
            ran = {r["program"] for r in reqs if r["program"]}
            t = _trace(tdir, {n: texts[n] for n in ran}, recs, spans)
            line["device"]["busy_s"] = t.busy_s()
            line["device"]["window_s"] = t.window_s
            ctx = {"trace": t, "device_kind": dev0.device_kind, "cell": c,
                   "config": cfg, "hlo_texts": list(texts.values()),
                   "requests": reqs, "server_spans": spans}
            for m in c.per_layer:
                v = spec.reader(c, m["name"]).read(ctx)
                if v is not None:
                    line["metrics"][m["name"]] = {"value": v,
                                                  "unit": m["unit"]}
            line["breakdown"] = t.breakdown()
        finally:
            if trace_dir is None:
                shutil.rmtree(tdir, ignore_errors=True)
    else:
        values = {"setup_s": setup_s,
                  "gflops": sum(r["flops"] for r in in_window) / window
                  / 1e9,
                  "solve_ms_p95": float(np.percentile(lat_ms, 95))
                  if lat_ms else float("nan")}
        for m in c.end_to_end:
            line["metrics"][m["name"]] = {
                "value": values[m["name"].split(".")[0]], "unit": m["unit"]}
    line["compare"] = compare["numbers"]
    for name, v in compare["numbers"].items():
        sys.stderr.write(f"compare {name} {v['value']!r} "
                         f"limit {v['limit']!r}\n")
    sys.stderr.flush()
    return line


def warm_shapes(operands) -> list:
    """The request shapes set-up warms the server for: every operand's."""
    return [(o.op, o.n, o.nrhs) for o in operands]


class _Client:
    """What the loop sees of the server: submit pool operand ``k``."""

    def __init__(self, server, operands):
        self.server, self.operands = server, operands

    def submit(self, k):
        o = self.operands[k]
        return self.server.submit(o.op, o.a, o.b)

    def done(self, handle) -> bool:
        return self.server.outcome(handle) is not None


def _requests(server, operands, recs) -> list:
    out = []
    for k, due, t_sub, t_ns, h in recs:
        o = operands[k]
        r = {"k": k, "op": o.op, "n": o.n, "nrhs": o.nrhs,
             "flops": o.flops, "t_due": due, "t_submit": t_sub,
             "t_submit_ns": t_ns, "t_ready": None, "x": None, "meta": {},
             "id": None, "program": None, "bucket": None}
        got = h if isinstance(h, Exception) else server.outcome(h)
        if got is None:
            r["x"] = TimeoutError("no answer within the grace period")
        elif isinstance(got, Exception):
            r["x"] = got
        else:
            x, lat, meta = got
            r["x"], r["meta"] = x, meta
            r["id"] = meta.get("request_id")
            if not isinstance(x, Exception):
                r["t_ready"] = t_sub + lat
                name, n, nrhs, batch, carried = server.dispatched(o.op,
                                                                  meta)
                r["program"] = name
                r["bucket"] = {"n": n, "nrhs": nrhs, "batch": batch,
                               "requests": carried}
        out.append(r)
    return out


def judge(cfg: dict, seed: int, operands, reqs, max_compared: int) -> dict:
    """Backward error of every answer (or of a sample drawn from the
    seed) against its own ``A`` and ``b``, on the host, worst per op
    against the op's limit. A request with no answer counts as
    failed."""
    failed = sum(1 for r in reqs if r["t_ready"] is None)
    idx = [i for i, r in enumerate(reqs) if r["t_ready"] is not None]
    if len(idx) > max_compared:
        idx = sorted(np.random.default_rng(seed).choice(
            idx, max_compared, replace=False).tolist())
    eps = float(np.finfo(np.dtype(cfg["dtype"])).eps)
    limits = cfg["limits"]["backward_error"]
    worst = {op: 0.0 for op in limits}
    by_k: dict = {}
    for i in idx:
        by_k.setdefault(reqs[i]["k"], []).append(i)
    for k, rows in by_k.items():
        o = operands[k]
        a64 = np.asarray(o.a, np.float64)
        for i in rows:
            bwd = float(np.max(reference.backward_errors(
                a64, np.asarray(reqs[i]["x"]), o.b, eps)))
            if not bwd <= limits[o.op]:
                failed += 1
            worst[o.op] = max(worst[o.op], bwd) if np.isfinite(bwd) \
                else float("inf")
    ok = all(np.isfinite(worst[op]) and worst[op] <= limits[op]
             for op in limits)
    return {"ok": bool(ok), "failed": int(failed), "compared": len(idx),
            "numbers": {f"backward_error.{op}":
                        {"value": worst[op], "limit": float(limits[op])}
                        for op in sorted(limits)}}


def _trace(tdir: str, texts: dict, recs, spans) -> reduce.Trace:
    """The traced window, each device op named ``<program>/<op>`` by the
    program whose run it lies in, and the programs' launches (the
    server's ``dispatch`` spans, moved onto the profiler's clock by the
    loop's ``call`` spans) as the spans that set the clock shift."""
    raw = reduce.load(reduce.find_xplane(tdir), programs=True)
    index = {}
    for name, text in texts.items():
        for op, info in hlo.index([text]).items():
            index[f"{name}/{op}"] = info
    raw = reduce.name_by_program(raw, {n: hlo.module_name(t)
                                       for n, t in texts.items()})
    calls = [s[1] for s in raw["spans"] if s[0] == "call"]
    # profiler clock minus time_ns, one reading per traced submit
    offs = [t - rec[3] for t, rec in zip(calls, recs[-len(calls):])] \
        if calls else [0]
    off = float(np.median(offs))
    launches = [("launch", t0 + off, t1 + off)
                for name, _, t0, t1 in spans if name == "dispatch"]
    raw["spans"] = sorted(raw["spans"] + launches, key=lambda e: e[1])
    return reduce.Trace(raw, index, launch="launch")


def _pct(values, q) -> float:
    return float(np.percentile(values, q)) if len(values) else 0.0
