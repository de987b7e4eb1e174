"""One run of one cell: set-up, the measured window, the check, the line.

``run`` is what ``benchmark/run.py`` calls. An entry that serves
requests (``benchmark.ops.Service``) is run by ``benchmark/serve.py``;
what follows is the run of an entry that is one compiled call
(``benchmark.ops.Program``). Set-up makes the cell's
matrix and right-hand sides on the device from the seed, compiles the
one program the window calls (or loads it from the persistent cache),
and runs it once. The window then drives that compiled program through
the traffic mix's loop; nothing is compiled inside it, and the harness
counts compiles there. After the window: the device's peak memory, then
every answer (or a sample of 256 drawn from the seed) judged against
the plain reference on the host, then, in a traced run, the per-layer
metrics read from the trace.

``device.memory_peak_bytes`` is the larger of two readings for the
fullest chip, both also given under ``memory``: JAX's
``peak_bytes_in_use``, which on the TPU leaves out the compiled
program's temporaries, and the compiled window program's own
``memory_analysis()`` (arguments, outputs less aliased bytes,
temporaries and code, per device).
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time

import numpy as np

from benchmark import gen, hlo, ops, reduce, reference, serve, spec

#: answers compared per run at most (a sample drawn from the seed)
MAX_COMPARED = 256
#: the traced part of a ``--trace 1`` window, in seconds at most
TRACE_SECONDS = 4.0


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def _check_devices(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")


def _compile_counter(jax) -> dict:
    """``{"on": bool, "n": int}``: backend compiles are counted in ``n``
    while ``on`` is true."""
    state = {"on": False, "n": 0}

    def listen(event, duration, **_):
        if state["on"] and event.endswith("backend_compile_duration"):
            state["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return state


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, sizes: dict | None = None,
        require_chip: bool = True, wrap=None, trace_dir: str | None = None,
        root: str = spec.ROOT) -> dict:
    """The result line of one run, as a dict.

    ``wrap`` replaces the entry's traced function by ``wrap(fn)`` and
    skips the entry's check of its compiled path: the control and the
    faults of the tests run so. ``sizes`` (config keys such as ``N`` and
    ``nb``) and ``require_chip`` are for tests at small sizes on the
    CPU; ``trace_dir`` keeps the profile there instead of in a
    temporary directory."""
    import jax
    import jax.numpy as jnp

    c = spec.cell(spec.load_spec(root), cell_name, root)
    cfg = dict(c.config, **(sizes or {}))
    if "nrhs" in c.traffic:
        cfg["nrhs"] = int(c.traffic["nrhs"])
    if require_chip:
        _check_devices(jax, c.chips)
    devices = jax.devices()[:c.chips]
    parts = {"start_to_devices": time.perf_counter() - t_process}
    dev0 = devices[0]
    dtype = jnp.dtype(cfg["dtype"])
    if dtype == jnp.float64:
        jax.config.update("jax_enable_x64", True)
    t0 = time.perf_counter()
    prog = spec.entry(c).build(cfg, devices)
    parts["build"] = time.perf_counter() - t0
    if isinstance(prog, ops.Service):
        return serve.run(c, cfg, prog, seed, seconds, trace,
                         t_process=t_process, parts=parts, devices=devices,
                         wrap=wrap, trace_dir=trace_dir,
                         max_compared=MAX_COMPARED,
                         trace_seconds=TRACE_SECONDS,
                         compile_counter=_compile_counter)
    fn = prog.fn if wrap is None else wrap(prog.fn)
    n, nrhs = cfg["N"], cfg["nrhs"]
    bump = float(n) if cfg.get("bump") == "N" else float(cfg.get("bump", 0))

    t0 = time.perf_counter()
    a = gen.matrix(cfg["generator"], seed, n, n, dtype, bump,
                   prog.a_sharding)
    pool = [gen.matrix("plrnt", seed + 1, n, nrhs, dtype, 0.0,
                       prog.b_sharding, col0=k * nrhs)
            for k in range(int(c.traffic["rhs_pool"]))]
    a.block_until_ready()
    parts["inputs"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    with prog.scope():
        compiled = jax.jit(fn).lower(a, pool[0]).compile()
    compile_s = parts["compile_or_load"] = time.perf_counter() - t0
    if wrap is None:
        prog.check(compiled, dev0.platform)
    program_bytes = _program_bytes(compiled)
    t0 = time.perf_counter()
    compiled(a, pool[0]).block_until_ready()
    parts["warm_up"] = time.perf_counter() - t0

    def issue(k):
        with jax.profiler.TraceAnnotation("call"):
            t_call = time.perf_counter()
            x = compiled(a, pool[k])
        with jax.profiler.TraceAnnotation("wait"):
            x.block_until_ready()
        return x, t_call, time.perf_counter()

    loop = spec.loop(c)
    window = min(seconds, TRACE_SECONDS) if trace else seconds
    compiles = _compile_counter(jax)
    if trace:
        tdir = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir)
    setup_s = time.perf_counter() - t_process
    compiles["on"] = True
    recs, w0, w1 = loop.drive(issue, window, c.traffic)
    compiles["on"] = False
    if trace:
        jax.profiler.stop_trace()
    window_s = w1 - w0
    in_use = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devices)

    line = {"correct": None, "attempted": len(recs), "failed": None,
            "metrics": {},
            "device": {"platform": dev0.platform, "kind": dev0.device_kind,
                       "count": len(devices),
                       "memory_peak_bytes": max(in_use, program_bytes)},
            "memory": {"peak_bytes_in_use": in_use,
                       "program_bytes": program_bytes},
            "compile_s": compile_s, "setup_parts": parts,
            "window_compiles": compiles["n"]}
    if trace:
        hlo_texts = [compiled.as_text()]
    flops = prog.flops
    del compiled, prog, fn
    lat_ms = [(r[2] - r[1]) * 1e3 for r in recs]

    compare = _judge(cfg, c, seed, a, pool, recs, dtype)
    line["failed"] = compare.pop("failed_answers")
    line["answers_compared"] = compare.pop("answers_compared")
    line["correct"] = bool(compare["ok"] and line["failed"] == 0
                           and compiles["n"] == 0)

    if trace:
        try:
            t = reduce.Trace(reduce.load(reduce.find_xplane(tdir)),
                             hlo.index(hlo_texts))
            line["device"]["busy_s"] = t.busy_s()
            line["device"]["window_s"] = t.window_s
            ctx = {"trace": t, "device_kind": dev0.device_kind,
                   "cell": c, "config": cfg, "hlo_texts": hlo_texts}
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
                  "gflops": flops * len(lat_ms) / window_s / 1e9,
                  "solve_ms_p95": float(np.percentile(lat_ms, 95))}
        # ``gflops.short_calls`` is ``gflops`` held to a bound of its own
        for m in c.end_to_end:
            line["metrics"][m["name"]] = {
                "value": values[m["name"].split(".")[0]], "unit": m["unit"]}
    line["compare"] = compare["numbers"]
    for name, v in compare["numbers"].items():
        sys.stderr.write(f"compare {name} {v['value']!r} "
                         f"limit {v['limit']!r}\n")
    sys.stderr.flush()
    return line


def _program_bytes(compiled) -> int:
    """What the compiled program holds on each device while it runs."""
    m = compiled.memory_analysis()
    if m is None:
        return 0
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes
               + m.generated_code_size_in_bytes)


def _judge(cfg: dict, c, seed: int, a, pool, recs, dtype) -> dict:
    """Backward error of every answer (or of a sample drawn from the
    seed) against the plain reference, on the host."""
    import jax
    idx = np.arange(len(recs))
    if len(idx) > MAX_COMPARED:
        idx = np.sort(np.random.default_rng(seed).choice(
            idx, MAX_COMPARED, replace=False))
    xs = np.concatenate([np.asarray(jax.device_get(recs[i][3]), np.float64)
                         for i in idx], axis=1)
    bs = np.concatenate([np.asarray(jax.device_get(pool[recs[i][0]]),
                                    np.float64) for i in idx], axis=1)
    a_host = np.asarray(jax.device_get(a), np.float64)
    for r in recs:
        r[3].delete()
    del recs[:]
    eps = float(np.finfo(dtype).eps)
    bwd = reference.backward_errors(a_host, xs, bs, eps)
    limit = float(cfg["limits"]["backward_error"])
    worst = float(np.max(bwd))
    return {"ok": bool(np.isfinite(worst) and worst <= limit),
            "failed_answers": int(np.sum(~(bwd <= limit))),
            "answers_compared": int(len(idx)),
            "numbers": {"backward_error": {"value": worst, "limit": limit}}}
