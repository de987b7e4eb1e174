#!/usr/bin/env python3
"""Proof that the library's main path runs on a TPU, in one process.

``python chip_smoke.py`` (one chip) runs, through the entry points a
user calls:

* ``testing_spotrf -N 16384 -t 1024 -x`` (f32 Cholesky, a 1 GiB matrix);
* ``testing_dpotrf -N 8192 -t 512 -x`` (f64-equivalent: the int8 limb
  engine and the ``pallas_dd`` recombine);
* ``testing_sgetrf -N 8192 -t 1024 -x`` (partial-pivot LU);
* a ``SolverService`` answering posv/gesv requests at n = 300, 1000,
  2000, each checked against a NumPy solve on the host.

``python chip_smoke.py --chips 4`` runs only the 2x2-grid path:
``testing_dpotrf -N 4096 -t 512 -x`` (GSPMD-sharded; the size the
PR's four-chip budget allowed, see CHANGES.md, PR 21) and
``testing_sgetrf_ptgpanel -N 8192 -t 512 -x`` (block-cyclic
``shard_map`` LU; its compiled program must hold the ICI ring
kernels). Each grid run
passes ``-x``, holds a quarter of its input and output on every
device, and its factor matches a float64 factor of the same input made
on the host.

Every phase prints one ``phase {...}`` line: wall and compile seconds,
the residuals its checks printed, and the platform and device kind its
result lives on. The last line is the verdict,
``{"ok": true, "device": {...}}``. Without a TPU the script fails
before any phase; a failed phase fails the script.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

ONE_CHIP = (
    ["testing_spotrf", "-N", "16384", "-t", "1024", "-x"],
    ["testing_dpotrf", "-N", "8192", "-t", "512", "-x"],
    ["testing_sgetrf", "-N", "8192", "-t", "1024", "-x"],
)
GRID = (
    ["testing_dpotrf", "-N", "4096", "-t", "512", "-x"],
    ["testing_sgetrf_ptgpanel", "-N", "8192", "-t", "512", "-x"],
)
SERVE_SIZES = (300, 1000, 2000)
SERVE_NB = 256
#: the repo's residual threshold (ops.checks.THRESHOLD)
THRESHOLD = 60.0


class SmokeFailure(RuntimeError):
    pass


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _placement(tree) -> dict:
    """Platform, device kind and device count of the arrays in
    ``tree``."""
    import jax
    devs = {d for x in jax.tree.leaves(tree) if isinstance(x, jax.Array)
            for d in x.devices()}
    return {"platform": sorted({d.platform for d in devs}),
            "kind": sorted({d.device_kind for d in devs}),
            "devices": len(devs)}


def _phase_line(rec: dict) -> None:
    print("phase " + json.dumps(rec, sort_keys=True), flush=True)


def driver_phase(argv, keep=None):
    """Run one ``testing_*`` driver through ``drivers.main`` and print
    its phase line; fail on a non-zero exit, a failed or missing check,
    or a result off the TPU. Returns what ``keep(drv)`` took for a
    later comparison; its ``"note"`` joins the phase line."""
    from dplasma_tpu import drivers
    seen: dict = {}

    def inspect(drv):
        seen["checks"] = [dict(c) for c in drv.report.checks]
        seen["compile_s"] = drv.report.ops[-1]["timings"]["enq_s"]
        seen["run_s"] = drv.report.ops[-1]["timings"]["min_s"]
        seen["where"] = _placement(drv.output)
        if keep is not None:
            seen["kept"] = keep(drv)

    t0 = time.perf_counter()
    rc = drivers.main(list(argv), inspect=inspect)
    wall = time.perf_counter() - t0
    _require(rc == 0, f"{' '.join(argv)} exited {rc}")
    _require(bool(seen.get("checks")), f"{argv[0]}: no -x check ran")
    rec = {"phase": " ".join(argv), "wall_s": wall,
           "compile_s": seen["compile_s"], "run_s": seen["run_s"],
           "residuals": {c["what"]: c["residual"] for c in seen["checks"]},
           "where": seen["where"]}
    rec.update(seen.get("kept", {}).get("note", {}))
    _phase_line(rec)
    _require(all(c["ok"] for c in seen["checks"]),
             f"{argv[0]}: failed check {seen['checks']}")
    _require(seen["where"]["platform"] == ["tpu"],
             f"{argv[0]}: result on {seen['where']}")
    return seen.get("kept")


def _serve_problem(op: str, n: int, rng):
    """A well-conditioned f32 system: SPD for posv, a shifted Gaussian
    for gesv (pivoting still runs)."""
    g = rng.standard_normal((n, n)) / np.sqrt(n)
    a = g @ g.T + np.eye(n) if op == "posv" else g + 2.5 * np.eye(n)
    b = rng.standard_normal((n, 2))
    return a.astype(np.float32), b.astype(np.float32)


def serving_phase(sizes=SERVE_SIZES, nb: int = SERVE_NB,
                  seed: int = 3872) -> dict:
    """posv and gesv at every size through ``SolverService``; each
    answer must come from the primary batched rung, pass the normwise
    backward-error threshold and match a float64 NumPy solve."""
    from dplasma_tpu.serving import SolverService
    rng = np.random.default_rng(seed)
    eps = float(np.finfo(np.float32).eps)
    svc = SolverService(nb=nb)
    t0 = time.perf_counter()
    try:
        reqs = []
        for n in sizes:
            for op in ("posv", "gesv"):
                a, b = _serve_problem(op, n, rng)
                reqs.append((op, n, a, b, svc.submit(op, a, b)))
        results = [(op, n, a, b, fut.result(timeout=900), fut.meta)
                   for op, n, a, b, fut in reqs]
    finally:
        svc.close()
    wall = time.perf_counter() - t0
    residuals = {}
    for op, n, a, b, x, meta in results:
        key = f"{op} n={n}"
        _require(meta.get("batched") and "resilience" not in meta,
                 f"serving {key}: answered off the primary rung {meta}")
        a64, b64, x64 = (v.astype(np.float64) for v in (a, b, x))
        bwd = np.linalg.norm(a64 @ x64 - b64, np.inf) / (
            (np.linalg.norm(a64, np.inf) * np.linalg.norm(x64, np.inf)
             + np.linalg.norm(b64, np.inf)) * n * eps)
        ref = np.linalg.solve(a64, b64)
        fwd = np.abs(x64 - ref).max() / np.abs(ref).max()
        residuals[key] = {"backward": float(bwd), "vs_numpy": float(fwd)}
        _require(bwd < THRESHOLD and fwd < 1e-3,
                 f"serving {key}: backward {bwd} vs numpy {fwd}")
    where = _service_placement(svc)
    _require(where["platform"] == ["tpu"], f"serving: ran on {where}")
    return {"phase": f"SolverService posv/gesv n={list(sizes)} nb={nb}",
            "wall_s": wall, "compile_s": svc.cache.stats()["compile_s"],
            "requests": len(results), "residuals": residuals,
            "where": where}


def _service_placement(svc) -> dict:
    """Where the service's compiled executables run (its answers come
    back to the host as NumPy arrays by contract)."""
    import jax
    devs = set()
    for entry in svc.cache.entries():
        for sharding in jax.tree.leaves(entry.fn.input_shardings):
            devs |= sharding.device_set
    return {"platform": sorted({d.platform for d in devs}),
            "kind": sorted({d.device_kind for d in devs}),
            "devices": len(devs)}


def _quarters(x, what: str) -> None:
    """Every one of the 4 devices holds a distinct quarter of ``x``."""
    shards = x.addressable_shards
    _require(len({s.device for s in shards}) == 4,
             f"{what}: on {len({s.device for s in shards})} device(s)")
    _require(all(s.data.size * 4 == x.size for s in shards),
             f"{what}: shards {[s.data.shape for s in shards]} of "
             f"{x.shape} are not quarters")


def _factor(drv) -> dict:
    """The dense factor of a potrf (L) or LU (LU, perm) output, on the
    host, plus the permutation for LU."""
    out = drv.output
    if isinstance(out, tuple):
        return {"factor": np.asarray(out[0].to_dense()),
                "perm": np.asarray(out[1])}
    return {"factor": np.tril(np.asarray(out.to_dense())), "perm": None}


def _ring_calls(compiled) -> int:
    """Mosaic custom calls of the ICI ring kernels in an executable."""
    from dplasma_tpu.kernels.pallas_ring import RING_NAME_PREFIX
    return len(re.findall(
        r"%" + RING_NAME_PREFIX + r"[\w.]* = [^\n]*custom_call_target="
        r'"tpu_custom_call"', compiled.as_text()))


def _grid_keep(drv) -> dict:
    _quarters(drv.inputs[0].data, f"{drv.name} input")
    out = drv.output[0] if isinstance(drv.output, tuple) else drv.output
    _quarters(out.data, f"{drv.name} output")
    kept = _factor(drv)
    kept["a"] = np.asarray(drv.inputs[0].to_dense(), np.float64)
    if kept["perm"] is not None:
        # the block-cyclic LU must move its panels over the ring kernels
        rings = _ring_calls(drv.compiled)
        kept["note"] = {"ring_calls": rings}
        _require(rings > 0, f"{drv.name}: no ring kernel in the grid "
                 "program")
    return kept


def _lu_nopiv(a, nb: int = 512):
    """Packed L\\U of ``a`` without pivoting, in float64 on the host:
    the unique LU of a matrix whose rows are already in pivot order."""
    from scipy.linalg import solve_triangular
    a = a.copy()
    n = a.shape[0]
    for s in range(0, n, nb):
        e = min(s + nb, n)
        d = a[s:e, s:e]
        for j in range(e - s - 1):
            d[j + 1:, j] /= d[j, j]
            d[j + 1:, j + 1:] -= np.outer(d[j + 1:, j], d[j, j + 1:])
        if e < n:
            a[s:e, e:] = solve_triangular(d, a[s:e, e:], lower=True,
                                          unit_diagonal=True)
            a[e:, s:e] = solve_triangular(d, a[e:, s:e].T, trans="T").T
            a[e:, e:] -= a[e:, s:e] @ a[s:e, e:]
    return a


def grid_phase(argv) -> dict:
    """One op on the 2x2 grid: it passes ``-x``, holds a quarter
    of its input and output on every device, and its factor matches
    the float64 factor of the same input made on the host — Cholesky's
    L, or for LU the unique L\\U of the rows in the grid's own pivot
    order (the 2x2 LU elects pivots by tournament over the process
    rows, so its factor is not the one-chip partial-pivoting one)."""
    g = driver_phase(argv + ["-p", "2", "-q", "2"], keep=_grid_keep)
    n = int(argv[argv.index("-N") + 1])
    fg = g["factor"]
    if g["perm"] is None:
        ref = np.linalg.cholesky(g["a"])
    else:
        ref = _lu_nopiv(g["a"][g["perm"]])
    cmp = {"factor_maxdiff": float(np.abs(fg - ref).max()
                                   / np.abs(ref).max())}
    tol = THRESHOLD * n * float(np.finfo(fg.dtype).eps)
    _require(cmp["factor_maxdiff"] < tol,
             f"{argv[0]}: factor differs {cmp} (limit {tol})")
    return {"phase": f"{argv[0]} 2x2 vs float64 on the host",
            "compare": cmp}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2-grid path")
    ns = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(f"chip_smoke: no TPU (jax.devices()[0] is "
                         f"{dev.platform}); nothing was run\n")
        return 1
    if len(jax.devices()) < ns.chips:
        sys.stderr.write(f"chip_smoke: --chips {ns.chips} but JAX sees "
                         f"{len(jax.devices())} device(s)\n")
        return 1
    from dplasma_tpu.utils.config import use_compile_cache
    use_compile_cache()
    if ns.chips == 4:
        for argv_ in GRID:
            _phase_line(grid_phase(list(argv_)))
    else:
        for argv_ in ONE_CHIP:
            driver_phase(argv_)
        _phase_line(serving_phase())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
