#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and
prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; ``memory`` and ``setup_parts`` give the readings behind
``memory_peak_bytes`` and ``setup_s``; the numbers that decided
``correct`` come last, under
``compare``, and again as the last lines of standard error. Without a
TPU, or with fewer chips than the cell asks for, it prints no result
and exits 1.

JAX's persistent compilation cache is ``<checkout>/.jax_cache``, a fixed
path, so that only the first run of a cell in a checkout compiles. It
keeps every program however quickly it compiled, and evicts nothing: a
size limit set for the machine's own cache would make JAX read the
access times of entries this checkout never wrote.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def use_checkout_cache() -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``,
    keeping every program; the TPU runtime's logs under ``TMPDIR``.
    Call before anything touches a device."""
    cache = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    # the TPU runtime's logs go under TMPDIR, not to its fixed /tmp path
    os.environ["TPU_LOG_DIR"] = os.path.join(tempfile.gettempdir(),
                                             "tpu_logs")
    sys.path.insert(0, ROOT)
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ns = ap.parse_args(argv)

    use_checkout_cache()
    from benchmark import harness
    try:
        line = harness.run(ns.workload, ns.seed, ns.seconds, bool(ns.trace),
                           t_process=T_PROCESS)
    except harness.NoChip as e:
        sys.stderr.write(f"benchmark: {e}; nothing was run\n")
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
