"""CLI-compatible test/benchmark drivers (the reference's tests/ binaries).

Run as ``python -m dplasma_tpu.drivers testing_dpotrf -N 378 -t 93 -x``
or via the ``bin/testing_*`` shims. The precision letter after
``testing_`` picks the dtype, mirroring the reference's
precision-generated driver binaries (ref tests/CMakeLists.txt:16-81).
"""
from dplasma_tpu.drivers.common import Driver, IParam, parse_arguments, \
    run_driver
from dplasma_tpu.drivers.testers import DRIVERS

__all__ = ["Driver", "IParam", "parse_arguments", "run_driver", "DRIVERS",
           "main"]


def main(argv=None, prog=None, inspect=None):
    import sys
    args = list(sys.argv[1:] if argv is None else argv)
    name = prog
    if name is None:
        if not args or args[0].startswith("-"):
            sys.stderr.write(
                "usage: python -m dplasma_tpu.drivers testing_<prec><algo> "
                "[options]\n  algos: " + " ".join(sorted(DRIVERS)) + "\n")
            return 2
        name = args.pop(0)
    base = name.rsplit("/", 1)[-1]
    algo = base
    if base.startswith("testing_"):
        from dplasma_tpu.drivers.common import PRECISIONS
        rest = base[8:]
        algo = rest[1:] if rest[:1] in PRECISIONS and rest[1:] else rest
    if algo not in DRIVERS:
        sys.stderr.write(f"unknown driver {base}; algos: "
                         + " ".join(sorted(DRIVERS)) + "\n")
        return 2
    return run_driver(base, DRIVERS[algo], args, inspect)
