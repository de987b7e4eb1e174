"""CLI driver harness (the reference's testing_* binaries + ctest
invocations, ref tests/Testings.cmake): run a few drivers in-process on
the CPU mesh with the reference's small odd sizes and -x checks."""
import numpy as np
import pytest

from dplasma_tpu.drivers import main


@pytest.mark.parametrize("prog,args", [
    # shm sizes mirror Testings.cmake's odd-size strategy (-N 378 -t 93)
    ("testing_dpotrf", ["-N", "117", "-t", "25", "-x"]),
    ("testing_sgemm", ["-N", "96", "-M", "80", "-K", "64", "-t", "32",
                       "-x"]),
    ("testing_dgeqrf", ["-N", "96", "-M", "96", "-t", "32", "-x"]),
    ("testing_dpotrf_dtd", ["-N", "96", "-t", "32", "-x"]),
    ("testing_dgemm_dtd", ["-N", "64", "-M", "64", "-K", "64", "-t",
                           "32", "-x"]),
    ("testing_dpivgen", ["-N", "128", "-t", "16", "-v"]),
    ("testing_dgetrf_1d", ["-N", "96", "-t", "32", "-x"]),
    ("testing_dhbrdt", ["-N", "64", "-t", "16", "-x"]),
    ("testing_dgebrd_ge2gb", ["-N", "64", "-M", "64", "-t", "16", "-x"]),
    ("testing_dunmqr_hqr", ["-N", "64", "-M", "64", "-t", "16"]),
    ("testing_dgeqrf_rd", ["-N", "64", "-M", "64", "-t", "16", "-x"]),
])
def test_driver_runs_clean(prog, args, capsys):
    rc = main(args, prog=prog)
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "TIME(s)" in out or "pivgen" in out
    assert "FAILED" not in out


@pytest.mark.slow
def test_driver_distributed_grid(capsys):
    rc = main(["-N", "128", "-t", "16", "-P", "2", "-Q", "4", "-x"],
              prog="testing_dpotrf")
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "PxQxg=   2 4" in out


def test_driver_dot_dump(tmp_path, capsys):
    dot = str(tmp_path / "dag.dot")
    rc = main(["-N", "64", "-t", "16", f"--dot={dot}", "-v"],
              prog="testing_dpotrf")
    capsys.readouterr()
    assert rc == 0
    text = open(dot).read()
    # default pipeline: the split-column engine DAG (panel/upd_col)
    assert "digraph" in text and "panel(0)" in text
    dot0 = dot + ".classic"
    rc = main(["-N", "64", "-t", "16", "--lookahead", "0",
               f"--dot={dot0}", "-v"], prog="testing_dpotrf")
    capsys.readouterr()
    assert rc == 0
    assert "potrf(0)" in open(dot0).read()


def test_driver_unknown_and_usage(capsys):
    assert main([], prog=None) == 2
    assert main(["-N", "8"], prog="testing_dnotanalgo") == 2


def test_driver_warmup_run_excluded(monkeypatch, capsys):
    """The warm run executes before the timed loop and is excluded
    from stats (ref testing_zpotrf.c:138-202 warmup); --nowarmup
    disables it."""
    import jax
    import jax.numpy as jnp

    from dplasma_tpu.drivers import common as dc

    for flag, expect in ((["--nowarmup"], 1), ([], 2)):
        ip = dc.parse_arguments(
            ["-N", "64", "-t", "16", "--nruns", "1"] + flag)
        drv = dc.Driver(ip, "warmup_probe")
        jfn = jax.jit(lambda x: x * 2.0)
        n0 = [0]
        orig = dc.Driver._sync

        def counting_sync(self, out):
            n0[0] += 1
            return orig(self, out)

        monkeypatch.setattr(dc.Driver, "_sync", counting_sync)
        drv.progress(jfn, (jnp.ones((64, 64), jnp.float32),),
                     flops=1.0)
        monkeypatch.undo()
        assert n0[0] == expect, (flag, n0[0])
        capsys.readouterr()


class TestParseArguments:
    """CLI vocabulary coverage (ref tests/common.c:73-259): clustered
    short flags, optional-value long flags, -v=n, MCA passthrough, and
    the observability flags."""

    def _parse(self, argv):
        from dplasma_tpu.drivers import common as dc
        return dc.parse_arguments(argv)

    def test_clustered_short_flags(self):
        ip = self._parse(["-N", "64", "-xX"])
        assert ip.check and ip.check_inv and not ip.sync
        ip = self._parse(["-N", "64", "-xb"])
        assert ip.check and ip.sync and not ip.check_inv

    def test_bad_cluster_rejected(self):
        with pytest.raises(SystemExit):
            self._parse(["-N", "64", "-xZ"])

    def test_dot_default_and_explicit(self):
        assert self._parse(["-N", "8"]).dot is None
        assert self._parse(["-N", "8", "--dot"]).dot == "dag.dot"
        assert self._parse(["-N", "8", "--dot=g.dot"]).dot == "g.dot"

    def test_verbosity_forms(self):
        assert self._parse(["-N", "8"]).loud == 1
        assert self._parse(["-N", "8", "-v"]).loud == 2
        assert self._parse(["-N", "8", "-v=3"]).loud == 3
        assert self._parse(["-N", "8", "--verbose=4"]).loud == 4

    def test_mca_passthrough(self):
        ip = self._parse(["-N", "8", "--", "--mca", "cyclic.convert",
                          "a2a"])
        assert ip.extra == ["--mca", "cyclic.convert", "a2a"]
        assert ip.N == 8

    def test_observability_flags(self):
        ip = self._parse(["-N", "8"])
        assert ip.profile is None and ip.report is None \
            and ip.jaxtrace is None
        ip = self._parse(["-N", "8", "--profile", "--report",
                          "--jaxtrace"])
        assert ip.profile == "run.prof"
        assert ip.report == "report.json"
        assert ip.jaxtrace == "jax_trace"
        ip = self._parse(["-N", "8", "--profile=a.prof",
                          "--report=b.json", "--jaxtrace=tr"])
        assert (ip.profile, ip.report, ip.jaxtrace) == \
            ("a.prof", "b.json", "tr")

    def test_telemetry_flag(self):
        assert self._parse(["-N", "8"]).telemetry is None
        assert self._parse(["-N", "8", "--telemetry"]).telemetry \
            == "telemetry.prom"
        assert self._parse(["-N", "8", "--telemetry=t.prom"]) \
            .telemetry == "t.prom"


def test_driver_per_run_stats_printed(capsys):
    """-v>=2 prints per-run lines and the min/median/max spread (the
    reference prints each run; best alone hides variance)."""
    rc = main(["-N", "64", "-t", "16", "--nruns", "3", "-v"],
              prog="testing_dpotrf")
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "#+ run 0:" in out and "#+ run 2:" in out
    assert "min/median/max" in out and "stddev" in out


def test_driver_dot_uses_global_recorder(tmp_path, capsys):
    """The --dot path records through the module-global recorder under
    profiling.recording(): no cross-run task accumulation, disabled
    again afterwards."""
    from dplasma_tpu.utils import profiling

    dot = str(tmp_path / "dag.dot")
    for _ in range(2):
        rc = main(["-N", "64", "-t", "16", f"--dot={dot}"],
                  prog="testing_dpotrf")
        assert rc == 0
        capsys.readouterr()
        # recorder was used, then left disabled; its contents are the
        # single run's pipelined DAG (4 panels + 3 narrow + 2 agg
        # updates -> 9 tasks), not an accumulation
        assert not profiling.recorder.enabled
        assert len(profiling.recorder.tasks) == 9


# ------------------------------------------- chip bring-up contracts

def test_compile_error_propagates_without_host_fallback():
    """A compile the default backend refuses is an error, never a
    quiet re-run on another device (here: a Pallas kernel forced out
    of interpret mode on the CPU)."""
    import jax.numpy as jnp

    from dplasma_tpu.drivers.common import run_driver
    from dplasma_tpu.kernels import pallas_kernels as pk
    x = jnp.ones((128, 128), jnp.float32)

    def body(drv):
        drv.progress(lambda a: pk.matmul(a, a, interpret=False), (x,),
                     1.0)
        return 0

    with pytest.raises(ValueError, match="interpret mode"):
        run_driver("testing_sgemm", body, ["-N", "128"])


def test_inspect_sees_the_timed_op_before_close():
    """``main(inspect=)``: the callback gets the driver with its -x
    checks, the op's timings and the timed op's operands/result."""
    seen = {}

    def inspect(drv):
        seen.update(checks=list(drv.report.checks),
                    timings=drv.report.ops[-1]["timings"],
                    out=drv.output, inputs=drv.inputs)

    assert main(["-N", "64", "-t", "32", "-x"], prog="testing_spotrf",
                inspect=inspect) == 0
    assert [c["ok"] for c in seen["checks"]] == [True, True]
    assert seen["timings"]["enq_s"] > 0
    assert seen["out"].data.shape == seen["inputs"][0].data.shape


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    import os

    import jax

    from dplasma_tpu.utils import config
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = config.use_compile_cache()
    assert path == config.REPO_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == path
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")


def test_driver_writes_cache_where_the_environment_says(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, a driver process caches its
    executables there."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    r = subprocess.run(
        [sys.executable, "-m", "dplasma_tpu.drivers", "testing_spotrf",
         "-N", "64", "-t", "32"], cwd=root, env=env,
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert any(cache.iterdir())
