"""Compiled-artifact verification (analysis.hlocheck).

Golden fixtures: the cyclic shard_map kernels' COMPILED post-GSPMD
HLO carries exactly the per-kind collective counts the jaxpr-level
schedule traced (4 ops x 1x1/2x2 grids, exact ``==`` reconciliation),
donations that were honored audit clean, and the end-to-end drivers
pass ``--hlocheck`` on the 8-device CPU mesh. Mutation tests: one per
check class — an injected surplus collective, a dropped donation, a
forced demoting convert, a shrunk HBM budget, a host callback, and a
copy-volume blowup — each caught with a diagnostic naming the
offending HLO op / buffer (the same style as tests/test_spmdcheck.py
one layer up).
"""
import json
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from dplasma_tpu.analysis import hlocheck as hc
from dplasma_tpu.analysis import spmdcheck as sp
from dplasma_tpu.descriptors import Dist
from dplasma_tpu.parallel import cyclic
from dplasma_tpu.parallel import mesh as pmesh

NB = 4
GRIDS = [(1, 1), (2, 2)]


def _kernel(op, P_, Q_, devices8, nt=4, la=1):
    m = pmesh.make_mesh(P_, Q_, devices8)
    desc = cyclic.CyclicDesc(nt * NB, nt * NB, NB, NB,
                             Dist(P=P_, Q=Q_))
    data = jnp.zeros((P_, Q_, desc.MTL * NB, desc.NTL * NB),
                     jnp.float32)
    KT = min(desc.MT, desc.NT)
    if op == "gemm":
        return (partial(cyclic._gemm_cyclic_jit, adesc=desc,
                        bdesc=desc, mesh=m), (data, data), desc.NT, 0)
    fn = {"potrf": cyclic._potrf_cyclic_jit,
          "getrf": cyclic._getrf_cyclic_jit,
          "geqrf": cyclic._geqrf_cyclic_jit}[op]
    return (partial(fn, desc=desc, mesh=m, lookahead=la), (data,),
            KT, la)


def _audit(op, P_, Q_, devices8, **kw):
    fn, args, KT, la = _kernel(op, P_, Q_, devices8)
    lowered = jax.jit(fn).lower(*args)
    compiled = lowered.compile()
    schedule = sp.extract_schedule(fn, *args, kernel=op)
    return hc.check_executable(
        lowered, compiled, f"{op}_{P_}x{Q_}", schedule=schedule,
        op=op, KT=KT, lookahead=la, prec="s", **kw), schedule


# ------------------------------------------------------- golden sweep

@pytest.mark.parametrize("grid", GRIDS)
@pytest.mark.parametrize("op", ["potrf", "getrf", "geqrf", "gemm"])
def test_golden_exact_reconciliation(op, grid, devices8):
    """The compiled module implements EXACTLY the collective schedule
    the jaxpr pinned — GSPMD neither inserted nor dropped — and every
    other check class is clean."""
    res, schedule = _audit(op, *grid, devices8)
    assert res.ok, res.summary()
    assert res.relation == "=="
    assert res.counts == hc.schedule_counts(schedule)
    assert sum(res.counts.values()) > 0
    assert res.hbm_peak_bytes is not None and res.hbm_peak_bytes > 0


def test_summary_round_trips(devices8):
    res, _ = _audit("potrf", 2, 2, devices8)
    doc = json.loads(json.dumps(res.summary()))
    assert doc["ok"] and doc["relation"] == "=="
    assert doc["counts"] == res.counts
    assert "OK" in res.format("potrf")


# ------------------------------------------------- donation (honored)

def test_donation_honored_audits_clean():
    """A donate_argnums the compiler honored shows as an
    input-output alias: requested == delivered, no diagnostic."""
    def f(a, b):
        return jax.lax.dynamic_update_slice(a, b, (0, 0))
    a = jnp.zeros((64, 64), jnp.float32)
    b = jnp.ones((8, 8), jnp.float32)
    lowered = jax.jit(f, donate_argnums=(0,)).lower(a, b)
    res = hc.check_executable(lowered, lowered.compile(), "donate-ok",
                              prec="s")
    assert res.ok, res.summary()
    assert res.donated == 1 and res.aliased == 1


def test_dd_cache_write_donation_is_delivered():
    """kernels/dd.py's donated limb-cache write — the site the audit
    exists for — actually produces aliasing in its compiled HLO."""
    from dplasma_tpu.kernels import dd
    W = jnp.zeros((2, 12, 16), jnp.float32)
    limbs = jnp.zeros((2, 4, 16), jnp.float32)
    lowered = dd._cache_write.lower(W, limbs, 0)
    res = hc.check_executable(lowered, lowered.compile(),
                              "dd._cache_write", prec="d")
    assert res.ok, res.summary()
    assert res.donated == 1 and res.aliased == 1


def test_donation_survives_pruned_arguments():
    """jax prunes unused arguments from the executable, renumbering
    the compiled parameters — an honored donation AFTER a pruned arg
    must still audit clean (regression: the audit previously numbered
    by flat argument index and reported a phantom drop)."""
    def f(unused, a, b):
        return jax.lax.dynamic_update_slice(a, b, (0, 0))
    a = jnp.zeros((32, 32), jnp.float32)
    b = jnp.ones((4, 4), jnp.float32)
    lowered = jax.jit(f, donate_argnums=(1,)).lower(a, a, b)
    compiled = lowered.compile()
    mod = hc.parse_module(compiled.as_text())
    assert mod.entry_params == 2          # arg 0 was pruned
    res = hc.check_executable(lowered, compiled, "pruned", prec="s")
    assert res.ok, res.summary()
    assert res.donated == 1 and res.aliased == 1
    # a donated-but-pruned argument carries no buffer: not a drop
    def g(unused_donated, x):
        return x * 2.0
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        lowered = jax.jit(g, donate_argnums=(0,)).lower(a, a)
        res = hc.check_executable(lowered, lowered.compile(),
                                  "pruned-donated", prec="s")
    assert res.ok, res.summary()


def test_map_to_compiled_params_fallbacks():
    """Without the executable's kept-index set: identity when the
    entry parameter count agrees, skip (no phantom diagnostics) when
    pruning provably happened but is unmappable."""
    reqs = [(0, True, 64), (1, False, 32)]
    mod = hc.HloModule(entry_params=2)
    assert hc.map_to_compiled_params(reqs, object(), mod) == reqs
    mod_pruned = hc.HloModule(entry_params=1)
    assert hc.map_to_compiled_params(reqs, object(), mod_pruned) == []


def test_gemm_model_leg_uses_contraction_tiles(devices8):
    """The SUMMA kernel runs ceil(K/NB) contraction steps — a
    rectangular gemm (K != N) must reconcile exactly with KT = K
    tiles (regression: min(M,N) tiles falsely demanded more)."""
    m = pmesh.make_mesh(2, 2, devices8)
    M = N = 4 * NB
    K = 2 * NB
    adesc = cyclic.CyclicDesc(M, K, NB, NB, Dist(P=2, Q=2))
    bdesc = cyclic.CyclicDesc(K, N, NB, NB, Dist(P=2, Q=2))
    da = jnp.zeros((2, 2, adesc.MTL * NB, adesc.NTL * NB), jnp.float32)
    db = jnp.zeros((2, 2, bdesc.MTL * NB, bdesc.NTL * NB), jnp.float32)
    fn = partial(cyclic._gemm_cyclic_jit, adesc=adesc, bdesc=bdesc,
                 mesh=m)
    lowered = jax.jit(fn).lower(da, db)
    schedule = sp.extract_schedule(fn, da, db, kernel="gemm_rect")
    res = hc.check_executable(lowered, lowered.compile(), "gemm_rect",
                              schedule=schedule, exact=True,
                              op="gemm", KT=adesc.NT, prec="s")
    assert res.ok and res.relation == "==", res.summary()
    # the wrong KT (min(M,N) tiles = 4 > 2 contraction tiles) demands
    # collectives the kernel never runs
    res2 = hc.check_executable(lowered, lowered.compile(),
                               "gemm_rect_bad", schedule=schedule,
                               exact=True, op="gemm",
                               KT=min(adesc.MT, bdesc.NT), prec="s")
    assert any(d.kind == "model-mismatch" for d in res2.diagnostics)


def test_model_op_kt_selection():
    """The driver's comm-model leg: gemm prices K tiles, the
    factorizations min(M,N) tiles, and the lumped BLAS3 ops
    (trsm/syrk/... share gemm's roofline class but not its
    collective structure) are excluded."""
    from dplasma_tpu.drivers.common import IParam, _model_op_kt
    ip = IParam(M=512, N=512, K=256, NB=64)
    assert _model_op_kt("gemm", ip) == ("gemm", 4)       # ceil(K/NB)
    assert _model_op_kt("potrf", ip) == ("potrf", 8)
    assert _model_op_kt("getrf_ptgpanel", ip) == ("getrf", 8)
    assert _model_op_kt("gels", ip) == ("geqrf", 8)
    assert _model_op_kt("trsm", ip) == (None, 0)
    assert _model_op_kt("syrk", ip) == (None, 0)
    assert _model_op_kt("lange", ip) == (None, 0)
    # solve-only / variant drivers share the roofline class but NOT
    # the priced kernel's collective structure — excluded
    assert _model_op_kt("potrs", ip) == (None, 0)
    assert _model_op_kt("potri", ip) == (None, 0)
    assert _model_op_kt("geqrf_hqr", ip) == (None, 0)
    assert _model_op_kt("getrf_incpiv", ip) == (None, 0)
    assert _model_op_kt("gemm_dtd", ip) == (None, 0)


# ------------------------------------------------------ mutation tests

def test_mutation_surplus_collective_named(devices8):
    """A collective the traced schedule does not account for — the
    GSPMD-inserted hidden resharding class — is a named failure."""
    res, schedule = _audit("potrf", 2, 2, devices8)
    mutated = {k: v - 1 if k == "all-gather" else v
               for k, v in hc.schedule_counts(schedule).items()}
    # replay the REAL compiled module against a schedule that pins one
    # fewer all-gather: the surplus must be caught and named
    fn, args, KT, la = _kernel("potrf", 2, 2, devices8)
    mod = hc.parse_module(jax.jit(fn).lower(*args).compile().as_text())
    res = hc.HloResult(kernel="potrf_mut")
    hc.check_collectives(mod, res, mutated, exact=True)
    assert not res.ok
    (d,) = [d for d in res.diagnostics
            if d.kind == "surplus-collective"]
    assert "all-gather" in d.message and "GSPMD inserted" in d.message
    assert d.op.startswith("all_gather")
    assert d.detail["compiled"] == d.detail["traced"] + 1


def test_mutation_dropped_collective_named(devices8):
    """The compiled module carrying FEWER collectives than the pinned
    schedule fails in both exact and dominating modes."""
    fn, args, KT, la = _kernel("potrf", 2, 2, devices8)
    mod = hc.parse_module(jax.jit(fn).lower(*args).compile().as_text())
    schedule = sp.extract_schedule(fn, *args, kernel="potrf")
    inflated = {k: v + 2 for k, v in
                hc.schedule_counts(schedule).items()}
    for exact in (True, False):
        res = hc.HloResult(kernel="potrf_drop")
        hc.check_collectives(mod, res, inflated, exact=exact)
        assert not res.ok
        assert any(d.kind == "missing-collective"
                   for d in res.diagnostics)


def test_dominating_allows_wrapping_collectives(devices8):
    """exact=False (driver programs): GSPMD conversion collectives
    AROUND the pinned schedule are legitimate — relation '>='."""
    fn, args, KT, la = _kernel("potrf", 2, 2, devices8)
    mod = hc.parse_module(jax.jit(fn).lower(*args).compile().as_text())
    schedule = sp.extract_schedule(fn, *args, kernel="potrf")
    shrunk = {k: v - 1 for k, v in
              hc.schedule_counts(schedule).items()}
    res = hc.HloResult(kernel="potrf_dom")
    hc.check_collectives(mod, res, shrunk, exact=False)
    assert res.ok and res.relation == ">="


def test_mutation_dropped_donation_named():
    """donate_argnums the compiler could not honor (dtype-changed
    output) is flagged with the parameter and its buffer size."""
    def g(a, b):
        return (a @ b)[:32].astype(jnp.bfloat16)
    a = jnp.zeros((64, 64), jnp.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        lowered = jax.jit(g, donate_argnums=(0,)).lower(a, a)
        compiled = lowered.compile()
    res = hc.check_executable(lowered, compiled, "donate-drop",
                              prec="d")
    assert not res.ok
    (d,) = [d for d in res.diagnostics if d.kind == "dropped-donation"]
    assert d.detail["param"] == 0
    assert d.detail["bytes"] == 64 * 64 * 4
    assert "16384 bytes" in d.message


def test_mutation_demoting_convert_named():
    """A float demotion below the working precision outside the
    registered dd/limb sites names the convert op and the types."""
    def f(a):
        return (a.astype(jnp.bfloat16).astype(jnp.float32)
                @ a.astype(jnp.float32))
    a = jnp.zeros((16, 16), jnp.float32)
    lowered = jax.jit(f).lower(a)
    res = hc.check_executable(lowered, lowered.compile(), "demote",
                              prec="s")
    assert not res.ok
    diags = [d for d in res.diagnostics
             if d.kind == "precision-demotion"]
    assert diags and "f32 -> bf16" in diags[0].message
    assert diags[0].op.startswith("convert")


def _frames(path: str, line: int) -> str:
    """The stack-frame tables a compiled module's metadata points at:
    one frame at ``path:line``."""
    return (f'\nFileNames\n1 "{path}"\n\nFunctionNames\n1 "f"\n\n'
            f'FileLocations\n1 {{file_name_id=1 function_name_id=1 '
            f'line={line} end_line={line} column=1 end_column=2}}\n\n'
            f'StackFrames\n1 {{file_location_id=1 parent_frame_id=1}}\n')


def test_demotion_allowed_at_registered_site():
    """The same demoting convert with a registered dd/limb
    source_file is the AUTHORIZED precision ladder — no diagnostic."""
    text = (
        'HloModule jit_x, entry_computation_layout='
        '{(f32[4,4]{1,0})->bf16[4,4]{1,0}}\n\n'
        'ENTRY %main (p0: f32[4,4]) -> bf16[4,4] {\n'
        '  %p0 = f32[4,4]{1,0} parameter(0)\n'
        '  %convert.1 = bf16[4,4]{1,0} convert(%p0), '
        'metadata={op_name="x" stack_frame_id=1}\n'
        '  ROOT %r = bf16[4,4]{1,0} copy(%convert.1)\n'
        '}\n' + _frames("/repo/dplasma_tpu/kernels/dd.py", 42))
    mod = hc.parse_module(text)
    res = hc.HloResult(kernel="dd-site")
    hc.check_precision(mod, res, working_bits=32)
    assert res.ok, res.summary()
    # the identical convert at an unregistered site fails
    mod2 = hc.parse_module(text.replace("kernels/dd.py",
                                        "ops/lu.py"))
    res2 = hc.HloResult(kernel="bad-site")
    hc.check_precision(mod2, res2, working_bits=32)
    assert not res2.ok
    assert res2.diagnostics[0].detail["source"].endswith("ops/lu.py")


def test_declared_demotion_quantizer_site_both_directions():
    """Float->INTEGER narrowing is held to DECLARED_DEMOTIONS, not
    PRECISION_SITES: the block-scaled quantizer's f32 -> s8 store
    (kernels/quant.py) passes, while the SAME convert from any
    undeclared site — or a different triple at the declared site —
    still fails the audit."""
    text = (
        'HloModule jit_q, entry_computation_layout='
        '{(f32[4,4]{1,0})->s8[4,4]{1,0}}\n\n'
        'ENTRY %main (p0: f32[4,4]) -> s8[4,4] {\n'
        '  %p0 = f32[4,4]{1,0} parameter(0)\n'
        '  %convert.1 = s8[4,4]{1,0} convert(%p0), '
        'metadata={op_name="q" stack_frame_id=1}\n'
        '  ROOT %r = s8[4,4]{1,0} copy(%convert.1)\n'
        '}\n' + _frames("/repo/dplasma_tpu/kernels/quant.py", 77))
    assert ("kernels/quant.py", "f32", "s8") in hc.DECLARED_DEMOTIONS
    mod = hc.parse_module(text)
    res = hc.HloResult(kernel="quant-site")
    hc.check_precision(mod, res, working_bits=32)
    assert res.ok, res.summary()
    # the identical quantize at an UNDECLARED site fails — even a
    # registered PRECISION_SITES member does not cover f32 -> s8
    mod2 = hc.parse_module(text.replace("kernels/quant.py",
                                        "kernels/dd.py"))
    res2 = hc.HloResult(kernel="undeclared-site")
    hc.check_precision(mod2, res2, working_bits=32)
    assert not res2.ok
    d = res2.diagnostics[0]
    assert d.kind == "precision-demotion"
    assert "DECLARED_DEMOTIONS" in d.message
    assert d.detail["src"] == "f32" and d.detail["dst"] == "s8"
    # a DIFFERENT triple at the declared site fails too: the
    # allowlist is exact (site, src, dst), not per-file
    mod3 = hc.parse_module(
        text.replace("f32[4,4]", "f64[4,4]").replace(
            "(p0: f32", "(p0: f64"))
    res3 = hc.HloResult(kernel="wrong-triple")
    hc.check_precision(mod3, res3, working_bits=64)
    assert not res3.ok
    assert res3.diagnostics[0].detail["src"] == "f64"


def test_mutation_shrunk_hbm_budget_names_worst_buffer(devices8):
    """Peak bytes over hlocheck.hbm_budget fails naming the largest
    temp buffer in the module."""
    res, _ = _audit("potrf", 2, 2, devices8, hbm_budget=1)
    assert not res.ok
    (d,) = [d for d in res.diagnostics if d.kind == "hbm-budget"]
    assert "worst temp buffer" in d.message
    assert d.detail["budget"] == 1
    assert d.detail["peak_bytes"] > 1
    assert d.detail["worst_op"] and d.detail["worst_bytes"] > 0


def test_mutation_host_callback_named():
    """infeed/outfeed and callback custom-calls are hot-path
    poison — named with the op and target."""
    text = (
        'HloModule jit_cb\n\n'
        'ENTRY %main (p0: f32[4]) -> f32[4] {\n'
        '  %p0 = f32[4]{0} parameter(0)\n'
        '  %cc.1 = f32[4]{0} custom-call(f32[4]{0} %p0), '
        'custom_call_target="xla_ffi_python_cpu_callback"\n'
        '  %if.2 = (f32[4]{0}, token[]) infeed(token[] %tok)\n'
        '  ROOT %r = f32[4]{0} copy(f32[4]{0} %cc.1)\n'
        '}\n')
    mod = hc.parse_module(text)
    res = hc.HloResult(kernel="cb")
    hc.check_antipatterns(mod, res, copy_frac=1.0)
    kinds = [d.kind for d in res.diagnostics]
    assert kinds.count("host-callback") == 2
    msgs = " ".join(d.message for d in res.diagnostics)
    assert "xla_ffi_python_cpu_callback" in msgs
    assert "infeed" in msgs
    # vendor math custom-calls (lapack/blas) are NOT callbacks
    ok_text = text.replace("xla_ffi_python_cpu_callback",
                           "lapack_spotrf_ffi")
    ok_text = "\n".join(line for line in ok_text.splitlines()
                        if "infeed" not in line)
    res2 = hc.HloResult(kernel="ok")
    hc.check_antipatterns(hc.parse_module(ok_text), res2,
                          copy_frac=1.0)
    assert res2.ok


def test_mutation_copy_volume_named(devices8):
    """copy/transpose bytes above the knob fraction name the biggest
    copy op."""
    res, _ = _audit("potrf", 2, 2, devices8, copy_frac=0.001)
    assert not res.ok
    (d,) = [d for d in res.diagnostics if d.kind == "copy-volume"]
    assert "biggest" in d.message and d.detail["biggest_op"]
    assert d.detail["copy_bytes"] > 0
    # the default knob passes the same module clean
    res2, _ = _audit("potrf", 2, 2, devices8)
    assert res2.ok


# -------------------------------------------------- parsing edge cases

def test_parse_module_header_and_tuples():
    text = (
        "HloModule jit_t, is_scheduled=true, input_output_alias="
        "{ {}: (0, {}, may-alias), {1}: (2, {}, must-alias) }, "
        "entry_computation_layout={(f32[8]{0})->f32[8]{0}}, "
        "num_partitions=4\n\n"
        "ENTRY %main (p0: f32[8]) -> (f32[8], s32[2,2]) {\n"
        "  %p0 = f32[8]{0} parameter(0)\n"
        "  %t.1 = (f32[8]{0}, s32[2,2]{1,0}) tuple(f32[8]{0} %p0)\n"
        "  ROOT %r = (f32[8]{0}, s32[2,2]{1,0}) copy(%t.1)\n"
        "}\n")
    mod = hc.parse_module(text)
    assert mod.num_partitions == 4
    assert mod.aliased_params == {"": 0, "1": 2}
    tup = next(o for o in mod.ops if o.opcode == "tuple")
    assert tup.bytes == 8 * 4 + 4 * 4 and tup.dtype == ""
    par = next(o for o in mod.ops if o.opcode == "parameter")
    assert par.bytes == 32 and par.dtype == "f32"
    assert par.shape == (8,)


def test_shape_bytes():
    assert hc.shape_bytes("f32[64,64]{1,0}") == ("f32", (64, 64),
                                                 64 * 64 * 4)
    assert hc.shape_bytes("bf16[8]{0}") == ("bf16", (8,), 16)
    assert hc.shape_bytes("f64[]") == ("f64", (), 8)
    assert hc.shape_bytes("(f32[4]{0}, s32[])")[2] == 16 + 4
    assert hc.shape_bytes("token[]") == ("", (), 0)


def test_verify_executable_raises():
    def f(a):
        return a.astype(jnp.bfloat16)
    lowered = jax.jit(f).lower(jnp.zeros((8, 8), jnp.float32))
    with pytest.raises(hc.HloCheckError) as ei:
        hc.verify_executable(lowered, lowered.compile(), "raise",
                             prec="s")
    assert "precision" in str(ei.value)


# --------------------------------------------- integration touchpoints

@pytest.mark.parametrize("prog", ["testing_dpotrf", "testing_dgetrf",
                                  "testing_dgeqrf", "testing_dgemm"])
def test_driver_hlocheck_end_to_end(prog, tmp_path, capsys, devices8):
    """--hlocheck audits the exact executable before the timed loop
    on the 8-device CPU mesh and lands in the schema-v10 run-report;
    the GSPMD-partitioned drivers pass clean."""
    from dplasma_tpu.drivers import main
    rj = str(tmp_path / "r.json")
    rc = main(["-N", "64", "-t", "16", "-p", "2", "-q", "2",
               "--hlocheck", f"--report={rj}", "-v=2"], prog=prog)
    out = capsys.readouterr().out
    assert rc == 0
    assert f"hlocheck[{prog}]" in out and "OK" in out
    doc = json.load(open(rj))
    assert doc["schema"] == 18
    (entry,) = doc["hlocheck"]
    assert entry["ok"] and entry["op"] == prog
    assert entry["relation"] in ("gspmd", "==", ">=",
                                 "no-collectives")
    assert entry["diagnostics"] == []
    assert entry["hbm_peak_bytes"] > 0
    assert any(m["name"] == "hlocheck_hbm_peak_bytes"
               for m in doc["metrics"])
    assert any(m["name"] == "hlocheck_collectives_total"
               for m in doc["metrics"])


def test_driver_hlocheck_ptgpanel_dominates(tmp_path, capsys,
                                            devices8):
    """The driver that really runs the cyclic kernel
    (getrf_ptgpanel): the pinned shard_map schedule must be fully
    implemented (relation >=), GSPMD's wrapping collectives
    allowed. Runs --spmdcheck too: hlocheck reuses its schedule
    instead of re-tracing, and both report sections land."""
    from dplasma_tpu.drivers import main
    rj = str(tmp_path / "r.json")
    rc = main(["-N", "64", "-t", "16", "-p", "2", "-q", "2",
               "--spmdcheck", "--hlocheck", f"--report={rj}"],
              prog="testing_dgetrf_ptgpanel")
    assert rc == 0
    doc = json.load(open(rj))
    (entry,) = doc["hlocheck"]
    assert entry["ok"] and entry["relation"] == ">="
    assert entry["expected"]  # the pinned cyclic schedule
    for kind, n in entry["expected"].items():
        assert entry["counts"].get(kind, 0) >= n
    (sentry,) = doc["spmdcheck"]
    assert sentry["ok"]


def test_driver_hlocheck_budget_violation_aborts(tmp_path, capsys,
                                                 devices8):
    """A shrunk hlocheck.hbm_budget aborts the run before the timed
    loop, naming the worst buffer."""
    from tests.conftest import mca_overrides

    from dplasma_tpu.drivers import main
    with mca_overrides({"hlocheck.hbm_budget": "1"}):
        with pytest.raises(hc.HloCheckError) as ei:
            main(["-N", "64", "-t", "16", "-p", "2", "-q", "2",
                  "--hlocheck"], prog="testing_dpotrf")
    assert "worst temp buffer" in str(ei.value)


def test_driver_hlocheck_audits_fallback_executables(tmp_path,
                                                     capsys):
    """The audit contract covers EVERY executable the timed loop
    runs: a remediation-ladder rung that recompiles after a runtime
    fault gets its own audit entry (regression: only the first
    compiled artifact was audited)."""
    from dplasma_tpu.drivers import main
    rj = str(tmp_path / "r.json")
    # nan@potrf:1 corrupts the primary trace; the ladder retries with
    # injection suppressed — a SECOND compiled executable runs
    rc = main(["-N", "48", "-t", "16", "--hlocheck",
               "--inject=nan@potrf:1", "--max-retries", "1",
               f"--report={rj}"], prog="testing_spotrf")
    assert rc == 0
    doc = json.load(open(rj))
    (resil,) = doc["resilience"]
    assert resil["outcome"] == "remediated"
    retraced = [a for a in resil["attempts"][1:]]
    assert retraced, "expected a ladder rung past the primary"
    entries = doc["hlocheck"]
    assert len(entries) >= 2, entries   # primary + the retry's artifact
    assert all(e["ok"] for e in entries)


def test_driver_hlocheck_flag_parses():
    from dplasma_tpu.drivers.common import parse_arguments
    ip = parse_arguments(["-N", "64", "--hlocheck"])
    assert ip.hlocheck
    ip = parse_arguments(["-N", "64"])
    assert not ip.hlocheck


def test_serving_cache_entry_carries_audit():
    """The executable cache audits every admitted artifact (MCA
    hlocheck.serving): the entry carries the summary, hits don't
    re-audit, and 'off' disables."""
    import numpy as np

    from tests.conftest import mca_overrides

    from dplasma_tpu.serving import batched, cache as scache

    rng = np.random.default_rng(3872)
    n, nb, nrhs = 6, 4, 2
    g = rng.standard_normal((2, n, n)).astype(np.float32)
    spd = g @ g.transpose(0, 2, 1) + n * np.eye(n, dtype=np.float32)
    b = rng.standard_normal((2, n, nrhs)).astype(np.float32)

    def build():
        def fn(a, bb):
            x, _ = batched.solve_batched("posv", a, bb, nb)
            return x
        return fn

    c = scache.ExecutableCache(capacity=4)
    key = scache.make_key("posv", n, np.float32, 2, nrhs)
    e = c.get(key, build, jnp.asarray(spd), jnp.asarray(b))
    assert e.hlocheck is not None and e.hlocheck["ok"]
    e2 = c.get(key, build, jnp.asarray(spd), jnp.asarray(b))
    assert e2 is e
    m = c.metrics.get("serving_hlocheck_audits_total")
    assert m is not None and m.value == 1
    with mca_overrides({"hlocheck.serving": "off"}):
        c2 = scache.ExecutableCache(capacity=4)
        e3 = c2.get(key, build, jnp.asarray(spd), jnp.asarray(b))
        assert e3.hlocheck is None


# ----------------------------------------------------- perfdiff gating

def test_perfdiff_gates_hbm_peak_bytes(tmp_path):
    """hlocheck.hbm_peak_bytes is a lower-better perfdiff metric: a
    grown peak regresses, per-metric thresholds apply."""
    import sys as _sys
    _sys.path.insert(0, "tools")
    import perfdiff

    base = {"schema": 10, "ops": [], "metrics": [],
            "hlocheck": [{"op": "testing_dpotrf", "ok": True,
                          "hbm_peak_bytes": 1000}]}
    worse = {"schema": 10, "ops": [], "metrics": [],
             "hlocheck": [{"op": "testing_dpotrf", "ok": True,
                           "hbm_peak_bytes": 1500}]}
    m = perfdiff.extract_metrics(base)
    assert m["testing_dpotrf.hlocheck.hbm_peak_bytes"] == {
        "value": 1000.0, "better": "lower"}
    res = perfdiff.compare(base, worse)
    assert not res["ok"]
    assert res["worst"]["metric"] == \
        "testing_dpotrf.hlocheck.hbm_peak_bytes"
    # a generous per-metric threshold admits the same growth
    res2 = perfdiff.compare(base, worse,
                            per_metric={"hbm_peak_bytes": 0.6})
    assert res2["ok"]
    # shrinking the peak is an improvement, not a regression
    res3 = perfdiff.compare(worse, base)
    assert res3["ok"]


# ----------------------------------------------- xla error round-trip

def test_xla_capture_records_structured_errors():
    """A raising cost/memory analysis records {"error": reason} in
    the xla section instead of a silent null — and round-trips
    through JSON."""
    from dplasma_tpu.observability.xla import capture_compiled

    class _Boom:
        def cost_analysis(self):
            raise RuntimeError("cost backend down")

        def memory_analysis(self):
            raise NotImplementedError("no memory stats")

    out = capture_compiled(_Boom())
    assert out["cost"] == {"error": repr(RuntimeError(
        "cost backend down"))}
    assert out["memory"] == {"error": repr(NotImplementedError(
        "no memory stats"))}
    assert out["flops"] is None and out["peak_bytes"] is None
    back = json.loads(json.dumps(out))
    assert back["cost"]["error"].startswith("RuntimeError")
    assert back["memory"]["error"].startswith("NotImplementedError")

    class _Silent:
        def cost_analysis(self):
            return None

        def memory_analysis(self):
            return None

    out2 = capture_compiled(_Silent())
    assert out2["cost"] is None and out2["memory"] is None


# --------------------------------------- explicit ICI ring kernels

def _ring_hlo(n_ring=4, n_permute=0):
    lines = ["HloModule jit_ring, num_partitions=4\n",
             "ENTRY %main (p0: f32[8,128]) -> f32[8,128] {\n",
             "  %p0 = f32[8,128]{1,0} parameter(0)\n"]
    for i in range(n_ring):
        lines.append(
            f"  %cc.{i} = f32[8,128]{{1,0}} custom-call(%p0), "
            f'custom_call_target="tpu_custom_call", '
            f'metadata={{op_name="dplasma_ring_bcast_q.{i}"}}\n')
    for i in range(n_permute):
        lines.append(
            f"  %cp.{i} = f32[8,128]{{1,0}} "
            f"collective-permute(%p0), "
            f"source_target_pairs={{{{0,1}},{{1,2}},{{2,3}},{{3,0}}}}"
            f"\n")
    lines.append("  ROOT %r = f32[8,128]{1,0} copy(%p0)\n}\n")
    return "".join(lines)


def test_ring_custom_calls_counted_as_ring_dma():
    """Mosaic-lowered ring kernels (custom-calls carrying the
    dplasma_ring_ marker) count as the "ring-dma" collective kind —
    wire traffic the reconciliation must see, not anonymous
    custom-calls."""
    mod = hc.parse_module(_ring_hlo(n_ring=3, n_permute=2))
    assert mod.collective_counts == {"ring-dma": 3,
                                     "collective-permute": 2}


def test_ring_schedule_reconciles_against_compiled_counts():
    """A jaxpr schedule carrying ring_bcast/ring_shift collectives
    reconciles exactly against a compiled module's ring-dma count;
    a dropped ring kernel is a missing-collective diagnostic."""
    mod = hc.parse_module(_ring_hlo(n_ring=4))
    sched = sp.SpmdResult(kernel="ring")
    sched.collectives.append(sp.Collective("ring_bcast", ("q",), 4))
    res = hc.HloResult(kernel="ring")
    hc.check_collectives(mod, res, hc.schedule_counts(sched),
                         exact=True)
    assert res.ok and res.relation == "=="
    # mutation: compiled module lost one ring kernel
    mod2 = hc.parse_module(_ring_hlo(n_ring=3))
    res2 = hc.HloResult(kernel="ring")
    hc.check_collectives(mod2, res2, hc.schedule_counts(sched),
                         exact=True)
    assert not res2.ok
    assert any(d.kind == "missing-collective"
               and d.detail["kind"] == "ring-dma"
               for d in res2.diagnostics)


def test_ring_model_counts_price_ring_classes():
    """model_counts with ring=True collapses the ring count table
    onto the ring-dma kind at the right multiplicities (bcast: KT;
    LU exchange: KT*(P-1))."""
    mc = hc.model_counts("getrf", 4, ring=True, grid=(2, 2))
    assert mc["ring-dma"] == 4 + 4 * (2 - 1)
    assert mc["all-gather"] == 8
    mc_off = hc.model_counts("getrf", 4)
    assert "ring-dma" not in mc_off
