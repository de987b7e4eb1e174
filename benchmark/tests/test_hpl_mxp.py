"""The HPL-MxP cell at a small size on four virtual CPU devices: the
solve reads correct; the control (the plain reference in f32 at
HIGHEST) and one altered entry of the answer read not correct; the
refinement readers find the scopes of a compiled program."""
import json

from benchmark import control
from benchmark.metrics import refine_hbm_pct
from benchmark.tests.helpers import SMALL, run_small
from benchmark.tests.test_faults import _altered

CELL = "hpl_mxp.closed"
SEEDS = [5, 2 ** 31 + 9]


def test_cell_reads_correct():
    line = run_small(CELL)
    assert line["correct"] is True and line["failed"] == 0, line["compare"]
    assert line["device"]["count"] == 4
    assert set(line["metrics"]) == {"gflops", "solve_ms_p95", "setup_s"}


def test_traced_line():
    """Traced on the CPU: the answer is judged as untraced; of the
    cell's per-layer metrics only the host spans' is read (the CPU has
    no device trace, so the refinement readers find nothing)."""
    line = run_small(CELL, trace=True)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"host_call_pct"}


def test_control_reads_not_correct():
    for seed in SEEDS:
        line = json.loads(json.dumps(control.run(
            CELL, seed, 0.3, sizes=SMALL, require_chip=False)))
        cmp = line["compare"]["backward_error"]
        assert line["correct"] is False and cmp["value"] > cmp["limit"]


def test_altered_answer_reads_not_correct():
    line = run_small(CELL, wrap=_altered)
    assert line["correct"] is False and line["failed"] >= 1


def test_op_bytes():
    """Bytes an operation moves in HBM: whole operands and result; a
    slice's result twice; an in-place update's update twice, in a
    fusion too; nothing of an array in on-chip memory (``S(1)``)."""
    text = "\n".join([
        "HloModule m",
        "%fused (p0: f32[64,8], p1: f32[8]) -> f32[64,8] {",
        "  %p0 = f32[64,8]{1,0} parameter(0)",
        "  %p1 = f32[8]{0} parameter(1)",
        "  %c = s32[] constant(0)",
        "  %b = f32[1,8]{1,0} reshape(f32[8]{0} %p1)",
        "  ROOT %u = f32[64,8]{1,0} dynamic-update-slice(f32[64,8]{1,0} "
        "%p0, f32[1,8]{1,0} %b, s32[] %c, s32[] %c)",
        "}",
        "ENTRY %main (a: f32[64,8], v: f32[8]) -> f32[64,8] {",
        "  %a = f32[64,8]{1,0} parameter(0)",
        "  %v = f32[8]{0} parameter(1)",
        "  %i = s32[] constant(3)",
        "  %d = f32[64]{0} add(f32[64]{0} %x, f32[64]{0} %x)",
        "  %s = f32[1,8]{1,0} dynamic-slice(f32[64,8]{1,0} %a, s32[] %i, "
        "s32[] %i), dynamic_slice_sizes={1,8}",
        "  %c = (f32[64,8]{1,0:S(1)}, f32[64,8]{1,0}, u32[]) "
        "copy-start(f32[64,8]{1,0} %a)",
        "  %e = f32[64,8]{1,0:S(1)} copy-done(%c)",
        "  %g = f32[64,8]{1,0} add(f32[64,8]{1,0:S(1)} %e, "
        "f32[64,8]{1,0} %a)",
        "  ROOT %f = f32[64,8]{1,0} fusion(f32[64,8]{1,0} %a, f32[8]{0} "
        "%v), kind=kLoop, calls=%fused",
        "}"])
    got = refine_hbm_pct.op_bytes([text])
    assert got["d"] == 3 * 64 * 4
    assert got["s"] == 2 * 8 * 4
    assert got["f"] == 2 * 8 * 4 + 8 * 4
    # a copy into on-chip memory reads HBM once; on-chip operands are free
    assert got["c"] == 64 * 8 * 4 and got["e"] == 0
    assert got["g"] == 2 * 64 * 8 * 4


def _trace():
    """Two ops on one device, each 1 ms, one under ``dplasma.residual``
    (8 MB of operand and result) and one under ``dplasma.factor``."""
    from benchmark import hlo, reduce
    text = "\n".join([
        "HloModule m",
        "ENTRY %main (a: f32[1024,1024], b: f32[1024,1024]) -> "
        "f32[1024,1024] {",
        "  %a = f32[1024,1024]{1,0} parameter(0)",
        "  %b = f32[1024,1024]{1,0} parameter(1)",
        "  %r = f32[1024,1024]{1,0} add(f32[1024,1024]{1,0} %a, "
        "f32[1024,1024]{1,0} %b), metadata={op_name=\"jit(f)/"
        "dplasma.residual/add\"}",
        "  ROOT %f = f32[1024,1024]{1,0} multiply(f32[1024,1024]{1,0} "
        "%r, f32[1024,1024]{1,0} %b), metadata={op_name=\"jit(f)/"
        "dplasma.factor/mul\"}",
        "}"])
    ms = 1_000_000
    raw = {"devices": {"/device:TPU:0": [("r", 1 * ms, 2 * ms),
                                         ("f", 2 * ms, 3 * ms)]},
           "modules": {},
           "spans": [("call", 0, 1), ("call", 1, 2), ("wait", 2, 4 * ms)]}
    return reduce.Trace(raw, hlo.index([text])), text


def test_refine_readers():
    from benchmark import spec
    t, text = _trace()
    c = spec.cell(spec.load_spec(), CELL)
    ctx = {"trace": t, "device_kind": "TPU v5 lite", "cell": c,
           "config": c.config, "hlo_texts": [text]}
    assert spec.reader(c, "refine_pct").read(ctx) == 50.0
    # 3 x 4 MB moved in 1 ms at 819 GB/s
    assert abs(spec.reader(c, "refine_hbm_pct").read(ctx)
               - 100.0 * 3 * 4 * 2 ** 20 / 819e9 / 1e-3) < 1e-9
    assert spec.reader(c, "refine_pct").read(
        dict(ctx, hlo_texts=[])) is None
