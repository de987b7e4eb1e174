"""Pallas contract verification (analysis.palcheck).

Golden fixtures: every pallas_call site in the package captures and
verifies clean — without executing (or even lowering) a kernel, so
this runs on any backend, including ones where the kernels themselves
cannot (the test_pallas.py skip case). Mutation tests: each defect
class — VMEM-overflowing BlockSpec, non-covering or out-of-bounds
index map, non-dividing block, tiling-quantum violation, non-f32
accumulator, f64 outside the dd modules — is caught with a diagnostic
naming the site and the offending spec.
"""
import textwrap

import pytest

from dplasma_tpu.analysis import palcheck as pc


def _contract(site="dplasma_tpu/kernels/pallas_kernels.py:gemm",
              grid=(2, 2), ins=(), outs=(), scratch=()):
    return pc.PallasContract(site=site, grid=tuple(grid),
                             ins=list(ins), outs=list(outs),
                             scratch=list(scratch))


def _arg(name, shape, dtype="float32", block=None, imap=None):
    return pc.BlockArg(name, tuple(shape), dtype,
                       None if block is None else tuple(block), imap)


# ------------------------------------------------- golden clean sweep

def test_package_pallas_sites_verify_clean():
    """The full gate over the repo: every pallas_call site is found by
    the AST sweep, covered by the capture registry, and its captured
    contract passes every check."""
    res = pc.check_package()
    assert res.ok, res.format()
    assert res.sites_found == 7    # pallas_kernels, _lu (panel +
    #                              # nopiv tile), _qr, _dd,
    #                              # _ring (bcast + shift)
    if res.skipped is None:
        assert res.contracts == 8        # gemm epilogue + matmul +
        #                                # lu panel + nopiv tile +
        #                                # qr panel + dd recombine +
        #                                # ring bcast + ring shift


def test_every_site_is_registered():
    """A pallas_call site outside the registry is itself a diagnostic
    — new kernels cannot dodge the checker."""
    import pathlib
    pkg = pathlib.Path(pc.__file__).resolve().parents[1]
    sites = pc.find_call_sites(pkg)
    assert {rel for rel, _ in sites} == set(pc.SITES)


def test_unregistered_site_is_flagged(tmp_path):
    (tmp_path / "rogue.py").write_text(textwrap.dedent("""\
        from jax.experimental import pallas as pl

        def f(x):
            return pl.pallas_call(lambda i, o: None, out_shape=x)(x)
    """))
    res = pc.check_package(tmp_path)
    assert not res.ok
    (d,) = [d for d in res.diagnostics
            if d.kind == "unregistered-site"]
    assert "rogue.py" in d.message and "SITES" in d.message


def test_capture_records_real_gemm_contract():
    """The capture harness records the exact grid/BlockSpec surface of
    the fused GEMM without running it."""
    out = []
    pc._cap_pallas_kernels(out)
    assert len(out) == 2                 # epilogue + C-free variants
    epi, mm = out
    assert epi.grid == (2, 2, 2)
    assert len(epi.ins) == 3 and len(mm.ins) == 2
    assert epi.ins[0].block_shape == (8, 128)
    assert epi.scratch == [((8, 128), "float32")]
    # index maps came through callable: A block (i, k)
    assert epi.ins[0].index_map(1, 0, 1) == (1, 1)


# ------------------------------------------------------ mutation tests

def test_mutation_vmem_overflowing_blockspec():
    """A BlockSpec whose double-buffered blocks + scratch exceed the
    ~16 MiB VMEM ceiling is named with the per-buffer estimate."""
    c = _contract(
        site="dplasma_tpu/kernels/pallas_kernels.py:gemm",
        grid=(4,),
        ins=[_arg("in0", (8192, 1024), block=(2048, 1024),
                  imap=lambda i: (i, 0))],
        outs=[_arg("out0", (8192, 1024), block=(2048, 1024),
                   imap=lambda i: (i, 0))],
        scratch=[((2048, 1024), "float32")])
    res = pc.check_contract(c)
    assert not res.ok
    (d,) = [d for d in res.diagnostics if d.kind == "vmem-overflow"]
    assert d.site == c.site
    # 2 args x 2048*1024*4 double-buffered + 8 MiB scratch = 40 MiB
    assert d.detail["estimate"] == 40 * 1024 * 1024
    assert d.detail["budget"] == pc.VMEM_BYTES


def test_mutation_non_covering_index_map():
    """An index map that never visits an output block leaves tiles
    unwritten — the gap is enumerated and named."""
    c = _contract(
        grid=(4,),
        outs=[_arg("out0", (32, 128), block=(8, 128),
                   imap=lambda i: (i // 2, 0))])   # blocks 2,3 unhit
    res = pc.check_contract(c)
    assert not res.ok
    (d,) = [d for d in res.diagnostics if d.kind == "gap-index"]
    assert "never visits" in d.message
    assert [2, 0] in d.detail["missing"] and \
        [3, 0] in d.detail["missing"]


def test_mutation_out_of_bounds_index_map():
    c = _contract(
        grid=(2,),
        ins=[_arg("in0", (16, 128), block=(8, 128),
                  imap=lambda i: (i + 5, 0))])
    res = pc.check_contract(c)
    (d,) = [d for d in res.diagnostics if d.kind == "oob-index"]
    assert "outside" in d.message and d.detail["block_index"] == [5, 0]


def test_gap_check_applies_to_outputs_only():
    """Inputs may legitimately revisit/skip blocks (a reduction reads
    what it needs); only unwritten OUTPUT blocks are defects."""
    c = _contract(
        grid=(4,),
        ins=[_arg("in0", (32, 128), block=(8, 128),
                  imap=lambda i: (0, 0))],          # same block 4x
        outs=[_arg("out0", (32, 128), block=(8, 128),
                   imap=lambda i: (i, 0))])
    assert pc.check_contract(c).ok


def test_mutation_block_does_not_divide():
    c = _contract(
        grid=(2,),
        ins=[_arg("in0", (20, 128), block=(8, 128),
                  imap=lambda i: (i, 0))])
    res = pc.check_contract(c)
    assert any(d.kind == "block-divide" and "pad operands" in d.message
               for d in res.diagnostics)


def test_mutation_tiling_quantum_violation():
    """A 64-lane block on a 256-lane operand is neither full-extent
    nor a 128 multiple; a 12-sublane f32 block violates the 8-row
    quantum."""
    c = _contract(
        grid=(2, 2),
        ins=[_arg("in0", (64, 256), block=(8, 64),
                  imap=lambda i, j: (i, j))])
    res = pc.check_contract(c)
    (d,) = [d for d in res.diagnostics if d.kind == "tiling"]
    assert "lane quantum 128" in d.message
    c2 = _contract(
        grid=(2, 2),
        ins=[_arg("in0", (48, 128), block=(12, 128),
                  imap=lambda i, j: (i, j))])
    res2 = pc.check_contract(c2)
    (d2,) = [d for d in res2.diagnostics if d.kind == "tiling"]
    assert "sublane quantum 8" in d2.message


def test_full_extent_blocks_exempt_from_quanta():
    """Whole-dimension blocks (and spec-less whole-array operands) are
    legal at any size — the pallas_lu panel shape (M, nb=16)."""
    c = _contract(
        site="dplasma_tpu/kernels/pallas_lu.py:lu_panel",
        grid=(),
        ins=[_arg("in0", (128, 16))],        # no spec: whole array
        outs=[_arg("out0", (128, 16)), _arg("out1", (16,), "int32")])
    assert pc.check_contract(c).ok


def test_squeezed_none_dims_follow_pallas_semantics():
    """A None block_shape entry is a SQUEEZED dim (block size 1, one
    block per element, iterated by the index map) — not a full-extent
    block: the index map legitimately returns 1..s-1 there, and a map
    pinned to 0 genuinely gaps the output (review r6 finding)."""
    c = _contract(
        grid=(4,),
        outs=[_arg("out0", (4, 8, 128), block=(None, 8, 128),
                   imap=lambda i: (i, 0, 0))])
    assert pc.check_contract(c).ok          # visits all 4 slices
    c2 = _contract(
        grid=(4,),
        outs=[_arg("out0", (4, 8, 128), block=(None, 8, 128),
                   imap=lambda i: (0, 0, 0))])
    res = pc.check_contract(c2)
    (d,) = [d for d in res.diagnostics if d.kind == "gap-index"]
    assert [1, 0, 0] in d.detail["missing"]


def test_mutation_bf16_accumulator():
    """The MXU accumulate contract: VMEM scratch accumulators are f32;
    bf16 scratch silently halves the accumulate width."""
    c = _contract(scratch=[((8, 128), "bfloat16")], grid=(2,),
                  outs=[_arg("out0", (16, 128), block=(8, 128),
                             imap=lambda i: (i, 0))])
    res = pc.check_contract(c)
    (d,) = [d for d in res.diagnostics if d.kind == "precision"]
    assert "f32 scratch" in d.message


def test_mutation_f64_outside_dd_modules():
    c = _contract(site="dplasma_tpu/kernels/pallas_kernels.py:gemm",
                  ins=[_arg("in0", (8, 128), "float64")], grid=())
    res = pc.check_contract(c)
    (d,) = [d for d in res.diagnostics if d.kind == "f64-outside-dd"]
    assert "dd" in d.message
    # the config-guarded dd route is the one legal home for f64
    c2 = _contract(site="dplasma_tpu/kernels/pallas_dd.py:recombine",
                   ins=[_arg("in0", (8, 128), "float64")], grid=())
    assert pc.check_contract(c2).ok


def test_verify_contract_raises():
    c = _contract(grid=(0,))
    with pytest.raises(pc.PalCheckError, match="non-positive"):
        pc.verify_contract(c)
