"""Pallas kernel contract checker: validate every ``pl.pallas_call``.

The Pallas surface fails in ways XLA never tells you about nicely: a
BlockSpec that doesn't divide the operand silently reads garbage pad,
an index map that skips a grid block leaves output tiles unwritten, a
VMEM over-budget kernel dies in Mosaic with an opaque allocation
error, and a bf16 accumulator quietly loses the MXU's f32 accumulate.
This module checks those contracts *statically*, on CPU, before a
kernel ever lowers:

* **capture** — every package entry point that issues a
  ``pl.pallas_call`` is registered in :data:`SITES`; the checker
  invokes it eagerly on tiny shapes with ``pl.pallas_call`` replaced
  by a recorder, so the exact (grid, BlockSpecs, out_shape, scratch)
  contract is captured without executing (or even lowering) the
  kernel body;
* **block shapes** — each block divides its (padded) operand and
  obeys the (sublane, lane) tiling quanta — last dim a multiple of
  128 and second-minor a multiple of 8 (f32/i32) / 16 (bf16) / 32
  (i8), full-dimension blocks exempt (Mosaic handles whole-array
  edges);
* **index maps** — enumerated over the full grid (the captured grids
  are small by construction): every returned block index must be in
  range, and the union of visited *output* blocks must cover every
  output block — no out-of-bounds, no gap;
* **VMEM budget** — the resident estimate (in/out blocks with the
  pipeline's double buffering, plus scratch) must fit the ~16 MiB
  VMEM ceiling;
* **precision** — floating VMEM scratch accumulators must be f32 (the
  MXU accumulate contract), and f64 anywhere in a contract is only
  legal under ``kernels/{dd,pallas_dd}`` (the config-guarded
  float-float route — the jaxlint J005 companion at the call level);
* **site registry** — an AST sweep finds every ``pallas_call`` call
  site in the package; a site no registered entry point exercises is
  itself a diagnostic, so a new kernel file cannot dodge the checker.

Runs on CPU with no TPU (and degrades to the AST sweep alone when
pallas cannot even import). Wired into ``tools/lint_all.py`` and
enforced from tier-1 via ``tests/test_lint.py``.

Usage: ``python -m dplasma_tpu.analysis.palcheck`` — prints one line
per diagnostic, exits nonzero on any.
"""
from __future__ import annotations

import ast
import contextlib
import pathlib
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

#: the VMEM ceiling the budget estimate is checked against (v4/v5e
#: class parts carry 16 MiB per core; the estimate must fit it whole)
VMEM_BYTES = 16 * 1024 * 1024

#: index-map enumeration guard (captured grids are tiny; anything past
#: this is a mis-captured contract, reported instead of enumerated)
_GRID_ENUM_CAP = 65536

#: modules whose contracts may carry f64 (the config-guarded dd route)
F64_SITES = ("dplasma_tpu/kernels/dd.py",
             "dplasma_tpu/kernels/pallas_dd.py")


class PalCheckError(ValueError):
    """A pallas_call contract failed static verification."""

    def __init__(self, result: "PalResult"):
        self.result = result
        lines = [d.message for d in result.diagnostics[:8]]
        more = len(result.diagnostics) - len(lines)
        if more > 0:
            lines.append(f"... and {more} more")
        super().__init__("Pallas contract verification failed:\n  " +
                         "\n  ".join(lines))


@dataclass(frozen=True)
class PalDiagnostic:
    kind: str        # block-divide|tiling|oob-index|gap-index|
    #                # vmem-overflow|precision|f64-outside-dd|
    #                # bad-grid|unregistered-site|capture-failed
    message: str
    site: str = ""
    detail: Optional[dict] = None

    def as_dict(self) -> dict:
        return {"kind": self.kind, "message": self.message,
                "site": self.site, "detail": self.detail}


@dataclass(frozen=True)
class BlockArg:
    """One operand/output of a captured pallas_call."""

    name: str                          # in0/in1/../out0/..
    shape: Tuple[int, ...]
    dtype: str
    block_shape: Optional[Tuple[int, ...]]   # None = whole array
    index_map: Optional[object] = None


@dataclass
class PallasContract:
    """The statically checkable surface of one pallas_call invocation."""

    site: str                          # "relpath:function"
    grid: Tuple[int, ...]
    ins: List[BlockArg] = field(default_factory=list)
    outs: List[BlockArg] = field(default_factory=list)
    scratch: List[Tuple[Tuple[int, ...], str]] = field(
        default_factory=list)


@dataclass
class PalResult:
    """Outcome of a palcheck run (JSON-able via summary())."""

    ok: bool = True
    sites_found: int = 0
    contracts: int = 0
    skipped: Optional[str] = None
    diagnostics: List[PalDiagnostic] = field(default_factory=list)

    def add(self, kind: str, message: str, site: str = "",
            detail=None) -> None:
        self.ok = False
        self.diagnostics.append(
            PalDiagnostic(kind, message, site, detail))

    def summary(self) -> dict:
        return {"ok": self.ok, "sites_found": self.sites_found,
                "contracts": self.contracts, "skipped": self.skipped,
                "diagnostics": [d.as_dict() for d in self.diagnostics]}

    def format(self, label: str = "palcheck") -> str:
        head = f"#+ {label}: "
        if self.ok:
            note = f" ({self.skipped})" if self.skipped else ""
            return (head + f"OK ({self.contracts} contract(s) over "
                    f"{self.sites_found} pallas_call site(s){note})")
        lines = [head + f"{len(self.diagnostics)} violation(s)"]
        lines += [f"#!   [{d.site}] {d.kind}: {d.message}"
                  for d in self.diagnostics]
        return "\n".join(lines)


# ---------------------------------------------------------------------
# Capture: record pallas_call contracts without running kernels
# ---------------------------------------------------------------------

def _dtype_name(d) -> str:
    """'float32' for dtype instances, dtype classes, and strings."""
    import numpy as np
    try:
        return np.dtype(d).name
    except TypeError:
        return str(d)


def _norm_grid(grid) -> Tuple[int, ...]:
    if grid is None:
        return ()
    if isinstance(grid, int):
        return (grid,)
    return tuple(int(g) for g in grid)


def _spec_fields(spec):
    """(block_shape, index_map) of one BlockSpec-ish entry (None spec
    = whole-array block)."""
    if spec is None:
        return None, None
    return (tuple(spec.block_shape) if spec.block_shape is not None
            else None), spec.index_map


def _flat_specs(specs, n: int) -> list:
    if specs is None:
        return [None] * n
    if not isinstance(specs, (list, tuple)):
        specs = [specs]
    return list(specs) + [None] * (n - len(specs))


@contextlib.contextmanager
def capture(site: str, out: List[PallasContract]):
    """Within the context, ``pl.pallas_call`` records its contract into
    ``out`` and returns zeros of ``out_shape`` instead of running —
    kernels are never executed, so capture works even where the
    kernel body itself could not lower (the point of a static gate)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    orig_call = pl.pallas_call

    def recorder(kernel, out_shape=None, **kw):
        grid = _norm_grid(kw.get("grid"))
        out_leaves = jax.tree_util.tree_leaves(
            out_shape, is_leaf=lambda x: hasattr(x, "shape"))
        o_specs = _flat_specs(kw.get("out_specs"), len(out_leaves))
        scratch = []
        for s in kw.get("scratch_shapes") or ():
            scratch.append((tuple(getattr(s, "shape", ())),
                            _dtype_name(getattr(s, "dtype", ""))))

        def run(*operands):
            i_specs = _flat_specs(kw.get("in_specs"), len(operands))
            c = PallasContract(site=site, grid=grid, scratch=scratch)
            for i, (op, spec) in enumerate(zip(operands, i_specs)):
                bs, im = _spec_fields(spec)
                c.ins.append(BlockArg(f"in{i}", tuple(op.shape),
                                      _dtype_name(op.dtype), bs, im))
            for i, (o, spec) in enumerate(zip(out_leaves, o_specs)):
                bs, im = _spec_fields(spec)
                c.outs.append(BlockArg(f"out{i}", tuple(o.shape),
                                       _dtype_name(o.dtype), bs, im))
            out.append(c)
            zeros = [jnp.zeros(o.shape, o.dtype) for o in out_leaves]
            if isinstance(out_shape, (list, tuple)):
                return type(out_shape)(zeros)
            return zeros[0]

        return run

    pl.pallas_call = recorder
    try:
        yield
    finally:
        pl.pallas_call = orig_call


# ---------------------------------------------------------------------
# Contract checks
# ---------------------------------------------------------------------

def _sublane(dtype: str) -> int:
    if "bfloat16" in dtype or "float16" in dtype:
        return 16
    if "int8" in dtype or "float8" in dtype:
        return 32
    return 8


def _check_block(c: PallasContract, arg: BlockArg,
                 res: PalResult) -> None:
    bs = arg.block_shape
    if bs is None:                 # whole-array block: trivially fine
        return
    if len(bs) != len(arg.shape):
        res.add("block-divide",
                f"{arg.name}: BlockSpec rank {len(bs)} != operand "
                f"rank {len(arg.shape)}", c.site,
                {"block": list(bs), "shape": list(arg.shape)})
        return
    for d, (b, s) in enumerate(zip(bs, arg.shape)):
        if b is None:
            # a None entry is a SQUEEZED dim (block size 1, iterated
            # by the index map) — exempt from quanta, divides trivially
            continue
        b = int(b)
        if b <= 0 or s % b:
            res.add("block-divide",
                    f"{arg.name}: block dim {d} ({b}) does not "
                    f"divide the operand extent {s} — callers must "
                    f"pad operands to the block quantum", c.site,
                    {"arg": arg.name, "dim": d, "block": b,
                     "extent": s})
        quantum = None
        if d == len(bs) - 1:
            quantum = 128
        elif d == len(bs) - 2:
            quantum = _sublane(arg.dtype)
        if quantum and b != s and b % quantum:
            res.add("tiling",
                    f"{arg.name}: block dim {d} ({b}) is neither the "
                    f"full extent ({s}) nor a multiple of the "
                    f"{'lane' if quantum == 128 else 'sublane'} "
                    f"quantum {quantum} for {arg.dtype}", c.site,
                    {"arg": arg.name, "dim": d, "block": b,
                     "quantum": quantum})


def _iter_grid(grid: Tuple[int, ...]):
    import itertools
    return itertools.product(*(range(g) for g in grid))


def _check_index_maps(c: PallasContract, res: PalResult) -> None:
    total = 1
    for g in c.grid:
        total *= g
    if not c.grid:
        return
    if total > _GRID_ENUM_CAP:
        res.add("bad-grid",
                f"grid {c.grid} too large to enumerate "
                f"({total} > {_GRID_ENUM_CAP}) — capture the "
                f"contract on smaller probe shapes", c.site)
        return
    for arg, is_out in [(a, False) for a in c.ins] + \
                       [(a, True) for a in c.outs]:
        if arg.index_map is None or arg.block_shape is None:
            continue
        # None = squeezed dim: block size 1, so the dim has s blocks
        nblocks = tuple(
            s // (1 if b is None else int(b))
            for b, s in zip(arg.block_shape, arg.shape))
        seen = set()
        for pt in _iter_grid(c.grid):
            try:
                idx = arg.index_map(*pt)
            except TypeError as exc:
                res.add("bad-grid",
                        f"{arg.name}: index map arity does not match "
                        f"grid rank {len(c.grid)}: {exc}", c.site)
                break
            idx = tuple(int(i) for i in (
                idx if isinstance(idx, tuple) else (idx,)))
            if len(idx) != len(nblocks) or any(
                    not (0 <= i < max(n, 1))
                    for i, n in zip(idx, nblocks)):
                res.add("oob-index",
                        f"{arg.name}: index map sends grid point "
                        f"{pt} to block {idx}, outside the "
                        f"{nblocks} block grid of shape "
                        f"{arg.shape}", c.site,
                        {"arg": arg.name, "point": list(pt),
                         "block_index": list(idx)})
                break
            seen.add(idx)
        else:
            if is_out:
                all_blocks = set(_iter_grid(
                    tuple(max(n, 1) for n in nblocks)))
                missing = sorted(all_blocks - seen)
                if missing:
                    res.add("gap-index",
                            f"{arg.name}: index map never visits "
                            f"output block(s) {missing[:4]}"
                            f"{'...' if len(missing) > 4 else ''} — "
                            f"those tiles are left unwritten",
                            c.site,
                            {"arg": arg.name,
                             "missing": [list(m) for m in
                                         missing[:16]]})


def _itemsize(dtype: str) -> int:
    import numpy as np
    try:
        return np.dtype(dtype.replace("bfloat16", "uint16")).itemsize
    except (TypeError, ValueError):
        return 4


def _check_vmem(c: PallasContract, res: PalResult,
                budget: int = VMEM_BYTES) -> None:
    total = 0
    detail = {}
    gridded = bool(c.grid) and any(g > 1 for g in c.grid)
    for arg in c.ins + c.outs:
        bs = arg.block_shape if arg.block_shape is not None \
            else arg.shape
        n = 1
        for b in bs:
            # None = squeezed dim: one slice resident per grid step
            n *= 1 if b is None else int(b)
        # the pipeline double-buffers grid-iterated blocks
        mult = 2 if (gridded and arg.block_shape is not None) else 1
        bytes_ = n * _itemsize(arg.dtype) * mult
        detail[arg.name] = bytes_
        total += bytes_
    for i, (shape, dtype) in enumerate(c.scratch):
        n = 1
        for s in shape:
            n *= int(s)
        bytes_ = n * _itemsize(dtype)
        detail[f"scratch{i}"] = bytes_
        total += bytes_
    if total > budget:
        res.add("vmem-overflow",
                f"VMEM budget estimate {total} bytes exceeds the "
                f"{budget} byte ceiling (blocks double-buffered: "
                f"{detail})", c.site,
                {"estimate": total, "budget": budget,
                 "by_buffer": detail})


def _check_precision(c: PallasContract, res: PalResult) -> None:
    dd_ok = any(c.site.startswith(p) for p in F64_SITES)
    for i, (shape, dtype) in enumerate(c.scratch):
        if "float" in dtype and dtype not in ("float32",):
            res.add("precision",
                    f"scratch{i}: {dtype} VMEM accumulator — the MXU "
                    f"accumulate contract is f32 scratch "
                    f"(downcast in the epilogue, never the "
                    f"accumulator)", c.site,
                    {"scratch": i, "dtype": dtype})
    if not dd_ok:
        for arg in c.ins + c.outs:
            if arg.dtype == "float64":
                res.add("f64-outside-dd",
                        f"{arg.name}: float64 in a pallas contract "
                        f"outside kernels/{{dd,pallas_dd}} (TPU has "
                        f"no native f64; route through the dd "
                        f"emulation)", c.site,
                        {"arg": arg.name})


def check_contract(c: PallasContract,
                   budget: int = VMEM_BYTES) -> PalResult:
    """All static checks over one captured contract."""
    res = PalResult(contracts=1)
    for g in c.grid:
        if int(g) < 1:
            res.add("bad-grid", f"grid {c.grid} has a non-positive "
                    f"dimension", c.site)
    for arg in c.ins + c.outs:
        _check_block(c, arg, res)
    _check_index_maps(c, res)
    _check_vmem(c, res, budget)
    _check_precision(c, res)
    return res


def verify_contract(c: PallasContract, **kw) -> PalResult:
    res = check_contract(c, **kw)
    if not res.ok:
        raise PalCheckError(res)
    return res


# ---------------------------------------------------------------------
# Site registry: every pallas_call entry point in the package
# ---------------------------------------------------------------------

def _cap_pallas_kernels(out: List[PallasContract]) -> None:
    """kernels/pallas_kernels.py: the fused GEMM (both the 3-operand
    epilogue variant and the C-free matmul) on a 2x2x2 grid."""
    import jax.numpy as jnp
    from dplasma_tpu.kernels import pallas_kernels as pk
    a = jnp.zeros((16, 256), jnp.float32)
    b = jnp.zeros((256, 256), jnp.float32)
    c = jnp.zeros((16, 256), jnp.float32)
    fn = pk.gemm.__wrapped__          # eager: jit cache never involved
    with capture("dplasma_tpu/kernels/pallas_kernels.py:gemm", out):
        fn(a, b, c, alpha=1.0, beta=0.5, bm=8, bn=128, bk=128)
        fn(a, b, None, alpha=1.0, beta=0.0, bm=8, bn=128, bk=128)


def _cap_pallas_lu(out: List[PallasContract]) -> None:
    """kernels/pallas_lu.py: the blocked LU panel (whole-panel VMEM
    residency, no grid) and the no-pivot tile LU (whole tile)."""
    import jax.numpy as jnp
    from dplasma_tpu.kernels import pallas_lu
    a = jnp.zeros((32, 16), jnp.float32)
    with capture("dplasma_tpu/kernels/pallas_lu.py:lu_panel", out):
        pallas_lu._panel_call.__wrapped__(a, True)
    t = jnp.zeros((128, 128), jnp.float32)
    with capture("dplasma_tpu/kernels/pallas_lu.py:lu_nopiv_tile", out):
        pallas_lu._nopiv_call.__wrapped__(t, True)


def _cap_pallas_qr(out: List[PallasContract]) -> None:
    """kernels/pallas_qr.py: the fused blocked Householder QR panel
    (whole-panel VMEM residency, no grid)."""
    import jax.numpy as jnp
    from dplasma_tpu.kernels import pallas_qr
    a = jnp.zeros((32, 16), jnp.float32)
    with capture("dplasma_tpu/kernels/pallas_qr.py:geqrt_panel", out):
        pallas_qr._geqrt_call.__wrapped__(a, True)


def _cap_pallas_dd(out: List[PallasContract]) -> None:
    """kernels/pallas_dd.py: the dd level-recombine epilogue."""
    import jax.numpy as jnp
    from dplasma_tpu.kernels import pallas_dd
    lv = jnp.zeros((2, 16, 128), jnp.int32)
    bh = jnp.zeros((16, 128), jnp.float32)
    sa = jnp.zeros((16, 1), jnp.float32)
    sb = jnp.zeros((1, 128), jnp.float32)
    with capture("dplasma_tpu/kernels/pallas_dd.py:recombine_base",
                 out):
        pallas_dd._recombine_call.__wrapped__(lv, bh, bh, sa, sb, 24,
                                              True)


def _cap_pallas_ring(out: List[PallasContract]) -> None:
    """kernels/pallas_ring.py: the ICI ring transfer kernels — the
    chunked panel-broadcast ring and the neighbor shift (whole-array
    ANY-space blocks, DMA-semaphore scratch, no grid). Capture only
    records the contract; the remote-DMA kernel bodies never run."""
    import jax.numpy as jnp
    from dplasma_tpu.kernels import pallas_ring
    x = jnp.zeros((16, 128), jnp.float32)
    axes = (("p", 1), ("q", 4))
    with capture("dplasma_tpu/kernels/pallas_ring.py:ring_bcast",
                 out):
        pallas_ring.ring_bcast(x, root=1, axis="q", axes=axes,
                               chunks=2, interpret=True)
    with capture("dplasma_tpu/kernels/pallas_ring.py:ring_shift",
                 out):
        pallas_ring.ring_shift(x, axis="q", axes=axes,
                               interpret=True)


#: relpath -> capture entry point exercising every pallas_call in it
SITES = {
    "dplasma_tpu/kernels/pallas_kernels.py": _cap_pallas_kernels,
    "dplasma_tpu/kernels/pallas_lu.py": _cap_pallas_lu,
    "dplasma_tpu/kernels/pallas_qr.py": _cap_pallas_qr,
    "dplasma_tpu/kernels/pallas_dd.py": _cap_pallas_dd,
    "dplasma_tpu/kernels/pallas_ring.py": _cap_pallas_ring,
}


def find_call_sites(root) -> List[Tuple[str, int]]:
    """AST sweep: every ``pallas_call`` call site under ``root`` as
    (repo-relative posix path, line)."""
    rootp = pathlib.Path(root)
    sites = []
    for path in sorted(rootp.rglob("*.py")):
        try:
            tree = ast.parse(path.read_text(), filename=str(path))
        except SyntaxError:
            continue
        s = path.as_posix()
        i = s.rfind("dplasma_tpu/")
        rel = s[i:] if i >= 0 else path.name
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                f = node.func
                name = f.attr if isinstance(f, ast.Attribute) else \
                    getattr(f, "id", "")
                if name == "pallas_call":
                    sites.append((rel, node.lineno))
    return sites


def check_package(root=None, budget: int = VMEM_BYTES) -> PalResult:
    """The full gate: AST sweep for call sites, capture via the
    registry, every captured contract checked. Unregistered sites are
    diagnostics (a new pallas kernel must register its entry point);
    a missing pallas install degrades to the sweep alone."""
    if root is None:
        root = pathlib.Path(__file__).resolve().parents[1]
    res = PalResult()
    sites = find_call_sites(root)
    res.sites_found = len(sites)
    by_file: Dict[str, list] = {}
    for rel, line in sites:
        by_file.setdefault(rel, []).append(line)
    try:
        from jax.experimental import pallas as _pl  # noqa: F401
        have_pallas = True
    except Exception:
        have_pallas = False
    for rel, lines in sorted(by_file.items()):
        if rel not in SITES:
            res.add("unregistered-site",
                    f"pallas_call at {rel}:{lines[0]} has no "
                    f"registered palcheck capture entry point — add "
                    f"one to analysis.palcheck.SITES", rel,
                    {"lines": lines})
    if not have_pallas:
        res.skipped = "pallas unavailable: contracts not captured"
        return res
    contracts: List[PallasContract] = []
    for rel, builder in sorted(SITES.items()):
        if rel not in by_file:
            continue                   # site file deleted: sweep rules
        try:
            builder(contracts)
        except Exception as exc:
            res.add("capture-failed",
                    f"capture entry point for {rel} raised "
                    f"{type(exc).__name__}: {exc}", rel)
    res.contracts = len(contracts)
    for c in contracts:
        sub = check_contract(c, budget)
        for d in sub.diagnostics:
            res.ok = False
            res.diagnostics.append(d)
    return res


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    root = args[0] if args else None
    res = check_package(root)
    print(res.format())
    return 0 if res.ok else 1


if __name__ == "__main__":
    sys.exit(main())
