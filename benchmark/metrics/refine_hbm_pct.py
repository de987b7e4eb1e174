"""Refinement: its operations' share of their memory roofline. GMRES and
the f64-equivalent residuals are matrix-vector work, bounded by HBM, so
for every operation under the ``dplasma.residual`` or
``dplasma.correct`` scope (at any depth, ``benchmark/scopes.index``) the
least time it could take is the bytes it must move, read from the
compiled HLO by ``op_bytes`` below, over ``hbm_bytes_per_s`` in
``benchmark/peaks.json``; the share is the sum of those least times
over the ops' summed device time. Loops and calls are left out: their
bodies' operations are counted themselves."""
import re

from benchmark import hlo, peaks, scopes
from benchmark.metrics.refine_pct import REFINE

ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
            "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
            "u64": 8, "c64": 8, "c128": 16}
#: opcodes that read a part of their first operand: they move their
#: result's bytes twice (read, write)
PARTIAL_READ = frozenset({"dynamic-slice", "slice", "gather"})
#: opcodes that write a part of their first operand in place: they move
#: their update's bytes twice
IN_PLACE = {"dynamic-update-slice": 1, "scatter": 2}
#: opcodes that only relabel their operand's shape
RELABEL = frozenset({"bitcast", "reshape"})
#: opcodes whose device time is that of the operations they run
CONTROL = frozenset({"while", "conditional", "call"})
#: opcodes that move no data (the ``-done`` half of an async pair waits)
FREE = frozenset({"tuple", "get-tuple-element", "bitcast", "parameter",
                  "constant", "after-all", "partition-id", "replica-id",
                  "copy-done", "slice-done"})
_TYPE = re.compile(r"([a-z]\w*)\[([\d,]*)\](\{[^}]*\})?")
#: a layout placing the array in a memory space other than HBM (on the
#: TPU, ``S(1)`` is the chip's own vector memory)
_ON_CHIP = re.compile(r"S\([1-9]\d*\)")


def type_bytes(text: str) -> int:
    """Bytes in HBM of every array in a printed (possibly tuple) type:
    arrays whose layout names another memory space count nothing."""
    total = 0
    for et, dims, layout in _TYPE.findall(text):
        if layout and _ON_CHIP.search(layout):
            continue
        n = ITEMSIZE.get(et, 0)
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n
    return total


def _ref(op: str) -> str:
    return op.split()[-1].lstrip("%")


def _operand_bytes(op: str, types: dict) -> int:
    if "[" in op:
        return type_bytes(op.rsplit(" ", 1)[0])
    return types.get(_ref(op), 0)


class _Program:
    """One module's computations, for the bytes its operations move."""

    def __init__(self, text: str):
        self.comps = hlo.parse(text)
        self.types = {ins["name"]: type_bytes(ins["type"])
                      for inss in self.comps.values() for ins in inss}

    def reads(self, comp: str, name: str, depth: int = 0) -> int:
        """Bytes read of value ``name`` inside computation ``comp``:
        where every use slices it (through shape relabels and nested
        fusions), the slices; else the whole value."""
        whole = self.types.get(name, 0)
        total = 0
        for ins in self.comps.get(comp, ()):
            for k, op in enumerate(ins["operands"]):
                if _ref(op) != name:
                    continue
                opc = ins["opcode"]
                if opc in PARTIAL_READ and k == 0:
                    total += self.types.get(ins["name"], 0)
                elif opc in IN_PLACE and k == 0:
                    continue              # the in-place base is not read
                elif opc in RELABEL and depth < 8:
                    total += self.reads(comp, ins["name"], depth + 1)
                elif opc == "fusion" and depth < 8:
                    total += self.reads(*self._param(ins, k), depth + 1)
                else:
                    return whole
        return min(total, whole)

    def _param(self, ins: dict, k: int):
        """(called computation, name of its k-th parameter)."""
        called = (hlo._attr(ins["attrs"], "calls") or "").lstrip("%")
        for p in self.comps.get(called, ()):
            if p["opcode"] == "parameter" and p["operands"] == [str(k)]:
                return called, p["name"]
        return called, ""

    def op_bytes(self, ins: dict):
        opc, ops = ins["opcode"], ins["operands"]
        if opc in CONTROL:
            return None
        if opc in FREE:
            return 0
        if opc in PARTIAL_READ:
            return 2 * self.types[ins["name"]]
        if opc in IN_PLACE and len(ops) > IN_PLACE[opc]:
            return 2 * _operand_bytes(ops[IN_PLACE[opc]], self.types)
        if opc in ("slice-start", "copy-start"):
            # (operand, result, context): what it moves, read and written
            # where each lies (a copy from HBM into on-chip memory reads
            # HBM once)
            parts = _TYPE.findall(ins["type"])
            if len(parts) > 1:
                moved = "%s[%s]" % parts[1][:2]
                return type_bytes(moved + parts[0][2]) + type_bytes(
                    "%s[%s]%s" % parts[1])
        if opc == "fusion":
            return self._fused_bytes(ins)
        return self.types[ins["name"]] + sum(
            _operand_bytes(o, self.types) for o in ops)

    def _fused_bytes(self, ins: dict) -> int:
        """A fusion moves its output (or, where its root updates a
        parameter in place, that update twice) and what it reads of
        each operand (``reads``)."""
        called = (hlo._attr(ins["attrs"], "calls") or "").lstrip("%")
        body = self.comps.get(called, [])
        root = body[-1] if body else None
        total = self.types[ins["name"]]
        in_place = None
        if root is not None and root["opcode"] in IN_PLACE:
            in_place = _ref(root["operands"][0])
            total = 2 * self.types.get(
                _ref(root["operands"][IN_PLACE[root["opcode"]]]), 0)
        for k in range(len(ins["operands"])):
            comp, pname = self._param(ins, k)
            if pname and pname != in_place:
                total += self.reads(comp, pname)
        return total


def op_bytes(texts) -> dict:
    """{instruction name: bytes it must move} over the modules in
    ``texts``; control flow maps to None."""
    out: dict = {}
    for text in texts:
        prog = _Program(text)
        for inss in prog.comps.values():
            for ins in inss:
                out[ins["name"]] = prog.op_bytes(ins)
    return out


def read(ctx):
    t = ctx["trace"]
    sc = scopes.index(ctx.get("hlo_texts", ()))
    nbytes = op_bytes(ctx.get("hlo_texts", ()))
    moved = busy = 0.0
    for evs in t.devices.values():
        for n, a, b in evs:
            if REFINE & set(sc.get(n, ())) and nbytes.get(n) is not None:
                moved += nbytes[n]
                busy += (b - a) * 1e-9
    if busy <= 0:
        return None
    bw = peaks.of(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * moved / bw / busy
