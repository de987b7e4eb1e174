"""What each instruction of a compiled program is, read from its HLO text.

The device trace names every operation by its HLO instruction name
(``fusion.12``, ``convolution.3``, ``all-gather.1``). This module turns
the text of the compiled programs into an index from that name to

* ``category``: ``collective``, ``panel`` (by the JAX op-name metadata,
  which says which ``jax.lax`` call emitted the instruction),
  ``matmul`` or ``other``;
* ``flops``: the nominal operations of the dots and convolutions the
  instruction runs (fused or not), 2*M*N*K for each output element's
  contraction, from the shapes in the HLO;
* ``operand``: the element type of those products' left operand, which
  selects the peak they are held to (``s8`` against the int8 peak,
  anything else against the bf16 peak).

The collective and ring vocabulary is copied from
``dplasma_tpu/analysis/hlo_names.py``.
"""
from __future__ import annotations

import re

#: HLO collective opcodes (async ``-start``/``-done`` halves included:
#: on the device trace the ``-done`` half is where a collective waits)
COLLECTIVES = frozenset({
    "all-reduce", "all-reduce-start", "all-reduce-done",
    "all-gather", "all-gather-start", "all-gather-done",
    "reduce-scatter", "collective-permute", "collective-permute-start",
    "collective-permute-done", "all-to-all", "collective-broadcast",
})
#: name marker of the ICI ring kernels' Mosaic custom calls
RING_MARKER = "dplasma_ring_"
#: op-name components of the ``jax.lax.linalg`` calls that factor and
#: solve the diagonal blocks and panels
PANEL_OPS = frozenset({"cholesky", "lu", "triangular_solve",
                       "lu_pivots_to_permutation"})

_SHAPE = re.compile(r"([a-z]\w*)\[([\d,]*)\]")
_HEAD = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*\))?\s*->.*\{\s*$")
_OPNAME = re.compile(r'op_name="([^"]*)"')


def _balanced(s: str, i: int) -> int:
    """Index just past the bracket group that opens at ``s[i]``."""
    pairs = {"(": ")", "{": "}", "[": "]"}
    stack = []
    for k in range(i, len(s)):
        ch = s[k]
        if ch in pairs:
            stack.append(pairs[ch])
        elif stack and ch == stack[-1]:
            stack.pop()
            if not stack:
                return k + 1
    return len(s)


def _shape(text: str):
    """(element type, dims) of the first array shape in ``text``."""
    m = _SHAPE.search(text)
    if not m:
        return None, ()
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return m.group(1), dims


def _prod(dims) -> int:
    out = 1
    for d in dims:
        out *= d
    return out


def _parse_line(line: str):
    """One instruction line -> dict, or None for anything else."""
    m = _HEAD.match(line)
    if not m:
        return None
    name, rest = m.group(1), line[m.end():]
    if rest.startswith("("):                       # tuple-shaped result
        end = _balanced(rest, 0)
        rtype, rest = rest[:end], rest[end:].lstrip()
    else:
        sp = rest.find(" ")
        rtype, rest = rest[:sp], rest[sp + 1:]
    p = rest.find("(")
    if p <= 0:
        return None
    opcode = rest[:p]
    end = _balanced(rest, p)
    operands = [o.strip() for o in _split_top(rest[p + 1:end - 1])]
    attrs = rest[end:]
    return {"name": name, "type": rtype, "opcode": opcode,
            "operands": operands, "attrs": attrs}


def _split_top(s: str):
    out, depth, cur = [], 0, []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if "".join(cur).strip():
        out.append("".join(cur))
    return out


def _attr(attrs: str, key: str):
    m = re.search(r"\b" + re.escape(key) + r"=(\{[^}]*\}|[^,\s]+)", attrs)
    return m.group(1) if m else None


def _dims_attr(attrs: str, key: str):
    v = _attr(attrs, key)
    if not v:
        return ()
    return tuple(int(d) for d in v.strip("{}").split(",") if d.strip())


def parse(text: str) -> dict:
    """{computation name: [instruction dicts]} of one HLO module text."""
    comps: dict = {}
    cur = None
    for line in text.splitlines():
        if cur is None:
            m = _COMP.match(line)
            if m:
                cur = comps.setdefault(m.group(1), [])
            continue
        if line.strip() == "}":
            cur = None
            continue
        ins = _parse_line(line)
        if ins is not None:
            cur.append(ins)
    return comps


def _operand_shape(op: str, shapes: dict):
    """(element type, dims) of an operand, printed with or without its
    shape."""
    if "[" in op:
        return _shape(op)
    return shapes.get(op.split()[-1].lstrip("%"), (None, ()))


def _product_flops(ins: dict, shapes: dict):
    """(flops, lhs element type) of a dot or convolution."""
    _, out = _shape(ins["type"])
    if len(ins["operands"]) < 2:
        return 0.0, None
    ltype, ldims = _operand_shape(ins["operands"][0], shapes)
    _, rdims = _operand_shape(ins["operands"][1], shapes)
    if ins["opcode"] == "dot":
        k = _prod(ldims[d] for d in _dims_attr(ins["attrs"],
                                               "lhs_contracting_dims"))
        return 2.0 * _prod(out) * k, ltype
    labels = _attr(ins["attrs"], "dim_labels")
    if not labels or "_" not in labels or not rdims:
        return 0.0, ltype
    kernel = labels.split("_")[1].split("->")[0]
    k = _prod(rdims[i] for i, c in enumerate(kernel) if c != "o")
    return 2.0 * _prod(out) * k, ltype


def module_name(text: str) -> str:
    """The module's name, from its first line (``HloModule jit_fn, ...``)."""
    head = text.split("\n", 1)[0]
    return head.split()[1].rstrip(",") if head.startswith("HloModule ") \
        else ""


def index(texts) -> dict:
    """{instruction name: info} over the modules in ``texts``."""
    out: dict = {}
    for text in texts:
        comps = parse(text)
        shapes = {}
        for inss in comps.values():
            for ins in inss:
                shapes[ins["name"]] = _shape(ins["type"])
        own: dict = {}   # computation -> (flops, operand type)

        def comp_flops(cname, seen=()):
            if cname in own:
                return own[cname]
            total, otype = 0.0, None
            for ins in comps.get(cname, ()):
                f, t = _ins_flops(ins, seen + (cname,))
                total += f
                otype = otype or t
            own[cname] = (total, otype)
            return own[cname]

        def _ins_flops(ins, seen):
            if ins["opcode"] in ("dot", "convolution"):
                return _product_flops(ins, shapes)
            if ins["opcode"] == "fusion":
                called = (_attr(ins["attrs"], "calls") or "").lstrip("%")
                if called and called not in seen:
                    return comp_flops(called, seen)
            return 0.0, None

        roots = {}
        for cname, inss in comps.items():
            for ins in inss:
                roots[cname] = ins   # the last line is the ROOT
        for cname, inss in comps.items():
            for ins in inss:
                flops, otype = _ins_flops(ins, (cname,))
                m = _OPNAME.search(ins["attrs"])
                if not m and ins["opcode"] == "fusion":
                    called = (_attr(ins["attrs"], "calls") or "").lstrip("%")
                    root = roots.get(called)
                    m = root and _OPNAME.search(root["attrs"])
                op_name = m.group(1) if m else ""
                out[ins["name"]] = {
                    "category": _category(ins, op_name, flops),
                    "flops": flops, "operand": otype}
    return out


def _category(ins: dict, op_name: str, flops: float) -> str:
    if ins["opcode"] in COLLECTIVES or RING_MARKER in ins["name"] \
            or RING_MARKER in ins["attrs"]:
        return "collective"
    if PANEL_OPS & {part.split("[")[0] for part in op_name.split("/")}:
        return "panel"
    if flops > 0:
        return "matmul"
    return "other"
