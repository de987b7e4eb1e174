"""XLA-side capture: cost_analysis / memory_analysis of a compiled op.

The reference stamps run metadata into its binary trace after the
taskpool compiles (``PROFILING_SAVE_[di]INFO``); the XLA analogue is the
compiled executable's own accounting — HLO flop/byte counts and the
buffer-assignment memory breakdown. Both are best-effort across
backends/versions (PJRT may return None, a list, or a dict), so every
field here is guarded and reported as an explicit ``None`` rather than
omitted: a null in the run-report means "backend declined to answer"
(returned None), never "forgot to ask" — and a backend that RAISES
instead records a structured ``{"error": <reason>}`` in the report's
``"xla"`` section, so a broken analysis path is distinguishable from
a merely silent one.
"""
from __future__ import annotations

from typing import Optional

#: cost_analysis keys lifted to the report top level (XLA spells them
#: with spaces; the report uses identifier-friendly names).
_COST_KEYS = {
    "flops": "flops",
    "transcendentals": "transcendentals",
    "bytes accessed": "bytes_accessed",
    "optimal_seconds": "optimal_seconds",
}

_MEM_ATTRS = (
    "generated_code_size_in_bytes", "argument_size_in_bytes",
    "output_size_in_bytes", "alias_size_in_bytes", "temp_size_in_bytes",
    "peak_memory_in_bytes",
)


def _cost_dict(compiled) -> Optional[dict]:
    try:
        ca = compiled.cost_analysis()
    except Exception as exc:              # raising backend: keep why
        return {"error": repr(exc)}
    if ca is None:
        return None
    return dict(ca) if isinstance(ca, dict) else None


def capture_compiled(compiled) -> dict:
    """Cost/memory capture of a ``jax.stages.Compiled``.

    Returns ``{"cost": {...}|None, "memory": {...}|None, ...}`` with
    the headline figures (``flops``, ``bytes_accessed``, ``peak_bytes``)
    lifted to the top so report consumers need not know XLA's key
    spelling. Never raises.
    """
    out = {"flops": None, "bytes_accessed": None, "transcendentals": None,
           "optimal_seconds": None, "cost": None, "memory": None,
           "peak_bytes": None}
    cost = _cost_dict(compiled)
    if cost and "error" in cost:
        # the structured failure record: a raising cost_analysis is
        # reported as {"error": reason}, never a silent null
        out["cost"] = cost
    elif cost:
        # keep only scalar entries (per-operand "bytes accessed0{}"
        # subkeys stay in the full dict)
        out["cost"] = {k: v for k, v in cost.items()
                       if isinstance(v, (int, float))}
        for xk, rk in _COST_KEYS.items():
            if xk in cost:
                out[rk] = float(cost[xk])
    try:
        ma = compiled.memory_analysis()
    except Exception as exc:              # raising backend: keep why
        ma = None
        out["memory"] = {"error": repr(exc)}
    if ma is not None:
        mem = {}
        for attr in _MEM_ATTRS:
            v = getattr(ma, attr, None)
            if isinstance(v, (int, float)):
                mem[attr] = int(v)
        if mem:
            out["memory"] = mem
            # peak live bytes as XLA reports it; the CPU's figure
            # leaves the temps out, so there args+outputs+temps bounds
            # the footprint of one execution
            if "peak_memory_in_bytes" in mem and not _on_cpu(compiled):
                out["peak_bytes"] = mem["peak_memory_in_bytes"]
            else:
                out["peak_bytes"] = sum(mem.get(a, 0) for a in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes"))
    return out


def _on_cpu(compiled) -> bool:
    """Does ``compiled`` run on the host CPU (judged by the devices of
    its shardings; the default backend when it has none)?"""
    import jax
    shardings = jax.tree.leaves((getattr(compiled, "input_shardings", ()),
                                 getattr(compiled, "output_shardings", ())))
    for s in shardings:
        return next(iter(s.device_set)).platform == "cpu"
    return jax.default_backend() == "cpu"
