"""From a profiler trace to the intervals the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but JAX (``jax.profiler.ProfileData``): the operations each
device ran (the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane,
each event named by its HLO instruction's text, ``%name = ...``), the
programs it ran (the ``XLA Modules`` line), and the benchmark's own host
spans (``TraceAnnotation`` names listed in ``SPANS``). ``Trace`` puts
the device events on the host spans' clock, cuts both to the traced
window and joins each operation to what ``hlo.index`` says about it.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import os

#: host spans the harness writes: ``call`` from the library call to its
#: return, ``wait`` from there to the answer being ready
SPANS = ("call", "wait")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: a program that starts more than this before its nearest call span was
#: not launched by it (the clocks agree far better than this)
MAX_LEAD_NS = 20e6


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def _op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...), ...`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def load(path: str, programs: bool = False) -> dict:
    """{"devices": {plane: [(op name, t0_ns, t1_ns), ...]},
    "modules": {plane: [(t0_ns, t1_ns), ...]},
    "spans": [(name, t0_ns, t1_ns), ...]} of one xplane file (raw or
    gzipped). With ``programs``, each module run also keeps its name,
    ``(t0_ns, t1_ns, name)``, for ``name_by_program``."""
    from jax.profiler import ProfileData
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    devices, modules, spans = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = sorted(
                        ((_op_name(ev.name), ev.start_ns,
                          ev.start_ns + ev.duration_ns)
                         for ev in line.events), key=lambda e: e[1])
                elif line.name == MODULES_LINE:
                    modules[plane.name] = sorted(
                        (ev.start_ns, ev.start_ns + ev.duration_ns)
                        + ((ev.name,) if programs else ())
                        for ev in line.events)
        else:
            for line in plane.lines:
                spans.extend((ev.name, ev.start_ns, ev.start_ns
                              + ev.duration_ns) for ev in line.events
                             if ev.name in SPANS)
    spans.sort(key=lambda e: e[1])
    return {"devices": {p: e for p, e in devices.items() if e},
            "modules": modules, "spans": spans}


def name_by_program(raw: dict, modules: dict) -> dict:
    """``raw`` (loaded with ``programs``) with each device op renamed
    ``<program>/<op>`` by the program whose run it lies in. ``modules``
    is ``{program: HLO module name}`` (``hlo.module_name``); a run
    belongs to a program when the run's name is that module's name and
    its id, ``jit_fn(1234)``. An op in no run, or in a run whose module
    name is no program's or more than one program's, keeps its bare
    name."""
    owner: dict = {}
    for prog, mod in modules.items():
        owner[mod] = None if mod in owner else prog
    devices, out_modules = {}, {}
    for plane, evs in raw["devices"].items():
        runs = raw["modules"].get(plane, [])
        starts = [m[0] for m in runs]
        out = []
        for n, a, b in evs:
            i = bisect.bisect_right(starts, a) - 1
            prog = None
            if i >= 0 and a < runs[i][1]:
                prog = owner.get(runs[i][2].rsplit("(", 1)[0])
            out.append((f"{prog}/{n}" if prog else n, a, b))
        devices[plane] = out
    for plane, runs in raw["modules"].items():
        out_modules[plane] = [(m[0], m[1]) for m in runs]
    return dict(raw, devices=devices, modules=out_modules)


def clock_shift(raw: dict, launch: str = "call") -> float:
    """Nanoseconds to add to device times so that no program starts on a
    device before the host span that launched it.

    Host and device events come from two clocks that the profiler
    aligns to about a millisecond. In a closed loop each program run
    follows its own ``call`` span, so where a run starts before the
    nearest call span, that lead is the device clock's. A server
    launches its programs from spans of its own (``launch``); spans of
    any other name launch nothing and set no shift."""
    calls = sorted(s[1] for s in raw["spans"] if s[0] == launch)
    lead = 0.0
    for mods in raw["modules"].values():
        for t0, _ in mods:
            i = bisect.bisect_left(calls, t0)
            if i == len(calls) or calls[i] - t0 >= MAX_LEAD_NS:
                continue
            if i == 0 or calls[i] - t0 < t0 - calls[i - 1]:
                lead = max(lead, calls[i] - t0)
    return lead


def union(intervals) -> list:
    """Sorted, merged copy of ``[(t0, t1), ...]``."""
    out = []
    for t0, t1 in sorted(intervals):
        if out and t0 <= out[-1][1]:
            if t1 > out[-1][1]:
                out[-1][1] = t1
        else:
            out.append([t0, t1])
    return [(a, b) for a, b in out]


def length(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def subtract(a, b) -> list:
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for t0, t1 in a:
        cur = t0
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < t1:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < t1:
            out.append((cur, t1))
    return out


class Trace:
    """One traced window: device operations joined to the HLO index,
    and the host spans, all cut to [t0, t1] (ns)."""

    def __init__(self, raw: dict, hlo_index: dict, launch: str = "call"):
        spans = raw["spans"]
        if not spans:
            raise ValueError("the trace holds none of the harness's spans")
        calls = sorted(s[1] for s in spans if s[0] == "call")
        # the first traced call is a lead-in: the device tracer may still
        # be starting while it runs, and miss some of its operations
        self.t0 = calls[1] if len(calls) > 1 else min(s[1] for s in spans)
        self.t1 = max(s[2] for s in spans)
        self.spans = [s for s in spans if s[2] > self.t0]
        self.hlo = hlo_index
        self.shift_ns = clock_shift(raw, launch)
        self.devices = {}
        for plane, evs in raw["devices"].items():
            sh = self.shift_ns
            self.devices[plane] = [
                (n, max(a + sh, self.t0), min(b + sh, self.t1))
                for n, a, b in evs if b + sh > self.t0 and a + sh < self.t1]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def info(self, name: str) -> dict:
        return self.hlo.get(name, {"category": "other", "flops": 0.0,
                                   "operand": None})

    def busy(self, plane: str) -> list:
        """Merged intervals in which an op ran on ``plane``."""
        return union((a, b) for _, a, b in self.devices[plane])

    def busy_s(self) -> float:
        """Device-busy seconds averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(length(self.busy(p)) for p in self.devices) * 1e-9 \
            / len(self.devices)

    def op_seconds(self) -> dict:
        """{op name: seconds} summed over devices, averaged per device."""
        out: dict = {}
        for evs in self.devices.values():
            for n, a, b in evs:
                out[n] = out.get(n, 0.0) + (b - a) * 1e-9
        nd = max(len(self.devices), 1)
        return {n: s / nd for n, s in out.items()}

    def span_at(self, t: float) -> str:
        for name, a, b in self.spans:
            if a <= t <= b:
                return name
        return "between_calls"

    def idle_gaps(self, plane: str) -> list:
        """[(t0, t1)] of the window in which ``plane`` ran nothing."""
        return subtract([(self.t0, self.t1)], self.busy(plane))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        device_ops = [[f"{self.info(n)['category']}:{n}", s]
                      for n, s in ops[:top]]
        gaps = []
        for plane in self.devices:
            for a, b in self.idle_gaps(plane):
                gaps.append((b - a, self.span_at((a + b) / 2), plane))
        gaps.sort(key=lambda g: -g[0])
        idle = [[f"{span}@{plane.rsplit(':', 1)[-1]}", d * 1e-9]
                for d, span, plane in gaps[:top]]
        return {"device_ops": device_ops, "idle_gaps": idle}
