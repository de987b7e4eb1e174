"""Finding a cell and everything it names, by name, in files of their own.

``BENCHMARK.json`` at the checkout's root lists the cells; a cell names
its configuration (``benchmark/configs/<file>``, from the ``configs``
entry) and its traffic mix (``benchmark/traffic/<traffic>.json``). The
configuration names its library entry (``benchmark/ops/<entry>.py``),
the traffic mix its loop (``benchmark/loops/<loop>.py``), and each
per-layer metric has its reader (``benchmark/metrics/<name>.py``). A
later cell, mix, entry, loop or metric is added as new files and
entries; no file here needs an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Cell:
    name: str
    root: str           # the checkout the cell's files are found in
    chips: int
    config: dict        # the configuration file's contents
    traffic: dict       # the traffic file's contents
    end_to_end: list    # BENCHMARK.json entries this cell reports
    per_layer: list


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


def cell(spec: dict, name: str, root: str = ROOT) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = _json(os.path.join(root, conf["file"]))
    traffic = _json(os.path.join(root, "benchmark", "traffic",
                                 w["traffic"] + ".json"))
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _applies(m, name, names)]
    return Cell(name, root, w["chips"], config, traffic, e2e, per_layer)


def _module(root: str, kind: str, name: str):
    """``<root>/benchmark/<kind>/<name>.py``, loaded from its path (a
    metric's name may hold dots)."""
    path = os.path.join(root, "benchmark", kind, name + ".py")
    s = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def entry(c: Cell):
    return _module(c.root, "ops", c.config["entry"])


def loop(c: Cell):
    return _module(c.root, "loops", c.traffic["loop"])


def reader(c: Cell, metric: str):
    return _module(c.root, "metrics", metric)
