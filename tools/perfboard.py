#!/usr/bin/env python3
"""perfboard: the standing perf observatory dashboard + CI gate.

Renders the full ``bench_history.jsonl`` ledger — every series the
longitudinal trend model (:mod:`dplasma_tpu.observability.trend`)
extracts, keyed by (family, metric, knob vector, platform,
placeholder) — as ONE static self-contained HTML page: an inline SVG
sparkline per series with its changepoints marked, placeholder
(CPU host-platform) series visually segregated, a worst-regression
table sorted by effect size in noise-sigma units, and per-series
provenance tooltips (git SHA, backend, jax version, MCA snapshot of
the newest stamped entry). No JavaScript, no external assets: the
file travels with an artifact tarball and opens anywhere. This is
the instrument the on-hardware scaling campaign reads its curves
from.

``--check`` is the CI mode. Exit codes mirror perfdiff's:

* 0 — no gated series regressed;
* 1 — at least one non-placeholder series' newest changepoint moved
  in the worse direction (the offending series and changepoint index
  are named on stdout);
* 2 — unusable input (missing/empty ledger, no extractable series).

Gating is changepoint-based, not fixed-threshold: a series gates
only once it has ``trend.MIN_POINTS`` points, and the bound adapts
to the series' own pooled MAD noise — the compile-dominated rungs
that swing 20-30% run-to-run stay informational while a quiet series
gates tightly. Placeholder series render (marked) but never gate: a
CPU-mesh curve is plumbing evidence, not a hardware claim.

Usage::

    python tools/perfboard.py --out perfboard.html
    python tools/perfboard.py --check          # CI gate, no HTML
    python tools/perfboard.py --check --out perfboard.html

Stdlib-only, like perfdiff and trend: loads the trend model by file
path so the jax-heavy package root never imports.
"""
from __future__ import annotations

import argparse
import html
import importlib.util
import pathlib
import sys
from typing import List, Optional

_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _trend():
    mod = sys.modules.get("dplasma_tpu.observability.trend")
    if mod is not None:
        return mod
    mod = sys.modules.get("_perfboard_trend")
    if mod is not None:
        return mod
    path = _ROOT / "dplasma_tpu" / "observability" / "trend.py"
    spec = importlib.util.spec_from_file_location(
        "_perfboard_trend", path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load trend from {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["_perfboard_trend"] = mod
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------------- rendering

_STYLE = """
body { font: 13px/1.5 system-ui, sans-serif; margin: 1.5em;
       color: #222; }
h1 { font-size: 1.3em; } h2 { font-size: 1.05em; margin-top: 1.6em; }
table { border-collapse: collapse; margin: 0.5em 0; }
td, th { border: 1px solid #ccc; padding: 2px 8px; text-align: left; }
th { background: #f0f0f0; }
.series { display: flex; align-items: center; gap: 12px;
          padding: 3px 0; border-bottom: 1px solid #eee; }
.series .name { width: 30em; overflow: hidden;
                text-overflow: ellipsis; white-space: nowrap; }
.series .val { width: 11em; text-align: right;
               font-variant-numeric: tabular-nums; }
.series .meta { color: #888; font-size: 11px; }
.placeholder { opacity: 0.55; }
.placeholder .name::after { content: " [placeholder]"; color: #b80; }
.reg { color: #b00; font-weight: 600; }
.ok { color: #080; }
.note { color: #888; font-size: 12px; }
svg { background: #fafafa; border: 1px solid #e5e5e5; }
"""


def _sparkline(values: List[float], cps: List[int],
               width: int = 240, height: int = 40) -> str:
    """Inline SVG sparkline: the series polyline (min-max normalized)
    with changepoint indices marked red and the newest point dotted."""
    n = len(values)
    if n == 0:
        return "<svg width='%d' height='%d'></svg>" % (width, height)
    lo, hi = min(values), max(values)
    span = (hi - lo) or 1.0
    pad = 4

    def xy(i: int, v: float):
        x = pad + (width - 2 * pad) * (i / max(n - 1, 1))
        y = height - pad - (height - 2 * pad) * ((v - lo) / span)
        return x, y

    pts = " ".join("%.1f,%.1f" % xy(i, v) for i, v in enumerate(values))
    parts = ["<svg width='%d' height='%d' role='img'>" % (width, height),
             "<polyline points='%s' fill='none' stroke='#36c' "
             "stroke-width='1.2'/>" % pts]
    for i in cps:
        if 0 <= i < n:
            x, y = xy(i, values[i])
            parts.append("<circle cx='%.1f' cy='%.1f' r='3' "
                         "fill='#b00'/>" % (x, y))
    x, y = xy(n - 1, values[-1])
    parts.append("<circle cx='%.1f' cy='%.1f' r='2' fill='#36c'/>"
                 % (x, y))
    parts.append("</svg>")
    return "".join(parts)


def _prov_tooltip(series: dict) -> str:
    """The newest stamped provenance of a series as a title tooltip."""
    prov = None
    for p in reversed(series["points"]):
        if isinstance(p.get("provenance"), dict):
            prov = p["provenance"]
            break
    if prov is None:
        return "no provenance stamp"
    bits = []
    git = prov.get("git")
    if isinstance(git, dict) and git.get("sha"):
        bits.append("git %s%s" % (git["sha"][:12],
                                  "+dirty" if git.get("dirty") else ""))
    for key in ("backend", "jax", "jaxlib", "peaks_source", "family"):
        if prov.get(key):
            bits.append(f"{key}={prov[key]}")
    if prov.get("mesh_shape"):
        bits.append("mesh=%sx%s" % tuple(prov["mesh_shape"][:2]))
    mca = prov.get("mca")
    if isinstance(mca, dict) and mca:
        bits.append("mca{%s}" % ",".join(f"{k}={v}"
                                         for k, v in sorted(mca.items())))
    if prov.get("backfilled"):
        bits.append("backfilled:%s" % prov.get("source", "?"))
    return "; ".join(bits) or "empty provenance stamp"


def render(series_map: dict, verdicts: dict, notes: List[str],
           ledger: str) -> str:
    """The full dashboard page."""
    tr = _trend()
    keys = sorted(series_map,
                  key=lambda k: (series_map[k]["placeholder"],
                                 series_map[k]["family"], k))
    regressions = [(k, verdicts[k]["regression"]) for k in keys
                   if verdicts.get(k) and verdicts[k]["regression"]]
    regressions.sort(key=lambda kr: -kr[1]["effect_sigma"])
    n_pts = sum(len(series_map[k]["points"]) for k in keys)
    out = ["<!doctype html><html><head><meta charset='utf-8'>",
           "<title>perfboard</title>",
           "<style>%s</style></head><body>" % _STYLE,
           "<h1>perfboard — longitudinal perf observatory</h1>",
           "<p class='note'>ledger: %s · %d series · %d points · "
           "gate: changepoint z=%.1f sigma, min shift %.0f%%, min "
           "history %d points</p>"
           % (html.escape(str(ledger)), len(keys), n_pts, tr.Z_SIGMA,
              100 * tr.MIN_SHIFT, tr.MIN_POINTS)]
    out.append("<h2>Worst regressions</h2>")
    if regressions:
        out.append("<table><tr><th>series</th><th>changepoint</th>"
                   "<th>shift</th><th>effect</th><th>before → after"
                   "</th></tr>")
        for key, reg in regressions:
            out.append(
                "<tr class='reg'><td>%s</td><td>@%d</td>"
                "<td>%+.1f%%</td><td>%.1f sigma</td>"
                "<td>%.6g → %.6g</td></tr>"
                % (html.escape(key), reg["index"],
                   100 * reg["shift"], reg["effect_sigma"],
                   reg["before"], reg["after"]))
        out.append("</table>")
    else:
        out.append("<p class='ok'>none — every gated series is within "
                   "its noise-calibrated bound.</p>")
    out.append("<h2>Series</h2>")
    for key in keys:
        s = series_map[key]
        values = [p["value"] for p in s["points"]]
        v = verdicts.get(key)
        cps = [c["index"] for c in (v or {}).get("changepoints", [])]
        sigma = tr.noise_sigma(values)
        cls = "series placeholder" if s["placeholder"] else "series"
        badge = ""
        if v and v["regression"]:
            badge = " <span class='reg'>REGRESSION @%d</span>" \
                % v["regression"]["index"]
        unit = f" {s['unit']}" if s.get("unit") else ""
        meta = "%d pts" % len(values)
        if sigma is not None:
            meta += ", sigma %.1f%%" % (100 * sigma)
        elif len(values) < tr.MIN_POINTS:
            meta += ", too short to gate"
        out.append(
            "<div class='%s' title='%s'><span class='name'>%s</span>"
            "%s<span class='val'>%.6g%s</span>"
            "<span class='meta'>%s</span>%s</div>"
            % (cls, html.escape(_prov_tooltip(s), quote=True),
               html.escape(key), _sparkline(values, cps),
               values[-1], html.escape(unit), meta, badge))
    if notes:
        out.append("<h2>Ingestion notes</h2><ul>")
        out.extend("<li class='note'>%s</li>" % html.escape(n)
                   for n in notes)
        out.append("</ul>")
    out.append("</body></html>")
    return "\n".join(out)


# ---------------------------------------------------------------- main

def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="perfboard", description=__doc__.splitlines()[0])
    ap.add_argument("--ledger", required=True,
                    help="the JSONL ledger to render")
    ap.add_argument("--out", default=None, metavar="HTML",
                    help="write the dashboard here (default "
                         "perfboard.html unless --check)")
    ap.add_argument("--check", action="store_true",
                    help="CI gate: exit 1 when a non-placeholder "
                         "series' newest changepoint regressed, 2 on "
                         "unusable input (mirrors perfdiff)")
    ap.add_argument("--z-sigma", type=float, default=None,
                    help="changepoint bound in noise-sigma units "
                         "(default trend.Z_SIGMA)")
    ap.add_argument("--min-shift", type=float, default=None,
                    help="minimum relative median shift to gate "
                         "(default trend.MIN_SHIFT)")
    ap.add_argument("-v", "--verbose", action="store_true")
    ns = ap.parse_args(argv)
    tr = _trend()
    z = ns.z_sigma if ns.z_sigma is not None else tr.Z_SIGMA
    min_shift = ns.min_shift if ns.min_shift is not None \
        else tr.MIN_SHIFT
    try:
        series_map, notes = tr.ingest_ledger(ns.ledger)
    except (OSError, ValueError) as exc:
        sys.stderr.write(f"perfboard: {exc}\n")
        return 2
    if not series_map:
        sys.stderr.write(f"perfboard: {ns.ledger}: no extractable "
                         f"series\n")
        return 2
    verdicts = {k: tr.gate_series(s, z=z, min_shift=min_shift)
                for k, s in series_map.items()}
    regressed = [(k, v["regression"]) for k, v in verdicts.items()
                 if v and v["regression"]]
    regressed.sort(key=lambda kr: -kr[1]["effect_sigma"])
    out_path = ns.out or (None if ns.check else "perfboard.html")
    if out_path:
        text = render(series_map, verdicts, notes, ns.ledger)
        with open(out_path, "w") as f:
            f.write(text + "\n")
        print(f"# perfboard: {len(series_map)} series -> {out_path}")
    if ns.verbose:
        for n in notes:
            print(f"# perfboard: note: {n}")
    gated = sum(1 for v in verdicts.values() if v is not None)
    for key, reg in regressed:
        print("perfboard: REGRESSION %s changepoint @%d "
              "(%+.1f%%, %.1f sigma, %.6g -> %.6g)"
              % (key, reg["index"], 100 * reg["shift"],
                 reg["effect_sigma"], reg["before"], reg["after"]))
    if ns.check:
        if regressed:
            print("perfboard: %d series regressed (of %d gated, "
                  "%d total)" % (len(regressed), gated,
                                 len(series_map)))
            return 1
        print("perfboard: OK (%d gated series within their "
              "noise-calibrated bounds; %d total)"
              % (gated, len(series_map)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
