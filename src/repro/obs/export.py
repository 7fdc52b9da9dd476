"""Exporters: Chrome-trace (Perfetto) JSON, span-tree text, and a CLI.

Chrome-trace format (the subset emitted here): a JSON object with a
``traceEvents`` list of *complete* events — ``ph: "X"`` with ``ts``/``dur``
in microseconds — one per recorded span, ``args`` carrying the span's
structured attributes plus ``span_id``/``parent_id``/``root_id``.  Load the file in
``chrome://tracing`` or https://ui.perfetto.dev.  A ``counters`` key (not
part of the Chrome schema; both viewers ignore unknown keys) embeds the
metrics-registry snapshot taken at export time.

The snapshot schema is *additive-only*: histogram stats may gain keys
(``.sum`` joined ``.count/.mean/.p50/.p90/.p99/.max``) but existing keys
keep their meaning, so traces and BENCH artifacts recorded under an older
schema still load and compare — ``benchmarks/run.py`` iterates the
recorded keys and never requires the fresh snapshot to be key-identical.

``python -m repro.obs TRACE.json [--json]`` prints a per-span-name
aggregate report (count / total / mean µs) of a saved trace.
"""
from __future__ import annotations

import json
import sys

from . import metrics as _metrics
from . import trace as _trace

__all__ = [
    "chrome_trace",
    "write_chrome_trace",
    "span_tree_lines",
    "format_report",
    "report_dict",
    "summarize_events",
    "main",
]


def _jsonable(v):
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, (tuple, list)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    return str(v)


def chrome_trace(spans=None, registry=None) -> dict:
    """Build the Chrome-trace document for ``spans`` (default: the current
    tracer's ring buffer) with ``registry``'s counter snapshot attached
    (default: the current registry)."""
    if spans is None:
        spans = _trace.spans()
    if registry is None:
        registry = _metrics.get_registry()
    t0 = min((s.t0_ns for s in spans), default=0)
    events = []
    for s in spans:
        args = {k: _jsonable(v) for k, v in s.attrs.items()}
        args["span_id"] = s.span_id
        args["parent_id"] = s.parent_id
        args["root_id"] = s.root_id
        events.append({
            "name": s.name,
            "cat": str(s.attrs.get("cat", "engine")),
            "ph": "X",
            "ts": (s.t0_ns - t0) / 1e3,
            "dur": s.dur_ns / 1e3,
            "pid": 0,
            "tid": 0,
            "args": args,
        })
    events.sort(key=lambda e: e["ts"])
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "counters": registry.snapshot(),
    }


def write_chrome_trace(path: str, spans=None, registry=None) -> dict:
    """Export to ``path``; returns the document that was written."""
    doc = chrome_trace(spans, registry)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return doc


def span_tree_lines(spans=None) -> list[str]:
    """Render the span forest as indented ``name  dur  attrs`` lines.

    Children are grouped under their parent by ``parent_id``; spans whose
    parent fell out of the ring buffer render as roots.  Within a level,
    start time orders siblings.
    """
    if spans is None:
        spans = _trace.spans()
    by_id = {s.span_id: s for s in spans}
    children: dict[int, list] = {}
    roots = []
    for s in spans:
        if s.parent_id in by_id:
            children.setdefault(s.parent_id, []).append(s)
        else:
            roots.append(s)
    lines: list[str] = []

    def walk(s, depth):
        attrs = " ".join(
            f"{k}={v}" for k, v in s.attrs.items()
            if isinstance(v, (int, float, str, bool, tuple)))
        pad = "  " * depth
        lines.append(f"{pad}{s.name}  {s.dur_ns / 1e3:.1f}us"
                     + (f"  [{attrs}]" if attrs else ""))
        for c in sorted(children.get(s.span_id, ()), key=lambda c: c.t0_ns):
            walk(c, depth + 1)

    for r in sorted(roots, key=lambda s: s.t0_ns):
        walk(r, 0)
    return lines


def summarize_events(events: list[dict]) -> dict:
    """Per-name aggregate of Chrome-trace events: count/total/mean µs."""
    agg: dict[str, dict] = {}
    for e in events:
        a = agg.setdefault(e["name"], {"count": 0, "total_us": 0.0})
        a["count"] += 1
        a["total_us"] += float(e.get("dur", 0.0))
    for a in agg.values():
        a["total_us"] = round(a["total_us"], 1)
        a["mean_us"] = round(a["total_us"] / a["count"], 1)
    return dict(sorted(agg.items(), key=lambda kv: -kv[1]["total_us"]))


def report_dict(spans=None, registry=None) -> dict:
    """Machine-readable report: span aggregates + counter snapshot."""
    doc = chrome_trace(spans, registry)
    return {
        "spans": summarize_events(doc["traceEvents"]),
        "counters": doc["counters"],
    }


def format_report(spans=None, registry=None) -> str:
    """Human-readable report: the span tree, per-name totals, counters."""
    if registry is None:
        registry = _metrics.get_registry()
    lines = ["== span tree =="]
    lines += span_tree_lines(spans) or ["(no spans recorded)"]
    rep = report_dict(spans, registry)
    lines.append("== spans by total time ==")
    for name, a in rep["spans"].items():
        lines.append(f"{a['total_us']:>12.1f}us  x{a['count']:<5d} {name}")
    lines.append("== counters ==")
    for k, v in rep["counters"].items():
        lines.append(f"{k} = {v}")
    return "\n".join(lines)


def main(argv=None) -> int:
    """CLI: summarize a saved Chrome-trace file (text or ``--json``)."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.obs",
        description="Summarize a Chrome-trace JSON exported by repro.obs")
    ap.add_argument("trace", help="path to a Chrome-trace JSON file")
    ap.add_argument("--json", action="store_true",
                    help="emit the aggregate report as JSON")
    args = ap.parse_args(argv)
    try:
        with open(args.trace) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        print(f"error: cannot read trace {args.trace!r}: {e}", file=sys.stderr)
        return 1
    if not isinstance(doc, dict):
        print(f"error: {args.trace!r} is not a Chrome-trace document",
              file=sys.stderr)
        return 1
    events = doc.get("traceEvents", [])
    rep = {"spans": summarize_events(events),
           "counters": doc.get("counters", {})}
    if args.json:
        print(json.dumps(rep, indent=1))
        return 0
    print(f"{len(events)} events")
    print("== spans by total time ==")
    for name, a in rep["spans"].items():
        print(f"{a['total_us']:>12.1f}us  x{a['count']:<5d} {name}")
    if rep["counters"]:
        print("== counters ==")
        for k, v in rep["counters"].items():
            print(f"{k} = {v}")
    return 0
