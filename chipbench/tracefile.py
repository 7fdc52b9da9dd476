"""Reduce one profiler trace to device busy time, idle gaps and op times.

The profiler writes an ``.xplane.pb`` file.  :func:`load_xplane` turns it
into plain tuples; everything after that is arithmetic on intervals, kept
apart so that it can be checked on a small synthetic trace without a chip:

* ``ops``: per device plane, ``(name, module, start_ns, end_ns)`` for every
  operation that ran on the device (the plane's "XLA Ops" line), with the
  XLA module (jitted program) that was running around it;
* ``spans``: the host annotations of interest, ``(name, start_ns, end_ns)``.

Host and device events share one clock in the trace.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

COLLECTIVE_RE = re.compile(
    r"all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute")


def load_xplane(log_dir: str, span_names) -> tuple[dict, list]:
    """``(ops, spans)`` from the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    wanted = set(span_names)
    ops: dict[str, list] = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: [(e.name, e.start_ns, e.end_ns)
                                 for e in line.events]
                     for line in plane.lines}
            if "XLA Ops" in lines:
                ops[plane.name] = assign_modules(
                    lines["XLA Ops"], lines.get("XLA Modules", []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events if e.name in wanted)
    return ops, sorted(spans, key=lambda s: s[1])


def assign_modules(ops, modules) -> list:
    """Tag each ``(name, start, end)`` op with the module that contains
    its start (``""`` when none does)."""
    mods = sorted(modules, key=lambda m: m[1])
    out, j = [], 0
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        while j < len(mods) and mods[j][2] <= s:
            j += 1
        mod = mods[j][0] if j < len(mods) and mods[j][1] <= s else ""
        out.append((name, mod, s, e))
    return out


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    merged: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def covered(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(intervals, lo, hi))


def gaps(intervals, lo: float, hi: float) -> list:
    """The idle intervals of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in union(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap_list, spans) -> dict:
    """Idle time per host activity: each gap goes to the innermost span
    (the shortest) that contains its midpoint, else to ``"(no span)"``."""
    out: dict = defaultdict(float)
    for s, e in gap_list:
        mid = (s + e) / 2
        inside = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        label = (min(inside, key=lambda sp: sp[2] - sp[1])[0] if inside
                 else "(no span)")
        out[label] += e - s
    return dict(out)


def reduce_trace(ops: dict, spans: list, lo: float, hi: float,
                 exclude_modules=()) -> dict:
    """Per-chip means over the device planes in ``ops``, in seconds.

    ``busy_s``: union of all op intervals in ``[lo, hi]``; ``work_s``: union
    of the ops whose module name contains none of ``exclude_modules``;
    ``collective_s``: union of collective ops; ``op_s``: time per
    ``module/op`` label;
    ``idle_by_span``: idle time per host activity.
    """
    if not ops:
        return {}
    n = len(ops)
    busy = work = coll = 0.0
    op_s: dict = defaultdict(float)
    idle: dict = defaultdict(float)
    for dev_ops in ops.values():
        all_iv = [(s, e) for _, _, s, e in dev_ops]
        busy += covered(all_iv, lo, hi)
        work += covered([(s, e) for _, m, s, e in dev_ops
                         if not any(x in m for x in exclude_modules)], lo, hi)
        coll += covered([(s, e) for name, _, s, e in dev_ops
                         if COLLECTIVE_RE.search(name)], lo, hi)
        for name, mod, s, e in dev_ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_s[op_label(name, mod)] += d
        for label, t in attribute(gaps(all_iv, lo, hi), spans).items():
            idle[label] += t
    ns = 1e-9 / n
    return {"chips": n, "window_s": (hi - lo) * 1e-9, "busy_s": busy * ns,
            "work_s": work * ns, "collective_s": coll * ns,
            "op_s": {k: v * ns for k, v in op_s.items()},
            "idle_by_span": {k: v * ns for k, v in idle.items()}}


def op_label(name: str, module: str) -> str:
    """``module/op``: the jitted program without its hash, and the HLO
    instruction's name without its shapes and operands."""
    return f"{re.sub(r'[(][0-9]+[)]$', '', module)}/{name.split(' = ')[0]}"


def breakdown(summary: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the longest device ops and idle time
    by host activity, each at most ``top`` entries, longest first."""
    def head(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"device_ops": head(summary.get("op_s", {})),
            "idle_gaps": head(summary.get("idle_by_span", {}))}
