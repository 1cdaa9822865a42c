"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`load` flattens it to :class:`Event` rows; everything else works on
those rows, so the test can feed a small recorded trace.

* Device operations are the events of the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane, named by their HLO instruction (a TPU trace
  names an operation by its whole HLO text, ``%fusion.3 = f32[..] ...``).
  Busy time is the union of their intervals.
* An operation's executable is its ``hlo_module`` stat where it has one,
  else the event of the plane's ``XLA Modules`` line that holds it.
* The harness marks its own spans with ``jax.profiler.TraceAnnotation``
  named ``bench:<span>``; they land on a host plane on the same clock, and
  ``bench:window`` bounds the measured window.
* An idle gap is a stretch of the window in which no device operation
  runs; it is named by the innermost harness span around its midpoint, or
  ``between_calls`` where none is.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float
    module: str          # the executable an operation belongs to, or ""


def op_name(name: str) -> str:
    """The HLO instruction's name of an operation event's name."""
    if name.startswith("%") and " = " in name:
        return name[1:name.index(" = ")]
    return name


def _modules_by_span(events: list[Event]) -> list[Event]:
    """Give each operation without a module the ``XLA Modules`` event of
    its plane that holds its start."""
    mods = {}
    for e in events:
        if e.line == MODULES_LINE:
            mods.setdefault(e.plane, []).append((e.start_ns, e.end_ns, e.name))
    for rows in mods.values():
        rows.sort()
    out = []
    for e in events:
        if e.line == OPS_LINE and not e.module and e.plane in mods:
            rows = mods[e.plane]
            i = bisect.bisect_right(rows, (e.start_ns, float("inf"), "")) - 1
            if i >= 0 and rows[i][0] <= e.start_ns < rows[i][1]:
                e = e._replace(module=rows[i][2])
        out.append(e)
    return out


def load(trace_dir: str) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            keep_ops = device and line.name == OPS_LINE
            keep = device and line.name in (OPS_LINE, MODULES_LINE)
            for ev in line.events:
                if not keep and not ev.name.startswith(SPAN_PREFIX):
                    continue
                module = ""
                if keep_ops:
                    for k, v in ev.stats:
                        if k == "hlo_module":
                            module = str(v)
                            break
                name = op_name(ev.name) if keep_ops else ev.name
                out.append(Event(plane.name, line.name, name,
                                 float(ev.start_ns), float(ev.end_ns),
                                 module))
    return _modules_by_span(out)


def save(events: list[Event], path: str) -> None:
    """Write events as gzipped JSON lines, one ``Event`` per line."""
    with gzip.open(path, "wt") as f:
        for e in events:
            f.write(json.dumps(list(e)) + "\n")


def read_saved(path: str) -> list[Event]:
    """The events :func:`save` wrote."""
    with gzip.open(path, "rt") as f:
        return [Event(*json.loads(line)) for line in f]


def union(intervals):
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


class Reduction(NamedTuple):
    window_s: float
    busy_s: float                 # mean over the chips used
    chips: int
    op_s: dict                    # operation name -> summed device seconds
    module_s: dict                # executable -> seconds its operations ran
    gaps: list                    # [(span name, seconds)], longest first
    spans: list                   # [(span name, start_ns, end_ns)]


def reduce(events: list[Event]) -> Reduction:
    spans = [(e.name[len(SPAN_PREFIX):], e.start_ns, e.end_ns)
             for e in events if e.name.startswith(SPAN_PREFIX)]
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one bench:window span, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    ops = [e for e in events if e.line == OPS_LINE
           and DEVICE_PLANE.match(e.plane)]
    planes = sorted({e.plane for e in ops})
    if not planes:
        raise ValueError("the trace holds no device operation")
    busy, op_s = [], {}
    gaps = []
    for plane in planes:
        mine = [(e.start_ns, e.end_ns) for e in ops if e.plane == plane]
        merged = union(_clip(mine, lo, hi))
        busy.append(sum(e - s for s, e in merged))
        if plane == planes[0]:
            edges = [lo] + [x for se in merged for x in se] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    by_module = {}
    for e in ops:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            op_s[e.name] = op_s.get(e.name, 0.0) + (t - s) * 1e-9
            if e.module:
                by_module.setdefault(e.module, []).append((s, t))
    # an executable's operations nest (a while loop holds its body's
    # fusions), so its time is the union of their intervals, not the sum
    module_s = {m: sum(t - s for s, t in union(iv)) * 1e-9
                for m, iv in by_module.items()}
    inner = [(n, s, e) for n, s, e in spans if n != "window"]

    def name_of(s, e):
        mid = 0.5 * (s + e)
        around = [(e2 - s2, n) for n, s2, e2 in inner if s2 <= mid <= e2]
        return min(around)[1] if around else "between_calls"

    named = sorted(((name_of(s, e), (e - s) * 1e-9) for s, e in gaps),
                   key=lambda x: -x[1])
    return Reduction(window_s=(hi - lo) * 1e-9,
                     busy_s=sum(busy) / len(busy) * 1e-9, chips=len(planes),
                     op_s=op_s, module_s=module_s, gaps=named, spans=spans)


def breakdown(red: Reduction, n: int = 10) -> dict:
    """The ``breakdown`` of a traced run's result line."""
    top = sorted(red.op_s.items(), key=lambda x: -x[1])[:n]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in red.gaps[:n]]}
