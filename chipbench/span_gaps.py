"""The device's idle gaps attributed to the program's own spans.

While a profiler capture is on, the program under test opens a
``jax.profiler.TraceAnnotation`` named ``repro.<span>`` for each of its
spans (``repro.obs``), so its phases sit on the profiler's clock.
``trace_reduce`` keeps only the benchmark's own annotations and books a
whole idle gap to the annotation over its middle.  This module reads
the same trace with the program's spans kept:

- ``load``: ``trace_reduce.load``'s events plus every host event whose
  name starts with ``repro.``;
- ``attribute``: every idle gap of a device split at each annotation
  edge inside it, each piece booked to the innermost annotation over it
  (a program span where one covers it, else the benchmark's label,
  else ``other``); per program span, its seconds inside the traced
  window and its count (``spans``), and the share of its own time that
  the spans nested in it cover (``covered``).

The window and the device's busy intervals are ``trace_reduce``'s.
"""
from __future__ import annotations

import bisect
import heapq
from collections import defaultdict
from typing import Dict, List, Tuple

import trace_reduce as tr
from trace_reduce import Event

PREFIX = "repro."


def _device(e: Event) -> bool:
    return e[0].startswith(tr.DEVICE_PREFIX)


def _host_label(name: str) -> bool:
    return name in tr.HOST_LABELS or name.startswith(PREFIX)


def load(path: str) -> List[Event]:
    from jax.profiler import ProfileData
    out: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(tr.DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in tr.OP_LINES + tr.MODULE_LINES:
                continue
            out += [(plane.name, line.name, e.name, e.start_ns,
                     e.duration_ns) for e in line.events
                    if device or _host_label(e.name)]
    return out


def segments(host: List[Event]) -> Tuple[List[float], List[str]]:
    """The elementary segments between annotation edges, ``cuts[i]`` to
    ``cuts[i + 1]``, each with the innermost (shortest) annotation over
    it, or ``other``: one sweep over a heap of the open annotations."""
    cuts = sorted({x for e in host for x in (e[3], e[3] + e[4])})
    by_start = sorted(host, key=lambda e: e[3])
    open_: List[Tuple[float, float, int, str]] = []
    labels: List[str] = []
    k = 0
    for a in cuts[:-1]:
        while k < len(by_start) and by_start[k][3] <= a:
            e = by_start[k]
            heapq.heappush(open_, (e[4], e[3] + e[4], k, e[2]))
            k += 1
        while open_ and open_[0][1] <= a:
            heapq.heappop(open_)
        labels.append(open_[0][3] if open_ else "other")
    return cuts, labels


def _covered(spans: List[Event]) -> Dict[str, float]:
    """Per span name, the share of its time covered by the spans nested
    in it (the spans of one thread nest; direct children do not
    overlap)."""
    own: Dict[str, float] = defaultdict(float)
    inner: Dict[str, float] = defaultdict(float)
    by_line: Dict[Tuple[str, str], List[Event]] = defaultdict(list)
    for e in spans:
        by_line[e[0], e[1]].append(e)
    for line in by_line.values():
        stack: List[Event] = []
        for e in sorted(line, key=lambda e: (e[3], -e[4])):
            while stack and stack[-1][3] + stack[-1][4] <= e[3]:
                stack.pop()
            if stack:
                inner[stack[-1][2]] += e[4]
            own[e[2]] += e[4]
            stack.append(e)
    return {k: inner[k] / v for k, v in own.items() if v > 0}


def attribute(events: List[Event]) -> Dict:
    win = [e for e in events if e[2] == tr.WINDOW and not _device(e)]
    if not win:
        raise ValueError(f"trace has no {tr.WINDOW!r} annotation")
    w0 = min(e[3] for e in win)
    w1 = max(e[3] + e[4] for e in win)
    ops: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for plane, line, _, t, dur in events:
        if plane.startswith(tr.DEVICE_PREFIX) and line in tr.OP_LINES:
            a, b = max(t, w0), min(t + dur, w1)
            if b > a:
                ops[plane].append((a, b))
    host = [e for e in events if not _device(e) and _host_label(e[2])]
    cuts, labels = segments(host)
    gaps: Dict[str, float] = defaultdict(float)
    for plane, intervals in ops.items():
        u = tr._union(intervals)
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            lo, hi = bisect.bisect_right(cuts, a), bisect.bisect_left(cuts, b)
            points = [a] + cuts[lo:hi] + [b]
            for j, (p, q) in enumerate(zip(points, points[1:])):
                i = lo - 1 + j
                label = labels[i] if 0 <= i < len(labels) else "other"
                gaps[label] += (q - p) * 1e-9 / len(ops)
    spans: Dict[str, List] = {}
    program = [e for e in host if e[2].startswith(PREFIX)]
    for _, _, name, t, dur in program:
        a, b = max(t, w0), min(t + dur, w1)
        if b > a:
            s = spans.setdefault(name, [0.0, 0])
            s[0] += (b - a) * 1e-9
            s[1] += 1
    idle = sum(gaps.values())
    return {"window_s": (w1 - w0) * 1e-9, "idle_s": idle,
            "program_share": (sum(v for k, v in gaps.items()
                                  if k.startswith(PREFIX)) / idle
                              if idle > 0 else None),
            "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                                key=lambda kv: -kv[1]),
            "spans": spans,
            "covered": _covered([e for e in program
                                 if w0 <= e[3] < w1])}
