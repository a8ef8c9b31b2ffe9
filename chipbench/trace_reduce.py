"""Reduction from a JAX profiler trace to device metrics.

``load`` reads the ``.xplane.pb`` the profiler wrote into a flat list
of events ``(plane, line, name, start_ns, duration_ns)``: the device
planes' op and module lines, and the benchmark's own host annotations.
``reduce`` works on that list alone, so a test can hand it a small
recorded trace.

- window: the host annotation ``chipbench.traced`` (the profiler is on
  for exactly that span of whole macro-steps);
- busy: per device plane, the union of its op intervals inside the
  window; ``busy_s`` is averaged over the planes that ran any op;
- modules: device time per compiled program, by name without its
  ``(id)`` suffix, summed over planes;
- ops: device time per op name (the HLO text up to ``=``), summed over
  planes, loops and calls left out since the ops inside them are
  counted; ``device_ops`` holds the ten largest;
- idle gaps: the complement of busy inside the window, each gap
  labelled by the innermost benchmark annotation that covers its
  middle, summed per label, the ten largest.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

WINDOW = "chipbench.traced"
HOST_LABELS = ("chipbench.traced", "engine.step", "submit", "driver.wait",
               "train_round", "flush_trust")
DEVICE_PREFIX = "/device:"
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
# ops whose interval holds other ops (a loop, a branch, a call): counted
# as busy, not as op time, so that nothing is counted twice
CONTAINER_OPS = ("%while", "%conditional", "%call")

Event = Tuple[str, str, str, float, float]


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def load(path: str) -> List[Event]:
    from jax.profiler import ProfileData
    out: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name in OP_LINES + MODULE_LINES:
                out += [(plane.name, line.name, e.name, e.start_ns,
                         e.duration_ns) for e in line.events]
            elif not device:
                out += [(plane.name, line.name, e.name, e.start_ns,
                         e.duration_ns) for e in line.events
                        if e.name in HOST_LABELS]
    return out


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _base(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _labeller(host: List[Event]):
    """Function from a time to the innermost host annotation covering
    it ("other" where none does), by bisection over the elementary
    segments between annotation edges."""
    cuts = sorted({x for e in host for x in (e[3], e[3] + e[4])})
    labels = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        cover = [e for e in host if e[3] <= mid <= e[3] + e[4]]
        labels.append(min(cover, key=lambda e: e[4])[2] if cover
                      else "other")

    def label_at(t: float) -> str:
        i = bisect.bisect_right(cuts, t) - 1
        return labels[i] if 0 <= i < len(labels) else "other"
    return label_at


def reduce(events: List[Event]) -> Dict:
    win = [e for e in events if e[2] == WINDOW
           and not e[0].startswith(DEVICE_PREFIX)]
    if not win:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    w0 = min(e[3] for e in win)
    w1 = max(e[3] + e[4] for e in win)
    ops_by_plane: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    op_time: Dict[str, float] = defaultdict(float)
    module_time: Dict[str, float] = defaultdict(float)
    for plane, line, name, t, dur in events:
        if not plane.startswith(DEVICE_PREFIX):
            continue
        a, b = max(t, w0), min(t + dur, w1)
        if b <= a:
            continue
        if line in OP_LINES:
            ops_by_plane[plane].append((a, b))
            op = name.split(" = ")[0]
            if not op.startswith(CONTAINER_OPS):
                op_time[op] += (b - a) * 1e-9
        elif line in MODULE_LINES:
            module_time[_base(name)] += (b - a) * 1e-9
    busy = {p: _union(iv) for p, iv in ops_by_plane.items()}
    busy_s = (sum(sum(b - a for a, b in u) for u in busy.values())
              / len(busy) * 1e-9) if busy else 0.0
    label_at = _labeller([e for e in events
                          if not e[0].startswith(DEVICE_PREFIX)
                          and e[2] in HOST_LABELS])
    gaps: Dict[str, float] = defaultdict(float)
    for u in busy.values():
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[label_at((a + b) / 2)] += (b - a) * 1e-9 / len(busy)
    top = lambda d: sorted(([k, v] for k, v in d.items()),
                           key=lambda kv: -kv[1])[:10]
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy_s,
            "chips": len(busy), "modules": dict(module_time),
            "ops": dict(op_time), "device_ops": top(op_time),
            "idle_gaps": top(gaps)}


def module_seconds(summary: Dict, needle: str) -> float:
    """Device seconds of every compiled program whose name holds
    ``needle``."""
    return sum(v for k, v in summary["modules"].items() if needle in k)

