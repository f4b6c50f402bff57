"""Reduces a profiler trace (``.xplane.pb``) of one traced stretch to the
numbers the per-layer metrics read.

- The traced window is the host span ``bench.traced`` that the harness
  opens around the traced stretch.
- Busy time of a device is the union of the intervals in which an
  operation ran on it (its ``XLA Ops`` line), clipped to the window;
  ``busy_s`` is the mean over the devices that ran anything.
- Device time per program is the sum of its executions on the
  ``XLA Modules`` line.  Device time per operation kind is the sum of
  the self time (its duration less that of the operations nested in it,
  as a loop's body is) of every execution on the ``XLA Ops`` line, keyed
  by the instruction's name without its ``%`` and numeric suffix
  (``fusion``, ``copy``, a kernel's own name).
- An idle gap is a stretch of the window in which no device ran an
  operation.  Each of the longest is named by the host span (``bench.*``
  or ``engine.*``) that covers most of it, the innermost on a tie.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.traced"
HOST_SPAN_PREFIXES = ("bench.", "engine.")
_MODULE_ID = re.compile(r"\(\d+\)$")
_OP_ID = re.compile(r"\.\d+$")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                           # mean over active devices
    devices: int
    programs: Dict[str, Tuple[float, int]]  # name -> (device s, executions)
    ops: Dict[str, Tuple[float, int]]       # kind -> (self s, executions)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    def program_seconds(self, pattern: str) -> Tuple[float, int]:
        """Device seconds and executions of the programs whose name
        matches ``pattern`` (a regular expression)."""
        rx = re.compile(pattern)
        hits = [v for k, v in self.programs.items() if rx.search(k)]
        return sum(s for s, _ in hits), sum(n for _, n in hits)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def op_kind(name: str) -> str:
    """``%fusion.279 = s32[4] fusion(...)`` -> ``fusion``."""
    return _OP_ID.sub("", name.split(" = ", 1)[0].lstrip("%"))


def _self_times(events, ops):
    """Add each event's self time to ``ops[kind]``; ``events`` are
    (start, end, name) of one device's op line, which nest."""
    events.sort(key=lambda e: (e[0], -e[1]))
    stack = []
    for a, b, name in events:
        while stack and stack[-1][1] <= a:
            stack.pop()
        o = ops[op_kind(name)]
        o[0] += b - a
        o[1] += 1
        if stack:
            ops[op_kind(stack[-1][2])][0] -= b - a
        stack.append((a, b, name))


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def reduce(path: str, top_gaps: int = 10) -> TraceSummary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window = None
    spans: List[Tuple[float, float, str]] = []
    device_ops: List[List[Tuple[float, float]]] = []
    programs: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    ops: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name.startswith(HOST_SPAN_PREFIXES):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
        elif _DEVICE_PLANE.match(plane.name):
            iv = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name) for ev in line.events]
                    _self_times(evs, ops)
                    iv += [(a, b) for a, b, _ in evs]
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        p = programs[_MODULE_ID.sub("", ev.name)]
                        p[0] += ev.duration_ns
                        p[1] += 1
            if iv:
                device_ops.append(iv)
    if window is None:
        raise ValueError(f"trace {path} has no {WINDOW_SPAN!r} host span")
    lo, hi = window
    busy = [sum(b - a for a, b in _clip(_union(iv), lo, hi))
            for iv in device_ops]
    any_busy = _clip(_union([x for iv in device_ops for x in iv]), lo, hi)
    gaps, prev = [], lo
    for a, b in any_busy + [(hi, hi)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_name_gap(g, spans), (g[1] - g[0]) / 1e9)
             for g in gaps[:top_gaps]]
    return TraceSummary(
        window_s=(hi - lo) / 1e9,
        busy_s=(sum(busy) / len(busy) / 1e9) if busy else 0.0,
        devices=len(busy),
        programs={k: (v[0] / 1e9, int(v[1])) for k, v in programs.items()},
        ops={k: (v[0] / 1e9, int(v[1])) for k, v in ops.items()},
        gaps=named)


def _name_gap(gap, spans) -> str:
    a, b = gap
    best, best_key = "host (no span)", None
    for s, e, name in spans:
        ov = min(b, e) - max(a, s)
        if ov <= 0:
            continue
        key = (ov, -(e - s))        # most overlap, then the innermost
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def breakdown(t: TraceSummary, top: int = 10) -> dict:
    """The ``breakdown`` entry of a traced run's result line."""
    ops = sorted(t.ops.items(), key=lambda kv: -kv[1][0])[:top]
    return {"device_ops": [[k, v[0]] for k, v in ops],
            "idle_gaps": [[n, s] for n, s in t.gaps[:top]]}
