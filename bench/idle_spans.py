"""Puts the device-idle time of a traced stretch down to the program's
host spans, and sums the time inside each span.

- The stretch is the host span ``bench.traced`` (``trace_reduce``'s
  window); the main thread is the host line that holds it.
- Device busy time is the union of every device's ``XLA Ops`` intervals,
  as ``trace_reduce`` takes it; the rest of the stretch is idle.
- ``idle_by_span``: each idle instant goes to the innermost ``bench.*`` or
  ``engine.*`` span on the main thread that covers it (the one opened
  last), or to ``"host (no span)"``.  It sums to window less busy.
- ``span_s``: each span name's total time on the main thread, clipped
  to the stretch.

Readers of per-layer metrics call ``of(ctx)``, which parses the traced
run's profile once, into ``ctx.spans``, however many readers ask.
"""
from __future__ import annotations

import os
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import trace_reduce as tr

NO_SPAN = "host (no span)"
# the harness's trace directory (run.TRACE_DIR)
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench_scratch", "trace")


@dataclass
class SpanSummary:
    window_s: float
    idle_s: float
    idle_by_span: Dict[str, float]
    span_s: Dict[str, float]

    def has(self, prefix: str) -> bool:
        """Whether any span of the stretch starts with ``prefix``."""
        return any(n.startswith(prefix) for n in self.span_s)

    def idle_under(self, *prefixes: str) -> float:
        return sum(s for n, s in self.idle_by_span.items()
                   if n.startswith(prefixes))


def attribute(idle: List[Tuple[int, int]],
              spans: List[Tuple[int, int, str]]) -> Dict[str, float]:
    """Seconds of the ``idle`` intervals (ns, disjoint) under each
    innermost covering span of ``spans`` ((start, end, name), ns)."""
    ev = []
    for i, (a, b, _) in enumerate(spans):
        ev += [(a, 1, i), (b, 0, i)]
    for a, b in idle:
        ev += [(a, 3, -1), (b, 2, -1)]
    ev.sort()
    open_, out = {}, defaultdict(float)
    inner, idle_on, last = NO_SPAN, False, None
    for t, kind, i in ev:
        if idle_on and t > last:
            out[inner] += (t - last) / 1e9
        last = t
        if kind >= 2:
            idle_on = kind == 3
            continue
        if kind == 1:
            open_[i] = spans[i]
        else:
            open_.pop(i, None)
        inner = (max(open_.values(), key=lambda s: (s[0], -s[1]))[2]
                 if open_ else NO_SPAN)
    return dict(out)


def summarize(path: str) -> SpanSummary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window, main, device = None, [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                       for ev in line.events]
                hit = [e for e in evs if e[2] == tr.WINDOW_SPAN]
                if hit:
                    window = hit[0][:2]
                    main = [e for e in evs
                            if e[2].startswith(tr.HOST_SPAN_PREFIXES)
                            and e[2] != tr.WINDOW_SPAN]
        elif tr._DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                               for ev in line.events]
    if window is None:
        raise ValueError(f"trace {path} has no {tr.WINDOW_SPAN!r} host span")
    lo, hi = window
    busy = tr._clip(tr._union(device), lo, hi)
    idle, prev = [], lo
    for a, b in busy + [(hi, hi)]:
        if a > prev:
            idle.append((prev, a))
        prev = max(prev, b)
    spans = [(max(a, lo), min(b, hi), n) for a, b, n in main
             if b > lo and a < hi]
    span_s: Dict[str, float] = defaultdict(float)
    for a, b, n in spans:
        span_s[n] += (b - a) / 1e9
    return SpanSummary(window_s=(hi - lo) / 1e9,
                       idle_s=sum(b - a for a, b in idle) / 1e9,
                       idle_by_span=attribute(idle, spans),
                       span_s=dict(span_s))


def of(ctx) -> Optional[SpanSummary]:
    """The traced stretch's span summary: ``ctx.spans`` where it is set,
    else the harness's profile, parsed and kept as ``ctx.spans``; None
    for an untraced run or one without a profile."""
    if getattr(ctx, "spans", None) is None:
        if getattr(ctx, "trace", None) is None:
            return None
        try:
            ctx.spans = summarize(tr.find_xplane(TRACE_DIR))
        except FileNotFoundError:
            return None
    return ctx.spans
