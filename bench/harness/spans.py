"""The trace reduction over the program's own host spans.

``harness/trace.py`` keeps only the benchmark's ``bench.*`` spans. The
program writes spans of its own into the same trace (``alchemist.*``,
named in ``repro.core.tracing``), on the same host plane and the same
clock. ``trace.load`` keeps both while ``trace.SPAN_PREFIX`` names both
prefixes (``idle.program_spans``), and the functions here answer the
questions those spans were put in for: how long the device was idle
inside a span, how long a span took, how long from one span to the
next, and who owns every idle gap of the window. Each reads chip 0, as
``trace.idle_gaps`` does, and only what lies inside the window.
"""
from __future__ import annotations

import bisect
import collections
import statistics

from harness import trace
from harness.trace import Interval, Trace


def _busy(t: Trace, lo: float, hi: float) -> list[tuple[float, float]]:
    return trace._union(t.ops[0], lo, hi) if t.chips else []


def _instances(t: Trace, name: str) -> list[Interval]:
    """The spans of ``name`` that start inside the window, in order."""
    lo, hi = trace.window(t)
    return sorted((s for s in t.spans
                   if s.name == name and lo <= s.start < hi),
                  key=lambda s: s.start)


def gaps(t: Trace) -> list[tuple[float, float]]:
    """Every idle gap of chip 0 inside the window, in order."""
    lo, hi = trace.window(t)
    out, at = [], lo
    for s, e in _busy(t, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return out


def idle_by_label(t: Trace) -> list[list]:
    """All idle seconds of the window summed by owner. Each gap is cut at
    the edges of the spans it crosses, and each piece goes to the
    innermost span (other than the window) over it, or to ``host`` when
    none is, so the pieces sum to the window's idle time. The largest
    first."""
    spans = sorted((s for s in t.spans if s.name != trace.WINDOW_SPAN),
                   key=lambda s: s.start)
    edges = sorted({x for s in spans for x in (s.start, s.end)})
    total: collections.Counter = collections.Counter()
    active: list[Interval] = []
    nxt = 0
    for g0, g1 in gaps(t):                 # pieces in time order
        cuts = [g0, *edges[bisect.bisect_right(edges, g0):
                            bisect.bisect_left(edges, g1)], g1]
        for p0, p1 in zip(cuts, cuts[1:]):
            mid = (p0 + p1) / 2            # no edge lies inside a piece
            while nxt < len(spans) and spans[nxt].start <= mid:
                active.append(spans[nxt])
                nxt += 1
            active = [s for s in active if s.end > mid]
            name = min(active, key=lambda s: s.end - s.start).name \
                if active else "host"
            total[name] += p1 - p0
    return [[name, secs] for name, secs in total.most_common()]


def idle_within(t: Trace, name: str) -> float:
    """Seconds in which chip 0 ran nothing, inside the instances of span
    ``name`` (clipped to the window), summed over the instances."""
    lo, hi = trace.window(t)
    busy = _busy(t, lo, hi)
    total = 0.0
    for s in _instances(t, name):
        s0, s1 = s.start, min(s.end, hi)
        covered = sum(max(0.0, min(e, s1) - max(b, s0)) for b, e in busy)
        total += (s1 - s0) - covered
    return total


def span_seconds(t: Trace, name: str) -> float:
    """Summed seconds of the instances of span ``name``."""
    return sum(s.end - s.start for s in _instances(t, name))


def time_to_next(t: Trace, first: str, then: str) -> list[float]:
    """For each instance of span ``first``, the seconds from its start to
    the start of the first span ``then`` at or after it; instances with
    none after them give nothing."""
    starts = [s.start for s in sorted(
        (s for s in t.spans if s.name == then), key=lambda s: s.start)]
    out = []
    for s in _instances(t, first):
        i = bisect.bisect_left(starts, s.start)
        if i < len(starts):
            out.append(starts[i] - s.start)
    return out


def device_lag(t: Trace, span: str, program: str) -> float | None:
    """Median seconds from each instance of ``span`` to the start of the
    run of ``program`` on chip 0 nearest to it: the host's dispatch
    latency plus the offset between the device's clock and the host's
    (negative when the device's clock reads behind)."""
    runs = sorted(m.start for m in (t.modules[0] if t.chips else [])
                  if m.name == program)
    lags = []
    for s in _instances(t, span):
        i = bisect.bisect_left(runs, s.start)
        near = [runs[j] for j in (i - 1, i) if 0 <= j < len(runs)]
        if near:
            lags.append(min(near, key=lambda r: abs(r - s.start))
                        - s.start)
    return statistics.median(lags) if lags else None


def shifted(t: Trace, seconds: float) -> Trace:
    """The trace with every device interval moved by ``seconds``: puts
    the device's clock on the host's when the two are ``-seconds``
    apart."""
    def move(chips):
        return [[Interval(iv.name, iv.start + seconds, iv.end + seconds)
                 for iv in chip] for chip in chips]
    return Trace(move(t.modules), move(t.ops), t.spans)
