"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

On a TPU the trace holds one plane per chip (``/device:TPU:<i>``) with an
``XLA Modules`` line (one event per program run, named
``jit_<function>(<fingerprint>)``) and an ``XLA Ops`` line (one event per
operation), and a host plane (``/host:CPU``) whose lines carry the
benchmark's own ``bench.*`` annotations. The reduction reads nothing
else, so it works on any trace that has those names.

* busy time: the union of the intervals in which an operation ran on a
  chip, clipped to the traced window, averaged over the chips;
* program time: summed device durations of one program's module events,
  and the chips that ran it;
* idle gaps: the spaces between busy intervals inside the window, each
  labelled by the innermost ``bench.*`` host span around its midpoint.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
MODULES = "XLA Modules"
OPS = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Interval:
    name: str
    start: float      # seconds on the trace's clock
    end: float


@dataclasses.dataclass
class Trace:
    """What the reduction keeps of a trace: per chip, its program runs
    and its op intervals; and the host's ``bench.*`` spans."""
    modules: list[list[Interval]]
    ops: list[list[Interval]]
    spans: list[Interval]

    @property
    def chips(self) -> int:
        return len(self.ops)


def program_name(event_name: str) -> str:
    """``jit__gram_matvec(1356...)`` -> ``jit__gram_matvec``."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """``%fusion.1 = f32[...] fusion(...)`` -> ``fusion.1``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    modules, ops, spans = [], [], []
    for plane in sorted(data.planes, key=lambda p: p.name):
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            modules.append(_intervals(lines.get(MODULES), program_name))
            ops.append(_intervals(lines.get(OPS), op_name))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [iv for iv in _intervals(line, str)
                          if iv.name.startswith(SPAN_PREFIX)]
    return Trace(modules, ops, spans)


def _intervals(line, name_of) -> list[Interval]:
    if line is None:
        return []
    return [Interval(name_of(ev.name), ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9)
            for ev in line.events]


def window(trace: Trace) -> tuple[float, float]:
    """The traced window: the benchmark's ``bench.window`` span."""
    spans = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if not spans:
        raise ValueError("the trace holds no bench.window span")
    return spans[0].start, spans[0].end


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for iv in sorted(intervals, key=lambda i: i.start):
        s, e = max(iv.start, lo), min(iv.end, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    lo, hi = window(trace)
    if not trace.chips:
        return 0.0
    return sum(sum(e - s for s, e in _union(ops, lo, hi))
               for ops in trace.ops) / trace.chips


def idle_share(trace: Trace) -> float:
    lo, hi = window(trace)
    return 1.0 - busy_seconds(trace) / (hi - lo)


def program_seconds(trace: Trace, name: str) -> tuple[float, int]:
    """Device seconds and runs of one program inside the window, summed
    over the chips and the runs."""
    lo, hi = window(trace)
    runs = [m for chip in trace.modules for m in chip
            if m.name == name and lo <= m.start < hi]
    return sum(m.end - m.start for m in runs), len(runs)


def program_chips(trace: Trace, name: str) -> int:
    """How many chips ran ``name`` inside the window: the chips that one
    run of a program sharded over them spans."""
    lo, hi = window(trace)
    return sum(any(m.name == name and lo <= m.start < hi for m in chip)
               for chip in trace.modules)


def top_ops(trace: Trace, limit: int = 10) -> list[list]:
    """The device operations that took most time in the window, each
    named ``<program>/<op>`` and given its seconds summed over runs and
    averaged over chips."""
    lo, hi = window(trace)
    total: collections.Counter = collections.Counter()
    for mods, ops in zip(trace.modules, trace.ops):
        mods = sorted(mods, key=lambda m: m.start)
        starts = [m.start for m in mods]
        for op in ops:
            if not lo <= op.start < hi:
                continue
            i = bisect.bisect_right(starts, op.start) - 1
            prog = mods[i].name if i >= 0 and op.start < mods[i].end \
                else "?"
            total[f"{prog}/{op.name}"] += op.end - op.start
    chips = max(1, trace.chips)
    return [[name, secs / chips] for name, secs in total.most_common(limit)]


def idle_gaps(trace: Trace, limit: int = 10) -> list[list]:
    """The longest idle gaps on chip 0 inside the window, each labelled by
    the innermost ``bench.*`` span (other than the window) that holds the
    gap's midpoint, or ``host`` when none does."""
    lo, hi = window(trace)
    busy = _union(trace.ops[0], lo, hi) if trace.chips else []
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    spans = [s for s in trace.spans if s.name != WINDOW_SPAN]
    out = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:limit]:
        mid = (g0 + g1) / 2
        around = [s for s in spans if s.start <= mid < s.end]
        best = min(around, key=lambda s: s.end - s.start).name \
            if around else "host"
        out.append([best, g1 - g0])
    return out
