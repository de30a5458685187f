"""Counters the program keeps, read for the traced window.

The program's jit counter (``repro.core.costmodel.JIT_LOG``) is process-
wide and keeps each event's ``time.perf_counter()`` time, so the events
inside the benchmark's ``bench.window`` span (timed on the same clock)
are the window's. A program without that counter reads nothing.
"""
from __future__ import annotations

WINDOW = "bench.window"


def executables_per_call(run, kind: str) -> float | None:
    """Executables JAX obtained inside the window (compiled by XLA or
    loaded from the persistent cache), per answered call of ``kind``;
    ``None`` where the counter's log no longer holds the whole window."""
    try:
        from repro.core.costmodel import JIT_LOG
    except ImportError:
        return None
    windows = [(t0, t1) for name, t0, t1 in run.spans.items
               if name == WINDOW]
    answered = sum(1 for c in run.calls
                   if c.request["kind"] == kind and c.error is None)
    if not windows or not answered:
        return None
    events = JIT_LOG.events(*windows[-1])
    if events is None:                     # the log no longer holds it all
        return None
    return sum(1 for _, key, _ in events if key == "executables") / answered
