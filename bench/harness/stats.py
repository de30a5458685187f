"""Metric arithmetic on host timestamps: whole-window rates and tails
over all requests.

``percentile`` is the nearest-rank percentile that the program's cost
model uses for its latency tables, copied here so that a change to the
program cannot move the yardstick.
"""
from __future__ import annotations


def percentile(values, q: float) -> float | None:
    """Nearest-rank percentile (q in [0, 100]); None for no values."""
    if not values:
        return None
    vals = sorted(values)
    rank = max(0, min(len(vals) - 1,
                      int(round(q / 100.0 * (len(vals) - 1)))))
    return float(vals[rank])


def time_per_call(starts, ends) -> float | None:
    """Seconds per completed call over the whole window: from the first
    call's start to the last completion, divided by the calls completed,
    so that no partial call rounds the rate."""
    if not ends:
        return None
    return (max(ends) - min(starts)) / len(ends)
