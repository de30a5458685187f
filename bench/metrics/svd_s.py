"""Seconds per truncated SVD over the whole window (client clock)."""
from harness.stats import time_per_call


def read(run):
    calls = [c for c in run.calls if c.request["kind"] == "svd"]
    return time_per_call([c.start for c in calls],
                         [c.end for c in calls if c.error is None])
