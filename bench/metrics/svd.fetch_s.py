"""Seconds per answered SVD to fetch U, S and V to the client (client
clock): the benchmark's ``bench.fetch`` spans that start inside the
window, summed, over the SVDs answered. U is gathered from every chip
that holds its rows and sent over the wire."""


def read(run):
    windows = [(t0, t1) for name, t0, t1 in run.spans.items
               if name == "bench.window"]
    answered = sum(1 for c in run.calls
                   if c.request["kind"] == "svd" and c.error is None)
    if not windows or not answered:
        return None
    lo, hi = windows[-1]
    return sum(t1 - t0 for name, t0, t1 in run.spans.items
               if name == "bench.fetch" and lo <= t0 < hi) / answered
