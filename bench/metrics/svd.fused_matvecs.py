"""Gram matvecs per answered SVD inside the traced window that ran the
one-pass kernel: device runs of ``jit__gram_matvec`` that hold the
kernel's op (``gram_matvec_pallas``), summed over the chips. Where every
matvec took XLA's two passes it reads 0."""
import bisect

from harness import trace

PROGRAM = "jit__gram_matvec"
KERNEL = "gram_matvec_pallas"


def read(run):
    if run.trace is None:
        return None
    answered = sum(1 for c in run.calls
                   if c.request["kind"] == "svd" and c.error is None)
    if not answered:
        return None
    lo, hi = trace.window(run.trace)
    fused = 0
    for mods, ops in zip(run.trace.modules, run.trace.ops):
        runs = sorted((m for m in mods
                       if m.name == PROGRAM and lo <= m.start < hi),
                      key=lambda m: m.start)
        starts = [m.start for m in runs]
        holding = set()
        for op in ops:
            if op.name.split(".", 1)[0] != KERNEL:
                continue
            i = bisect.bisect_right(starts, op.start) - 1
            if i >= 0 and op.start < runs[i].end:
                holding.add(i)
        fused += len(holding)
    return fused / answered
