"""Milliseconds per Gram matvec that a chip spends in the cross-chip
reduction of the partial products: the device time of the collective
ops inside ``jit__gram_matvec`` runs in the window, the union of their
intervals on each chip, summed over the chips and divided by the runs
summed over the chips. On a v5e 2x2 the compiled program's one
collective is ``all-reduce`` (the row blocks' X_i^T (X_i v) summed);
``reduce-scatter`` and ``all-gather`` are its other forms. A window with
no runs of the program reads nothing."""
import bisect

from harness import trace

PROGRAM = "jit__gram_matvec"
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather")


def read(run):
    if run.trace is None:
        return None
    lo, hi = trace.window(run.trace)
    seconds, runs = 0.0, 0
    for mods, ops in zip(run.trace.modules, run.trace.ops):
        mine = sorted((m for m in mods
                       if m.name == PROGRAM and lo <= m.start < hi),
                      key=lambda m: m.start)
        starts = [m.start for m in mine]
        inside = []
        for op in ops:
            if not op.name.startswith(COLLECTIVES):
                continue
            i = bisect.bisect_right(starts, op.start) - 1
            if i >= 0 and op.start < mine[i].end:
                inside.append(op)
        seconds += sum(e - s for s, e in trace._union(inside, lo, hi))
        runs += len(mine)
    return 1000.0 * seconds / runs if runs else None
