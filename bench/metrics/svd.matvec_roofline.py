"""The Lanczos Gram matvec's share of its roofline, per chip: the least
time of its runs over their device time. Over c chips each run reads
its chip's rows of X (n / c x d float32) once and does 4 n d / c flops
for one vector; the larger of bytes / bandwidth and flops / peak is the
least time. c is the number of chips that ran the program in the window
(1 on one chip, where this is the whole of X)."""
from harness import trace

PROGRAM = "jit__gram_matvec"


def read(run):
    if run.trace is None:
        return None
    seconds, runs = trace.program_seconds(run.trace, PROGRAM)
    if not runs:
        return None
    n = run.config["rows"] / trace.program_chips(run.trace, PROGRAM)
    d = run.config["cols"]
    least = max(n * d * 4 / run.peaks["hbm_bytes_per_s"],
                4 * n * d / run.peaks["flops_per_s"])
    return 100.0 * runs * least / seconds
