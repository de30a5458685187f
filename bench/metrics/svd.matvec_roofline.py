"""The Lanczos Gram matvec's share of its roofline: the least time of
its runs over their device time. One run reads X (n x d float32) once
and does 4 n d flops for one vector; the larger of bytes / bandwidth and
flops / peak is the least time."""
from harness import trace

PROGRAM = "jit__gram_matvec"


def read(run):
    if run.trace is None:
        return None
    seconds, runs = trace.program_seconds(run.trace, PROGRAM)
    if not runs:
        return None
    n, d = run.config["rows"], run.config["cols"]
    least = max(n * d * 4 / run.peaks["hbm_bytes_per_s"],
                4 * n * d / run.peaks["flops_per_s"])
    return 100.0 * runs * least / seconds
