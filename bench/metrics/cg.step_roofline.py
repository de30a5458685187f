"""The CG step's share of its roofline: the least time of its runs over
their device time. One step reads Z (n x D float32) once and does
4 n D c flops for c classes; the larger of bytes / bandwidth and
flops / peak is the least time. The step program is the jit of a lambda
in the program's CG driver, so it is found by that name."""
from harness import trace

PROGRAM = "jit__lambda"


def read(run):
    if run.trace is None:
        return None
    seconds, runs = trace.program_seconds(run.trace, PROGRAM)
    if not runs:
        return None
    cfg = run.config
    n, width, c = cfg["rows"], cfg["rf_dim"], cfg["classes"]
    least = max(n * width * 4 / run.peaks["hbm_bytes_per_s"],
                4 * n * width * c / run.peaks["flops_per_s"])
    return 100.0 * runs * least / seconds
