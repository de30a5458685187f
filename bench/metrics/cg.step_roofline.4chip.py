"""The CG step's share of its roofline per chip, in the four-chip cell,
where it moves ``cg_s``: ``cg.step_roofline``'s formula with the rows a
chip holds. Over c chips each run of the step reads its chip's rows of
Z (n / c x D float32) once and does 4 n D c' / c flops for c' classes;
c is the number of chips that ran the step in the window. The step's
device time holds its sum across the chips."""
from harness import trace

PROGRAM = "jit__lambda"


def read(run):
    if run.trace is None:
        return None
    seconds, runs = trace.program_seconds(run.trace, PROGRAM)
    if not runs:
        return None
    cfg = run.config
    n = cfg["rows"] / trace.program_chips(run.trace, PROGRAM)
    width, c = cfg["rf_dim"], cfg["classes"]
    least = max(n * width * 4 / run.peaks["hbm_bytes_per_s"],
                4 * n * width * c / run.peaks["flops_per_s"])
    return 100.0 * runs * least / seconds
