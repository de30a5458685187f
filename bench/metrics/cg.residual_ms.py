"""Device milliseconds per answered CG job, per chip, of the true
residual's program (``jit__cg_residual``): one pass over each chip's own
rows of Z in blocks, and one sum across the chips. A residual that
gathered Z onto every chip would not fit a chip at the four-chip cell's
size, and one that read Z more than once would show here."""
from harness import trace

PROGRAM = "jit__cg_residual"


def read(run):
    if run.trace is None:
        return None
    seconds, runs = trace.program_seconds(run.trace, PROGRAM)
    answered = sum(1 for c in run.calls
                   if c.request["kind"] == "cg_job" and c.error is None)
    if not runs or not answered:
        return None
    chips = trace.program_chips(run.trace, PROGRAM)
    return 1000.0 * seconds / chips / answered
