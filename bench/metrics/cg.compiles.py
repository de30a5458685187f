"""Executables obtained inside the window per answered CG job: compiled
by XLA or loaded from the persistent cache, as the program's jit counter
counts them. A jit built anew in every call shows here."""
from harness import counters


def read(run):
    return counters.executables_per_call(run, "cg_job")
