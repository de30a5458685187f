"""Executables obtained inside the window per answered SVD: compiled by
XLA or loaded from the persistent cache, as the program's jit counter
counts them. After warm-up there should be none."""
from harness import counters


def read(run):
    return counters.executables_per_call(run, "svd")
