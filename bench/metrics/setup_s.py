"""Set-up seconds: process start, JAX and chip start-up, engine and
server start, data, and one warm-up call per shape (compiles included)."""


def read(run):
    return run.setup_s
