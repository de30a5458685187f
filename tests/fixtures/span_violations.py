"""Fixture for the TRC001 host-span check: parsed as source by
``tests/test_tracing.py``, never imported."""
import jax

from repro.core import tracing


@jax.jit
def span_in_jit(x):
    with tracing.span(tracing.CG_STEP):                  # TRC001
        y = x * 2
    with jax.profiler.TraceAnnotation("inner"):          # TRC001
        return y + 1


def host_loop(x):
    with tracing.span(tracing.CG_STEP):                  # host code: fine
        return span_in_jit(x)
