"""The ``svd.fused_matvecs`` reader on synthetic traces: runs of the Gram
matvec program that hold the one-pass kernel, per answered SVD."""
import os
from types import SimpleNamespace

from harness.spec import load_module
from harness.trace import Interval, Trace

READ = load_module(os.path.join(
    os.path.dirname(os.path.dirname(__file__)), "metrics",
    "svd.fused_matvecs.py")).read


def _run(modules, ops, answered=2):
    calls = [SimpleNamespace(request={"kind": "svd"}, error=None)
             for _ in range(answered)]
    return SimpleNamespace(
        trace=Trace(modules=[modules], ops=[ops],
                    spans=[Interval("bench.window", 0, 10)]),
        calls=calls)


def test_counts_runs_that_hold_the_kernel():
    """Three Gram matvec runs in the window, two of them with the kernel;
    a run before the window and a kernel op outside any run count
    nothing."""
    modules = [Interval("jit__gram_matvec", -2, -1),
               Interval("jit__gram_matvec", 1, 2),
               Interval("jit__gram_matvec", 3, 4),
               Interval("jit__gram_matvec", 5, 6),
               Interval("jit_matmul", 7, 8)]
    ops = [Interval("gram_matvec_pallas.1", -1.5, -1.2),
           Interval("copy", 1.0, 1.1),
           Interval("gram_matvec_pallas.1", 1.1, 1.9),
           Interval("gram_matvec_pallas", 3.1, 3.9),
           Interval("multiply_reduce_fusion", 5.1, 5.5),
           Interval("gram_matvec_pallas.1", 7.1, 7.5),
           Interval("gram_matvec_pallas.1", 8.5, 8.6)]
    assert READ(_run(modules, ops)) == 1.0


def test_two_pass_program_reads_zero():
    modules = [Interval("jit__gram_matvec", 1, 2)]
    ops = [Interval("multiply_reduce_fusion", 1.0, 1.5),
           Interval("multiply_reduce_fusion.1", 1.5, 2.0)]
    assert READ(_run(modules, ops)) == 0.0


def test_reads_nothing_without_a_trace_or_an_answer():
    assert READ(SimpleNamespace(trace=None, calls=[])) is None
    assert READ(_run([], [], answered=0)) is None
