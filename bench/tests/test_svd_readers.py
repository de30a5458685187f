"""The ``svd.reduce_ms`` and ``svd.fetch_s`` readers on synthetic traces
and spans with known answers."""
import os
from types import SimpleNamespace

import pytest

from harness.served import Call
from harness.spec import load_module
from harness.trace import Interval, Trace

METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "metrics")
REDUCE = load_module(os.path.join(METRICS, "svd.reduce_ms.py")).read
FETCH = load_module(os.path.join(METRICS, "svd.fetch_s.py")).read


def _chip(shift: float):
    """One chip's runs of the Gram matvec in a [0, 10] s window: two in
    it, one before it. The first holds an all-reduce of 2 ms as an async
    start and done that overlap, the second a 1 ms reduce-scatter and a
    1 ms all-gather, plus a fusion. An all-reduce outside any run and one
    in another program's run count nothing."""
    t = shift
    modules = [Interval("jit__gram_matvec", -2, -1),
               Interval("jit__gram_matvec", 1 + t, 1.1 + t),
               Interval("jit__gram_matvec", 2 + t, 2.1 + t),
               Interval("jit_matmul", 5, 6)]
    ops = [Interval("all-reduce", -1.9, -1.5),
           Interval("multiply_reduce_fusion", 1 + t, 1.05 + t),
           Interval("all-reduce-start", 1.05 + t, 1.056 + t),
           Interval("all-reduce-done", 1.055 + t, 1.057 + t),
           Interval("multiply_reduce_fusion.1", 2 + t, 2.05 + t),
           Interval("reduce-scatter.3", 2.05 + t, 2.051 + t),
           Interval("all-gather.1", 2.051 + t, 2.052 + t),
           Interval("all-reduce", 3, 3.5),
           Interval("all-reduce", 5.1, 5.2)]
    return modules, ops


def _run(chips):
    pairs = [_chip(0.01 * i) for i in range(chips)]
    return SimpleNamespace(trace=Trace(
        modules=[m for m, _ in pairs], ops=[o for _, o in pairs],
        spans=[Interval("bench.window", 0, 10)]))


@pytest.mark.parametrize("chips", [1, 4])
def test_reduce_ms_is_collective_time_per_matvec_per_chip(chips):
    # per chip: 7 ms of the first run (the union of start and done) and
    # 2 ms of the second over two runs in the window
    assert REDUCE(_run(chips)) == pytest.approx(4.5)


def test_reduce_ms_reads_nothing_without_runs():
    assert REDUCE(SimpleNamespace(trace=None)) is None
    empty = Trace(modules=[[]], ops=[[]],
                  spans=[Interval("bench.window", 0, 10)])
    assert REDUCE(SimpleNamespace(trace=empty)) is None
    two_pass = Trace(modules=[[Interval("jit__gram_matvec", 1, 2)]],
                     ops=[[Interval("multiply_reduce_fusion", 1, 2)]],
                     spans=[Interval("bench.window", 0, 10)])
    assert REDUCE(SimpleNamespace(trace=two_pass)) == 0.0


def _fetch_run(items, calls):
    return SimpleNamespace(spans=SimpleNamespace(items=items), calls=calls)


def test_fetch_s_is_the_window_fetch_time_per_answered_svd():
    items = [("bench.fetch", 0.5, 0.9),          # warm-up, before
             ("bench.window", 1.0, 10.0),
             ("bench.fetch", 2.0, 2.3), ("bench.solve", 1.0, 2.0),
             ("bench.fetch", 5.0, 5.5),
             ("bench.fetch", 9.9, 10.4)]          # starts inside
    calls = [Call({"kind": "svd"}, 1.0, 2.4, answer={}),
             Call({"kind": "svd"}, 4.0, 5.6, answer={}),
             Call({"kind": "svd"}, 9.0, 10.5, answer={}),
             Call({"kind": "svd"}, 10.5, 10.6, error="failed")]
    assert FETCH(_fetch_run(items, calls)) == pytest.approx(1.3 / 3)


def test_fetch_s_reads_nothing_without_a_window_or_an_answer():
    calls = [Call({"kind": "svd"}, 1.0, 2.0, answer={})]
    assert FETCH(_fetch_run([("bench.fetch", 1, 2)], calls)) is None
    assert FETCH(_fetch_run([("bench.window", 0, 3)], [])) is None
