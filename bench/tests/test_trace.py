"""The trace reduction, on a recorded TPU v5e trace and on synthetic
intervals with known answers."""
import os

import pytest

from harness import trace
from harness.spec import load_module
from harness.trace import Interval, Trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "gram_matvec.xplane.pb")
METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "metrics")


@pytest.fixture(scope="module")
def recorded():
    """Three runs of ``_gram_matvec`` on an 8,192 x 1,024 float32 operand,
    traced on one TPU v5e, each inside a ``bench.call`` host span. The
    window is set here from 10 ms before the first call to the last
    call's end: in this trace the chip's clock reads about 1.5 ms behind
    the host's, so the first run starts before its call does."""
    t = trace.load(DATA)
    calls = [s for s in t.spans if s.name == "bench.call"]
    t.spans.append(Interval("bench.window", calls[0].start - 0.01,
                            calls[-1].end))
    return t


def test_recorded_trace_has_one_chip_and_its_programs(recorded):
    assert recorded.chips == 1
    assert [m.name for m in recorded.modules[0]] == ["jit__gram_matvec"] * 3
    secs, runs = trace.program_seconds(recorded, "jit__gram_matvec")
    assert runs == 3
    # device durations as recorded: 95.035, 94.895 and 94.946 us
    assert secs == pytest.approx(284.876e-6, rel=1e-4)


def test_recorded_busy_is_the_union_of_ops(recorded):
    lo, hi = trace.window(recorded)
    ops = recorded.ops[0]
    # the ops of a run overlap (copy-start runs beside the fusions), so
    # the union is below the plain sum and above the longest op
    plain = sum(min(o.end, hi) - max(o.start, lo) for o in ops
                if o.end > lo and o.start < hi)
    busy = trace.busy_seconds(recorded)
    assert 0 < busy <= plain
    assert trace.idle_share(recorded) == pytest.approx(1 - busy / (hi - lo))
    # the calls hold sleeps and fetches: the chip is idle most of the time
    assert trace.idle_share(recorded) > 0.9


def test_recorded_roofline_share(recorded):
    """The Gram matvec reader: 8,192 x 1,024 float32 read once is 33.6 MB,
    40.96 us at 819 GB/s; the runs took about 95 us, so about 43%."""
    read = load_module(os.path.join(METRICS, "svd.matvec_roofline.py")).read

    class Run:
        trace = recorded
        config = {"rows": 8192, "cols": 1024}
        peaks = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}

    least = 8192 * 1024 * 4 / 819e9
    assert read(Run) == pytest.approx(100 * 3 * least / 284.876e-6,
                                      rel=1e-3)
    assert 40 < read(Run) < 46


def test_recorded_breakdown(recorded):
    ops = trace.top_ops(recorded)
    assert ops[0][0].startswith("jit__gram_matvec/")
    assert all(secs > 0 for _, secs in ops)
    gaps = trace.idle_gaps(recorded)
    assert gaps and gaps[0][1] >= gaps[-1][1]
    assert {label for label, _ in gaps} <= {"bench.call", "bench.fetch",
                                            "host"}


def synthetic() -> Trace:
    """Two chips over a 10 s window. Chip 0 runs ops over [1, 3] and
    [2, 4] (overlapping) and [6, 7]; chip 1 runs [0, 10]. Host spans:
    send over [4, 6], fetch over [7, 9]."""
    ops0 = [Interval("a", 1, 3), Interval("b", 2, 4), Interval("c", 6, 7)]
    mods0 = [Interval("jit_f", 1, 4), Interval("jit_g", 6, 7)]
    return Trace(modules=[mods0, [Interval("jit_f", 0, 10)]],
                 ops=[ops0, [Interval("d", 0, 10)]],
                 spans=[Interval("bench.window", 0, 10),
                        Interval("bench.send", 4, 6),
                        Interval("bench.call", 3.5, 9.5),
                        Interval("bench.fetch", 7, 9)])


def test_synthetic_busy_and_idle():
    t = synthetic()
    # chip 0 busy 3 + 1 = 4 s, chip 1 busy 10 s: mean 7 of 10
    assert trace.busy_seconds(t) == pytest.approx(7.0)
    assert trace.idle_share(t) == pytest.approx(0.3)


def test_synthetic_programs_and_ops():
    t = synthetic()
    assert trace.program_seconds(t, "jit_f") == (pytest.approx(13.0), 2)
    top = dict(trace.top_ops(t))
    assert top["jit_f/d"] == pytest.approx(5.0)      # 10 s over two chips
    assert top["jit_f/a"] == pytest.approx(1.0)
    assert top["jit_g/c"] == pytest.approx(0.5)


def test_synthetic_gaps_take_the_innermost_span_at_their_midpoint():
    gaps = trace.idle_gaps(synthetic())
    # chip 0's gaps: [7, 10] (midpoint 8.5: call and fetch hold it,
    # fetch is inner), [4, 6] (midpoint 5: send and call, send is
    # inner), [0, 1] (no span)
    assert gaps == [["bench.fetch", pytest.approx(3.0)],
                    ["bench.send", pytest.approx(2.0)],
                    ["host", pytest.approx(1.0)]]


def _roofline_run(planes: int, rows: int):
    """``planes`` chips over a 10 s window, each running the Gram matvec
    three times for 1 ms; ``rows`` in the whole field, 1,024 columns."""
    runs = [Interval("jit__gram_matvec", t, t + 1e-3) for t in (1, 2, 3)]

    class Run:
        trace = Trace(modules=[list(runs) for _ in range(planes)],
                      ops=[[] for _ in range(planes)],
                      spans=[Interval("bench.window", 0, 10)])
        config = {"rows": rows, "cols": 1024}
        peaks = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    return Run


def test_roofline_share_is_per_chip():
    """Over four chips each run reads a quarter of the rows: 4 x 8,192
    rows read at 1 ms a run on each chip is the one-chip share of 8,192
    rows at 1 ms, not four times it. On one plane the reader is the
    whole-field formula, to the last bit."""
    read = load_module(os.path.join(METRICS, "svd.matvec_roofline.py")).read
    one, four = read(_roofline_run(1, 8192)), read(_roofline_run(4, 32768))
    least = 8192 * 1024 * 4 / 819e9
    assert one == 100.0 * 3 * least / trace.program_seconds(
        _roofline_run(1, 8192).trace, "jit__gram_matvec")[0]
    assert four == pytest.approx(one, rel=1e-12)
    assert trace.program_chips(_roofline_run(4, 32768).trace,
                               "jit__gram_matvec") == 4
