"""The reduction over the program's spans (``harness/spans.py``), the
window's jit counts (``harness/counters.py``) and ``idle.py``: on
synthetic intervals with known answers, on the recorded TPU v5e trace,
and on a whole run at test size on the CPU."""
import os
import subprocess
import sys

import pytest

import idle
import tiny
from harness import counters, spans, trace
from harness.served import Call
from harness.spec import load_module
from harness.trace import Interval, Trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "gram_matvec.xplane.pb")
METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "metrics")


def synthetic() -> Trace:
    """One chip over a 10 s window, busy over [1, 2], [3, 4] and [6, 7].
    Host spans on two threads: a call over [0.5, 9]; inside it a submit
    at 0.5, a task [1, 8] holding a loop [1.5, 7.5] whose matvecs start
    at 1.8, 3.2 and 6.1 (0.5 s each); a fetch [8, 9]."""
    ops = [Interval("a", 1, 2), Interval("b", 3, 4), Interval("c", 6, 7)]
    mods = [Interval("jit_mv", 1.9, 2), Interval("jit_mv", 3.3, 4),
            Interval("jit_mv", 6.2, 7)]
    return Trace(modules=[mods], ops=[ops], spans=[
        Interval("bench.window", 0, 10),
        Interval("bench.call", 0.5, 9),
        Interval("alchemist.client.submit", 0.5, 0.6),
        Interval("alchemist.task", 1, 8),
        Interval("alchemist.lanczos", 1.5, 7.5),
        Interval("alchemist.lanczos.matvec", 1.8, 2.3),
        Interval("alchemist.lanczos.matvec", 3.2, 3.7),
        Interval("alchemist.lanczos.matvec", 6.1, 6.6),
        Interval("bench.fetch", 8, 9)])


def test_gaps_cover_the_idle_window():
    assert spans.gaps(synthetic()) == [(0, 1), (2, 3), (4, 6), (7, 10)]


def test_idle_by_label_takes_the_innermost_span_of_either_prefix():
    idle = dict(spans.idle_by_label(synthetic()))
    # [0, 1]: no span over [0, 0.5], the submit over [0.5, 0.6], the
    # call over [0.6, 1]; [2, 3]: a matvec over [2, 2.3], the loop over
    # [2.3, 3]; [4, 6]: the loop; [7, 10]: the loop to 7.5, the task to
    # 8, the fetch to 9, no span to 10
    assert idle == {"host": pytest.approx(1.5),
                    "alchemist.client.submit": pytest.approx(0.1),
                    "bench.call": pytest.approx(0.4),
                    "alchemist.lanczos.matvec": pytest.approx(0.3),
                    "alchemist.lanczos": pytest.approx(3.2),
                    "alchemist.task": pytest.approx(0.5),
                    "bench.fetch": pytest.approx(1.0)}
    assert sum(idle.values()) == pytest.approx(
        10 * trace.idle_share(synthetic()))


def test_idle_by_label_splits_a_gap_across_the_spans_it_crosses():
    """One 4-s gap of a send: the hash takes 2 s of it, the stream 1 s
    and the server's commit 0.5 s, inside the upload; the stream's 1 s
    would own the whole gap if it were labelled at its midpoint."""
    t = Trace(modules=[[]], ops=[[Interval("a", 0, 1),
                                  Interval("b", 5, 6)]], spans=[
        Interval("bench.window", 0, 6),
        Interval("bench.send", 0.5, 5.5),
        Interval("alchemist.client.upload", 1, 5),
        Interval("alchemist.client.hash", 1, 3),
        Interval("alchemist.client.stream", 2.8, 3.8),
        Interval("alchemist.server.upload_commit", 4.2, 4.7)])
    assert spans.gaps(t) == [(1, 5)]
    idle = dict(spans.idle_by_label(t))
    # the stream is inner to the hash where the two overlap, [2.8, 3]
    assert idle == {"alchemist.client.hash": pytest.approx(1.8),
                    "alchemist.client.stream": pytest.approx(1.0),
                    "alchemist.server.upload_commit": pytest.approx(0.5),
                    "alchemist.client.upload": pytest.approx(0.7)}
    assert sum(idle.values()) == pytest.approx(4.0)


def test_idle_by_label_is_host_outside_every_span():
    t = synthetic()
    t.spans = [s for s in t.spans if s.name == "bench.window"]
    assert spans.idle_by_label(t) == [["host", pytest.approx(7.0)]]


def test_idle_within_a_span():
    t = synthetic()
    # the loop [1.5, 7.5] is idle over [2, 3], [4, 6] and [7, 7.5]
    assert spans.idle_within(t, "alchemist.lanczos") == pytest.approx(3.5)
    # matvecs: [1.8, 2.3] idle 0.3, [3.2, 3.7] idle 0, [6.1, 6.6] idle 0
    assert spans.idle_within(t, "alchemist.lanczos.matvec") == \
        pytest.approx(0.3)
    assert spans.idle_within(t, "no.such.span") == 0.0


def test_idle_within_clips_to_the_window():
    t = synthetic()
    t.spans.append(Interval("alchemist.cg", 9.5, 12))
    assert spans.idle_within(t, "alchemist.cg") == pytest.approx(0.5)


def test_span_seconds_sum_the_instances_in_the_window():
    t = synthetic()
    assert spans.span_seconds(t, "alchemist.lanczos.matvec") == \
        pytest.approx(1.5)
    t.spans.append(Interval("alchemist.lanczos.matvec", 11, 12))
    assert spans.span_seconds(t, "alchemist.lanczos.matvec") == \
        pytest.approx(1.5)


def test_time_to_next_span():
    t = synthetic()
    assert spans.time_to_next(t, "alchemist.client.submit",
                              "alchemist.lanczos.matvec") == \
        [pytest.approx(1.3)]
    # each matvec to the next matvec at or after its start: itself
    assert spans.time_to_next(t, "alchemist.lanczos.matvec",
                              "alchemist.lanczos.matvec") == [0, 0, 0]
    assert spans.time_to_next(t, "bench.fetch",
                              "alchemist.lanczos.matvec") == []


def test_device_lag_and_the_shift_that_corrects_it():
    t = synthetic()
    assert spans.device_lag(t, "alchemist.lanczos.matvec", "jit_mv") == \
        pytest.approx(0.1)
    assert spans.device_lag(t, "alchemist.lanczos.matvec", "none") is None
    # a device clock 0.2 s behind: the lag reads negative
    behind = spans.shifted(t, -0.2)
    lag = spans.device_lag(behind, "alchemist.lanczos.matvec", "jit_mv")
    assert lag == pytest.approx(-0.1)
    back = spans.shifted(behind, 0.2)
    assert [(o.start, o.end) for o in back.ops[0]] == \
        [pytest.approx((o.start, o.end)) for o in t.ops[0]]
    assert back.spans is t.spans


# ---- the recorded trace: loading the program's spans moves nothing ----
@pytest.fixture(scope="module")
def both():
    """The recorded trace loaded by ``trace.load`` as it is and with the
    program's spans kept too (``idle.program_spans``), each given the
    window the trace tests set."""
    with idle.program_spans():
        kept = trace.load(DATA)
    out = []
    for t in (trace.load(DATA), kept):
        calls = [s for s in t.spans if s.name == "bench.call"]
        t.spans.append(Interval("bench.window", calls[0].start - 0.01,
                                calls[-1].end))
        out.append(t)
    return out


def test_recorded_readers_read_the_same_with_program_spans(both):
    old, new = both
    assert trace.busy_seconds(new) == trace.busy_seconds(old)
    assert trace.idle_share(new) == trace.idle_share(old)
    assert trace.program_seconds(new, "jit__gram_matvec") == \
        trace.program_seconds(old, "jit__gram_matvec")
    assert trace.top_ops(new) == trace.top_ops(old)
    assert trace.idle_gaps(new) == trace.idle_gaps(old)


@pytest.mark.parametrize("metric", ["svd.device_idle", "cg.device_idle",
                                    "svd.matvec_roofline",
                                    "cg.step_roofline"])
def test_recorded_metric_reads_the_same_with_program_spans(both, metric):
    read = load_module(os.path.join(METRICS, metric + ".py")).read

    def run(t):
        class Run:
            trace = t
            config = {"rows": 8192, "cols": 1024, "rf_dim": 1024,
                      "classes": 1}
            peaks = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
        return Run

    old, new = both
    assert read(run(new)) == read(run(old))


def test_recorded_idle_by_label_sums_the_idle_share(both):
    old, new = both
    lo, hi = trace.window(new)
    idle = spans.idle_by_label(new)
    assert sum(s for _, s in idle) == pytest.approx(
        trace.idle_share(old) * (hi - lo))
    assert {name for name, _ in idle} <= {"bench.call", "bench.fetch",
                                          "host"}


# ---- the window's jit counts -----------------------------------------
class _Spans:
    def __init__(self, items):
        self.items = items


def _run(window, calls):
    class Run:
        spans = _Spans([("bench.call", 0.0, 1.0)] + window)
    Run.calls = calls
    return Run


def test_executables_per_call_counts_inside_the_window():
    import time

    import jax
    import numpy as np

    from repro.core.costmodel import JIT_LOG

    JIT_LOG.install()
    t0 = time.perf_counter()
    jax.jit(lambda v: v * 5.0)(np.ones(3, np.float32)).block_until_ready()
    jax.jit(lambda v: v * 6.0)(np.ones(3, np.float32)).block_until_ready()
    t1 = time.perf_counter()
    calls = [Call({"kind": "svd"}, 0.0, 1.0, answer={}),
             Call({"kind": "svd"}, 1.0, 2.0, answer={}),
             Call({"kind": "svd"}, 2.0, None, error="failed")]
    run = _run([("bench.window", t0, t1)], calls)
    assert counters.executables_per_call(run, "svd") == pytest.approx(1.0)
    assert counters.executables_per_call(run, "cg_job") is None
    assert counters.executables_per_call(_run([], calls), "svd") is None


def test_executables_per_call_reads_nothing_for_a_window_the_log_dropped(
        monkeypatch):
    from repro.core.costmodel import JIT_LOG

    monkeypatch.setattr(JIT_LOG, "events", lambda since, until: None)
    run = _run([("bench.window", 0.0, 1.0)],
               [Call({"kind": "svd"}, 0.0, 1.0, answer={})])
    assert counters.executables_per_call(run, "svd") is None


def test_executables_per_call_reads_nothing_without_the_counter(
        monkeypatch):
    import repro.core.costmodel as costmodel

    monkeypatch.delattr(costmodel, "JIT_LOG")
    run = _run([("bench.window", 0.0, 1.0)],
               [Call({"kind": "svd"}, 0.0, 1.0, answer={})])
    assert counters.executables_per_call(run, "svd") is None


# ---- idle.py, a whole traced run at test size --------------------------
@pytest.mark.parametrize("workload,readings", [
    ("ocean.svd", {"svd.call_start_s", "svd.loop_idle_s"}),
    ("speech.cg", {"cg.hash_s", "cg.stream_s", "cg.ingest_s",
                   "cg.loop_idle_s"}),
])
def test_idle_tool_reads_every_span_metric(tmp_path, workload, readings):
    import json

    root = tiny.copy(str(tmp_path))
    script = os.path.join(root, "idle_here.py")
    with open(script, "w") as f:
        f.write(tiny.WRAPPER.replace(
            "from harness import device, main",
            "from harness import device\nimport idle as main").format(
                bench=os.path.join(root, "bench"),
                src=os.path.join(tiny.ROOT, "src"), fault=""))
    out_file = os.path.join(root, "idle.json")
    p = subprocess.run(
        [sys.executable, script, "--workload", workload, "--seed",
         "3000000019", "--seconds", "2", "--out", out_file],
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=600)
    assert p.returncode == 0, p.stderr
    lines = p.stdout.strip().splitlines()
    result, report = json.loads(lines[-2]), json.loads(lines[-1])
    assert result["correct"], p.stderr
    compiles = "svd.compiles" if workload == "ocean.svd" else "cg.compiles"
    assert compiles in result["metrics"]
    assert set(report["readings"]) == readings
    assert all(v >= 0 for v in report["readings"].values())
    assert report["answered"] == result["attempted"]
    # the CPU runs no device plane: every idle second is the window's,
    # cut among the spans over it; the program's own spans hold the
    # calls almost whole, leaving the benchmark's call and the host the
    # moments between them
    assert report["idle_s"] == pytest.approx(result["device"]["window_s"])
    assert sum(report["shares"].values()) == pytest.approx(1.0)
    assert report["shares"]["program"] > 0.9
    with open(out_file) as f:
        assert json.load(f) == report
