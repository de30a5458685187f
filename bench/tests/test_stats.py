"""The metric arithmetic on synthetic timestamps, and the traffic
generator's promise that every seed gets the same work."""
import os
from types import SimpleNamespace

import numpy as np
import pytest

from harness import data, stats, traffic
from harness.served import Call
from harness.spec import load_module

METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "metrics")


def reader(name):
    return load_module(os.path.join(METRICS, name + ".py")).read


def test_percentile_is_nearest_rank():
    vals = list(range(1, 101))                      # 1 .. 100
    assert stats.percentile(vals, 95) == 95.0       # rank round(0.95 * 99)
    assert stats.percentile(vals, 50) == 51.0
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([], 95) is None


def test_time_per_call_spans_the_whole_window():
    # three calls: 0-1.0, 1.0-2.5, 2.5-3.0; a fourth started and failed
    starts, ends = [0.0, 1.0, 2.5, 3.0], [1.0, 2.5, 3.0]
    assert stats.time_per_call(starts, ends) == pytest.approx(1.0)
    assert stats.time_per_call([], []) is None


def calls(kind, spans, errors=()):
    out = []
    for i, (t0, t1) in enumerate(spans):
        c = Call({"kind": kind}, t0, t1)
        if i in errors:
            c.error = "failed"
        out.append(c)
    return out


def test_svd_s_reader_counts_completed_calls():
    run = SimpleNamespace(calls=calls("svd", [(0, 1.2), (1.2, 2.4),
                                              (2.4, 3.0)], errors={2}))
    # last completion 2.4 s after the first start, two completed
    assert reader("svd_s")(run) == pytest.approx(1.2)


def test_closed_loop_gives_every_seed_the_same_work():
    mix = {"loop": "closed", "requests": [{"kind": "a", "share": 3.0},
                                          {"kind": "b", "share": 1.0}]}
    a = traffic.closed_loop(mix, 1, 8)
    b = traffic.closed_loop(mix, 3_000_000_019, 8)
    for reqs in (a, b):
        assert sorted(r["kind"] for r in reqs) == ["a"] * 6 + ["b"] * 2
    assert [r["seed"] for r in a] != [r["seed"] for r in b]


def test_sample_is_drawn_from_the_seed():
    mix = {"check": 3}
    assert traffic.sample(mix, 2, 7) == [0, 1]
    assert traffic.sample({"check": "all"}, 5, 7) == [0, 1, 2, 3, 4]
    picked = traffic.sample(mix, 40, 7)
    assert len(set(picked)) == 3 and all(0 <= i < 40 for i in picked)
    assert picked == traffic.sample(mix, 40, 7)
    assert any(traffic.sample(mix, 40, s) != picked for s in range(8, 12))


def test_speech_jobs_are_the_same_work_on_different_problems():
    pool = data.SpeechPool(256, 4, 5, 3_000_000_019, jobs=4)
    grams = [x.T @ x for x, _ in pool.jobs]
    rhs = [x.T @ y for x, y in pool.jobs]
    for i in range(1, 4):
        assert np.allclose(grams[i], grams[0], rtol=1e-5, atol=1e-3)
        for j in range(i):
            assert not np.allclose(rhs[i], rhs[j], rtol=1e-2, atol=1e-2)


def test_same_seed_same_requests():
    closed = {"requests": [{"kind": "svd"}]}
    assert traffic.closed_loop(closed, 2 ** 40, 5) == \
        traffic.closed_loop(closed, 2 ** 40, 5)
