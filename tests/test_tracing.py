"""The served path's host spans and the jit counter.

A tiny ``truncated_svd`` and ``cg_solve`` are served over TCP inside a
``jax.profiler`` trace (on the CPU the spans land on the host plane, as
on a TPU); every span of ``repro.core.tracing`` must be found there,
nested as its layer implies. The jit counter is fed by JAX's own
monitoring events."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.analysis.rules_source import check_trace_purity
from repro.core import AlchemistContext, AlchemistEngine, tracing, wire
from repro.core.costmodel import JIT_EVENTS, JIT_EVENTS_KEPT, JIT_LOG, \
    JitLog
from repro.core.libraries import elemental, skylark
from repro.core.server import AlchemistServer, _Connection

P = tracing.PREFIX
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "span_violations.py")

#: span -> the span it must lie inside, on the same thread
PARENTS = {
    tracing.CLIENT_HASH: tracing.CLIENT_UPLOAD,
    tracing.CLIENT_ALIAS_LOOKUP: tracing.CLIENT_UPLOAD,
    tracing.CLIENT_STREAM: tracing.CLIENT_UPLOAD,
    tracing.CLIENT_COMMIT: tracing.CLIENT_UPLOAD,
    tracing.SERVER_ASSEMBLE: tracing.server_frame("UPLOAD_COMMIT"),
    tracing.SERVER_DEVICE_PUT: tracing.server_frame("UPLOAD_COMMIT"),
    tracing.LANCZOS: tracing.TASK,
    tracing.LANCZOS_MATVEC: tracing.LANCZOS,
    tracing.CG: tracing.TASK,
    tracing.CG_RF_MAP: tracing.CG,
    tracing.CG_RHS: tracing.CG,
    tracing.CG_STEP: tracing.CG,
    tracing.CG_RESIDUAL: tracing.CG,
}

#: the spans a served SVD and CG job cross, each layer's
SERVED = [
    tracing.CLIENT_SUBMIT, tracing.CLIENT_WAIT, tracing.CLIENT_FETCH,
    tracing.CLIENT_UPLOAD, tracing.CLIENT_HASH, tracing.CLIENT_ALIAS_LOOKUP,
    tracing.CLIENT_STREAM, tracing.CLIENT_COMMIT,
    *(tracing.server_frame(f) for f in (
        "COMMAND", "TASK_OP", "UPLOAD_BEGIN", "UPLOAD_CHUNK",
        "UPLOAD_COMMIT", "FETCH", "FREE", "ALIAS_LOOKUP")),
    tracing.SERVER_ASSEMBLE, tracing.SERVER_DEVICE_PUT,
    tracing.TASK, tracing.LANCZOS, tracing.LANCZOS_MATVEC,
    tracing.CG, tracing.CG_RF_MAP, tracing.CG_RHS, tracing.CG_STEP,
    tracing.CG_RESIDUAL,
]


@pytest.fixture(scope="module")
def served_trace(tmp_path_factory):
    """Spans of one served SVD and one CG job, by name: ``(thread line,
    start_ns, end_ns)``; the ids each span carried, by name; and what the
    routines reported."""
    from jax.profiler import ProfileData

    rng = np.random.default_rng(0)
    x = rng.standard_normal((512, 32)).astype(np.float32)
    y = rng.standard_normal((512, 3)).astype(np.float32)
    engine = AlchemistEngine()
    server = AlchemistServer(engine=engine, host="127.0.0.1",
                             port=0).start()
    ac = AlchemistContext(address=server.address, client_name="traced")
    ac.register_library("elemental", elemental)
    ac.register_library("skylark", skylark)
    el, sk = ac.library("elemental"), ac.library("skylark")
    out = tmp_path_factory.mktemp("trace")
    try:
        with jax.profiler.trace(str(out)):
            A = ac.send_matrix(x, chunk_rows=128)
            U, S, V = el.truncated_svd(A, k=2, oversample=4, seed=1)
            U.to_numpy()
            matvecs = U.stats()["matvecs"]
            for m in (U, S, V):
                m.free()
            X = ac.send_matrix(x * 0.5, chunk_rows=128)
            Y = ac.send_matrix(y)
            W = sk.cg_solve(X, Y, lam=1e-3, rf_dim=64, max_iters=5,
                            tol=0.0)
            W.to_numpy()
            iterations = W.stats()["iterations"]
    finally:
        ac.stop()
        server.stop(shutdown_engine=True)
    path = glob.glob(os.path.join(str(out), "**", "*.xplane.pb"),
                     recursive=True)[0]
    spans: dict = {}
    ids: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(P):
                    spans.setdefault(ev.name[len(P):], []).append(
                        (i, ev.start_ns, ev.start_ns + ev.duration_ns))
                    ids.setdefault(ev.name[len(P):], []).append(
                        dict(ev.stats))
    return spans, matvecs, iterations, ids


@pytest.mark.parametrize("name", SERVED)
def test_served_path_writes_span(served_trace, name):
    spans, _, _, _ = served_trace
    assert spans.get(name), f"no {P}{name} in the trace"


@pytest.mark.parametrize("child", sorted(PARENTS))
def test_span_nests_inside_its_layer(served_trace, child):
    spans, _, _, _ = served_trace
    parents = spans[PARENTS[child]]
    for line, s, e in spans[child]:
        assert any(pl == line and ps <= s and e <= pe
                   for pl, ps, pe in parents), \
            f"{child} at {s} is not inside a {PARENTS[child]}"


def test_one_span_per_host_loop_iteration(served_trace):
    spans, matvecs, iterations, _ = served_trace
    assert len(spans[tracing.LANCZOS_MATVEC]) == matvecs
    assert len(spans[tracing.CG_STEP]) == iterations == 5
    # X crosses in 4 chunks, the first matrix also in 4, Y in 1
    assert len(spans[tracing.server_frame("UPLOAD_CHUNK")]) == 9
    assert len(spans[tracing.CLIENT_UPLOAD]) == 3


@pytest.mark.parametrize("name", [
    tracing.CG, tracing.CG_RF_MAP, tracing.CG_RHS, tracing.CG_STEP,
    tracing.CG_RESIDUAL])
def test_cg_spans_carry_the_chips_z_spans(served_trace, name):
    """On one device Z's rows are split over one chip: every CG span
    says ``shards`` 1 (four virtual devices: ``test_cg_mesh4.py``)."""
    _, _, _, ids = served_trace
    assert ids[name] and all(i.get("shards") == 1 for i in ids[name])


def test_lanczos_span_carries_the_path_and_shards(served_trace):
    """The Lanczos span says which Gram matvec ran and over how many
    devices X's rows lie: on the CPU, one device, XLA's two passes (four
    virtual devices: ``test_svd_mesh4.py``)."""
    _, _, _, ids = served_trace
    assert ids[tracing.LANCZOS] == [{"path": "xla", "shards": 1}]


def test_every_request_frame_has_a_server_span():
    requests = [s for s in wire.FRAME_SPECS if s.role == "request"]
    assert _Connection._SPANS == {
        s.code: tracing.server_frame(s.name) for s in requests}
    assert tracing.server_frame("COMMAND") == "server.command"
    names = [v for k, v in vars(tracing).items()
             if k.isupper() and isinstance(v, str)
             and k not in ("PREFIX", "SERVER")]
    names += list(_Connection._SPANS.values())
    assert len(names) == len(set(names))


def test_span_is_a_trace_annotation_with_ids():
    s = tracing.span(tracing.TASK, task=3, session=1)
    assert isinstance(s, jax.profiler.TraceAnnotation)
    with s:
        pass


def test_trc001_finds_spans_inside_jit_only():
    found = check_trace_purity(paths=[FIXTURE], include_fusible=False)
    assert {f.symbol for f in found} == {"span_violations.py:span_in_jit"}
    messages = "\n".join(f.message for f in found)
    assert "tracing.span()" in messages
    assert ".TraceAnnotation()" in messages
    assert len(found) == 2


def _jit_totals():
    stats = JIT_LOG.stats()
    return {key: stats[key] for key in JIT_EVENTS.values()}


def test_jit_counter_counts_a_new_executable_once():
    JIT_LOG.install()
    JIT_LOG.install()                       # idempotent: one listener

    def fresh_function_for_the_jit_counter(v):
        return v * 3.0 + 1.0

    f = jax.jit(fresh_function_for_the_jit_counter)
    v = np.arange(7, dtype=np.float32)
    before = _jit_totals()
    f(v).block_until_ready()
    first = _jit_totals()
    f(v).block_until_ready()
    second = _jit_totals()
    assert first["executables"] - before["executables"] == 1
    assert first["traces"] - before["traces"] >= 1
    assert second == first
    by_fun = JIT_LOG.stats()["by_function"]
    assert by_fun["jit(fresh_function_for_the_jit_counter)"] == \
        {"executables": 1}


def test_jit_counter_events_fall_in_their_window():
    import time

    JIT_LOG.install()
    t0 = time.perf_counter()
    jax.jit(lambda v: v - 2.0)(np.ones(5, np.float32)).block_until_ready()
    t1 = time.perf_counter()
    inside = JIT_LOG.events(t0, t1)
    assert [k for _, k, _ in inside].count("executables") == 1
    assert all(t0 <= t < t1 for t, _, _ in inside)
    assert JIT_LOG.events(t1, t1) == []


def test_jit_counter_window_older_than_its_log_reads_none():
    log = JitLog()
    for _ in range(JIT_EVENTS_KEPT):
        log._on_event("/jax/core/compile/jaxpr_trace_duration", 0.0,
                      fun_name="f")
    first = log.events()[0][0]
    assert len(log.events(first)) == JIT_EVENTS_KEPT
    log._on_event("/jax/core/compile/backend_compile_duration", 0.0,
                  fun_name="f")
    assert log.events() is None
    assert log.events(first) is None       # the first event is gone
    second = log.events(first + 1e-9)
    assert second is not None and len(second) == JIT_EVENTS_KEPT
    assert log.stats()["traces"] == JIT_EVENTS_KEPT


def test_compile_stats_reports_jit_beside_plan_compiles():
    engine = AlchemistEngine()
    try:
        stats = engine.compile_stats()
    finally:
        engine.shutdown()
    for key in ("compiles", "hits", "request_compiles", "executable_index"):
        assert key in stats
    assert set(stats["jit"]) == {*JIT_EVENTS.values(), "by_function"}


def test_cg_step_jit_is_built_per_call():
    """``cg_solve`` builds its step's ``jax.jit`` anew in every call, so
    every call obtains the step's executables again: what the jit
    counter sees and the engine's plan log does not."""
    engine = AlchemistEngine()
    ac = AlchemistContext(engine=engine)
    ac.register_library("skylark", skylark)
    sk = ac.library("skylark")
    rng = np.random.default_rng(1)
    X = ac.send_matrix(rng.standard_normal((64, 8)).astype(np.float32))
    Y = ac.send_matrix(rng.standard_normal((64, 2)).astype(np.float32))
    try:
        counts = []
        for seed in (0, 1, 2):
            before = JIT_LOG.stats()["by_function"].get(
                "jit(<lambda>)", {}).get("executables", 0)
            sk.cg_solve(X, Y, lam=1e-3, max_iters=3, tol=0.0,
                        seed=seed).result()
            counts.append(JIT_LOG.stats()["by_function"].get(
                "jit(<lambda>)", {}).get("executables", 0) - before)
        assert min(counts) >= 1
        assert engine.compile_stats()["compiles"] == 0
    finally:
        ac.stop()
        engine.shutdown()
