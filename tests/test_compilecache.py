"""Compile-latency subsystem (``core/compilecache.py``): bucket-policy
units, pad/crop conformance of every bucketable cataloged routine
against the reference backend at odd (non-bucket) shapes, shape-aware
plan signatures, the program-cache LRU bound, AOT warmup, the
persistent executable index + warm-restart zero-recompile round trip,
fused chains with bucketing on/off, CompileLog accounting, the one
cache-directory rule, and the ``configure`` wire surface
(bucketing/warmup options; no option can move the cache)."""
import os
import threading

import numpy as np
import pytest

from repro.core import AlchemistContext, AlchemistEngine
from repro.core import compilecache
from repro.core.backends import base as backend_base
from repro.core.backends.jax_backend import JaxBackend
from repro.core.context import AlchemistError
from repro.core.engine import make_engine_mesh
from repro.core.handles import MatrixHandle
from repro.core.libraries import elemental

RNG = np.random.RandomState(11)

# deliberately odd, off-grid shapes: every dimension pads under the
# default pow2 bucket grid
ODD_A = RNG.randn(37, 53).astype(np.float32)
ODD_B = RNG.randn(53, 29).astype(np.float32)
ODD_C = RNG.randn(37, 53).astype(np.float32)
ODD_SQ = (RNG.randn(19, 19) / 4.0).astype(np.float32)


@pytest.fixture
def persistent_cache(tmp_path, monkeypatch):
    """JAX's persistent cache turned on the way an operator places it —
    ``JAX_COMPILATION_CACHE_DIR`` — at a test-owned directory, never the
    checkout's; the process-global JAX config is restored afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    cache = str(tmp_path / "ccache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    assert compilecache.enable_persistent_cache() == cache
    yield cache
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def fresh(cache_entries=0, **engine_kw):
    engine = AlchemistEngine(make_engine_mesh(1),
                             cache_entries=cache_entries, **engine_kw)
    engine.load_library("elemental", elemental)
    return engine


# ---------------------------------------------------------------------------
# BucketPolicy units
# ---------------------------------------------------------------------------
def test_bucket_dim_rounds_up_to_smallest_holding_bucket():
    p = compilecache.BucketPolicy(grid=(32, 64, 128))
    assert p.bucket_dim(1) == 32
    assert p.bucket_dim(32) == 32      # exact boundary stays
    assert p.bucket_dim(33) == 64
    assert p.bucket_dim(128) == 128
    assert p.bucket_dim(129) == 129    # beyond grid: passthrough


def test_bucket_shape_and_exactness():
    p = compilecache.BucketPolicy(grid=(32, 64))
    assert p.bucket_shape((37, 53)) == (64, 64)
    assert p.bucket_shape((32, 64)) == (32, 64)
    assert p.is_exact((32, 64))
    assert not p.is_exact((37, 53))


def test_disabled_policy_is_identity():
    p = compilecache.BucketPolicy(grid=(32, 64), enabled=False)
    assert p.bucket_dim(37) == 37
    assert p.bucket_shape((37, 53)) == (37, 53)
    assert p.is_exact((37, 53))


def test_bucket_grid_is_sorted_and_validated():
    p = compilecache.BucketPolicy(grid=(128, 32, 64))
    assert p.grid == (32, 64, 128)
    with pytest.raises(ValueError, match="positive"):
        compilecache.BucketPolicy(grid=(0, 32))


# ---------------------------------------------------------------------------
# pad/crop primitives
# ---------------------------------------------------------------------------
def test_pad_to_zero_pads_trailing_edges_and_crop_inverts():
    be = JaxBackend()
    a = np.arange(6, dtype=np.float32).reshape(2, 3)
    padded = np.asarray(be.pad_to(a, (4, 8)))
    assert padded.shape == (4, 8)
    np.testing.assert_array_equal(padded[:2, :3], a)
    assert float(np.abs(padded[2:, :]).sum()) == 0.0
    assert float(np.abs(padded[:, 3:]).sum()) == 0.0
    back = np.asarray(be.crop_to(padded, (2, 3)))
    np.testing.assert_array_equal(back, a)


def test_pad_to_rejects_shrinking_targets():
    be = JaxBackend()
    a = np.zeros((4, 4), dtype=np.float32)
    with pytest.raises(ValueError):
        be.pad_to(a, (2, 8))
    with pytest.raises(ValueError):
        be.pad_to(a, (4, 4, 4))


# ---------------------------------------------------------------------------
# bucket-padding conformance: every bucketable cataloged routine,
# bucketed jax vs exact reference, at odd shapes
# ---------------------------------------------------------------------------
# per-routine odd-shape operand sets satisfying each routine's shape rule
BUCKETABLE_CASES = {
    ("elemental", "multiply"): {"A": ODD_A, "B": ODD_B},
    ("elemental", "add"): {"A": ODD_A, "B": ODD_C},
    ("elemental", "transpose"): {"A": ODD_A},
    ("elemental", "gram"): {"A": ODD_A},
}


def test_bucketable_catalog_is_fully_covered():
    """Every routine the jax backend declares bucketable has a
    conformance case here — a new bucketable registration must add one."""
    engine = fresh()
    try:
        be = engine.backends["jax"]
        declared = {(lib, rn) for lib, rn in be.routines()
                    if be.routine_impl(lib, rn).bucketable}
        assert declared == set(BUCKETABLE_CASES)
        # and the reference backend declares the identical bucketable set
        ref = engine.backends["reference"]
        assert declared == {(lib, rn) for lib, rn in ref.routines()
                            if ref.routine_impl(lib, rn).bucketable}
    finally:
        engine.shutdown()


@pytest.mark.parametrize("lib,rn", sorted(BUCKETABLE_CASES))
def test_bucketed_result_identical_to_reference(lib, rn):
    engine = fresh(bucketing=True)
    ac_jax = AlchemistContext(engine=engine)
    ac_ref = AlchemistContext(engine=engine, backend="reference")
    try:
        arrays = BUCKETABLE_CASES[(lib, rn)]
        outs = {}
        for ac in (ac_jax, ac_ref):
            handles = {k: ac.send_matrix(v, dedup=False)
                       for k, v in arrays.items()}
            res = ac.call(lib, rn, **handles)
            outs[ac] = {k: (ac.fetch(v).collect(),
                            tuple(v.shape), v.dtype, v.layout)
                        for k, v in res.items()
                        if isinstance(v, MatrixHandle)}
        assert set(outs[ac_jax]) == set(outs[ac_ref])
        for k in outs[ac_jax]:
            arr_j, shape_j, dtype_j, layout_j = outs[ac_jax][k]
            arr_r, shape_r, dtype_r, layout_r = outs[ac_ref][k]
            # padded program outputs are cropped back to logical shapes
            assert (shape_j, dtype_j, layout_j) == \
                (shape_r, dtype_r, layout_r)
            np.testing.assert_allclose(arr_j, arr_r, rtol=1e-4, atol=1e-4)
        # the jax run actually exercised the bucket path
        assert engine.compile_log.stats()["bucketed_executions"] >= 1
    finally:
        ac_jax.stop()
        ac_ref.stop()
        engine.shutdown()


def test_non_bucketable_routine_unaffected_by_bucketing():
    """qr's values depend on operand extents — it must run at its exact
    shape even with bucketing on, and still conform to reference."""
    engine = fresh(bucketing=True)
    ac_jax = AlchemistContext(engine=engine)
    ac_ref = AlchemistContext(engine=engine, backend="reference")
    try:
        assert not engine.backends["jax"].routine_impl(
            "elemental", "qr").bucketable
        outs = {}
        for ac in (ac_jax, ac_ref):
            h = ac.send_matrix(ODD_A, dedup=False)
            res = ac.call("elemental", "qr", A=h)
            outs[ac] = {k: ac.fetch(v).collect() for k, v in res.items()
                        if isinstance(v, MatrixHandle)}
        for k in outs[ac_jax]:
            assert outs[ac_jax][k].shape == outs[ac_ref][k].shape
        # Q@R reconstructs A on both
        for ac in (ac_jax, ac_ref):
            np.testing.assert_allclose(
                outs[ac]["Q"] @ outs[ac]["R"], ODD_A,
                rtol=1e-3, atol=1e-3)
    finally:
        ac_jax.stop()
        ac_ref.stop()
        engine.shutdown()


# ---------------------------------------------------------------------------
# shape-aware plan signatures
# ---------------------------------------------------------------------------
def _plan(impl, shapes, dtype="float32"):
    args = {}
    specs = {}
    for n, (param, shape) in enumerate(sorted(shapes.items())):
        slot = f"i{n}"
        args[param] = backend_base.Input(slot)
        specs[slot] = (tuple(shape), dtype)
    return backend_base.ExecutionPlan(
        steps=[backend_base.PlanStep(library="elemental",
                                     routine="multiply", args=args,
                                     impl=impl)],
        input_specs=specs)


def test_signature_carries_operand_shapes_and_dtypes():
    be = JaxBackend()
    impl = be.routine_impl("elemental", "multiply")
    s1 = _plan(impl, {"A": (32, 32), "B": (32, 32)}).signature()
    s2 = _plan(impl, {"A": (64, 64), "B": (64, 64)}).signature()
    s3 = _plan(impl, {"A": (32, 32), "B": (32, 32)}).signature()
    s4 = _plan(impl, {"A": (32, 32), "B": (32, 32)},
               dtype="float64").signature()
    assert s1 != s2          # same structure, different shapes
    assert s1 == s3          # stable across rebuilds
    assert s1 != s4          # dtype is part of the identity
    hash(s1)                 # usable as a cache key


def test_signature_none_without_specs_is_distinct_key_shape():
    be = JaxBackend()
    impl = be.routine_impl("elemental", "multiply")
    plan = _plan(impl, {"A": (32, 32), "B": (32, 32)})
    plan.input_specs = None
    sig = plan.signature()
    assert sig is not None and sig[1] is None
    plan.steps[0].args["B"] = [1, 2]        # unhashable arg
    assert plan.signature() is None


# ---------------------------------------------------------------------------
# shape propagation (the crop-back contract)
# ---------------------------------------------------------------------------
def test_propagate_shapes_through_a_chain():
    be = JaxBackend()
    mul = be.routine_impl("elemental", "multiply")
    gram = be.routine_impl("elemental", "gram")
    plan = backend_base.ExecutionPlan(steps=[
        backend_base.PlanStep(
            library="elemental", routine="multiply",
            args={"A": backend_base.Input("i0"),
                  "B": backend_base.Input("i1")}, impl=mul),
        backend_base.PlanStep(
            library="elemental", routine="gram",
            args={"A": backend_base.StepRef(0, "C")}, impl=gram),
    ])
    crops = compilecache.propagate_shapes(
        plan, {"i0": (37, 53), "i1": (53, 29)})
    assert crops == [{"C": (37, 29)}, {"G": (29, 29)}]
    # a rule that rejects the shapes -> None, caller runs exact
    assert compilecache.propagate_shapes(
        plan, {"i0": (37, 53), "i1": (31, 29)}) is None
    assert compilecache.plan_bucketable(plan)


def test_plan_with_non_bucketable_step_is_not_bucketable():
    be = JaxBackend()
    mul = be.routine_impl("elemental", "multiply")
    qr = be.routine_impl("elemental", "qr")
    plan = backend_base.ExecutionPlan(steps=[
        backend_base.PlanStep(
            library="elemental", routine="multiply",
            args={"A": backend_base.Input("i0"),
                  "B": backend_base.Input("i1")}, impl=mul),
        backend_base.PlanStep(
            library="elemental", routine="qr",
            args={"A": backend_base.StepRef(0, "C")}, impl=qr),
    ])
    assert not compilecache.plan_bucketable(plan)


# ---------------------------------------------------------------------------
# warmup enumeration
# ---------------------------------------------------------------------------
def test_matrix_params_discovered_from_shape_rules():
    be = JaxBackend()
    assert compilecache.matrix_params_of(
        be.routine_impl("elemental", "multiply")) == ["A", "B"]
    assert compilecache.matrix_params_of(
        be.routine_impl("elemental", "gram")) == ["A"]
    assert compilecache.matrix_params_of(
        be.routine_impl("elemental", "qr")) == []


def test_warmup_shape_sets_respect_the_shape_rule():
    be = JaxBackend()
    mul = be.routine_impl("elemental", "multiply")
    combos = compilecache.warmup_shape_sets(mul, ["A", "B"], (32, 64),
                                            limit=1000)
    assert combos
    for c in combos:
        assert c["A"][1] == c["B"][0]       # contracted dims agree
    # 2 grid sizes: A has 4 shapes, B's rows pinned by A's cols -> 2 each
    assert len(combos) == 8
    add = be.routine_impl("elemental", "add")
    for c in compilecache.warmup_shape_sets(add, ["A", "B"], (32, 64),
                                            limit=1000):
        assert c["A"] == c["B"]
    # the enumeration ceiling holds
    assert len(compilecache.warmup_shape_sets(
        mul, ["A", "B"], (32, 64, 128, 256), limit=5)) == 5


# ---------------------------------------------------------------------------
# program-cache LRU bound
# ---------------------------------------------------------------------------
def test_program_cache_lru_evicts_oldest_and_counts():
    be = JaxBackend(max_programs=2)
    impl = be.routine_impl("elemental", "multiply")
    plans = [_plan(impl, {"A": (s, s), "B": (s, s)})
             for s in (8, 16, 32)]
    for p in plans:
        _, info = be.get_or_compile(p)
        assert not info["cached"]
    info = be.program_cache_info()
    assert info["programs"] == 2
    assert info["evictions"] == 1
    # oldest (8x8) was evicted -> recompiles; newest (32x32) still hot
    _, i32 = be.get_or_compile(plans[2])
    assert i32["cached"]
    _, i8 = be.get_or_compile(plans[0])
    assert not i8["cached"]
    assert be.evictions == 2                # recompile evicted 16x16


def test_aot_compiled_program_executes_without_retrace():
    be = JaxBackend()
    impl = be.routine_impl("elemental", "multiply")
    plan = _plan(impl, {"A": (8, 8), "B": (8, 8)})
    program, info = be.get_or_compile(plan)
    assert info["aot"] and not info["cached"] and info["compile_s"] > 0
    a = np.eye(8, dtype=np.float32)
    outs = program({"i0": a, "i1": a * 2.0})
    np.testing.assert_allclose(np.asarray(outs[0]["C"]), a * 2.0)


# ---------------------------------------------------------------------------
# executable index
# ---------------------------------------------------------------------------
def test_executable_index_round_trips_plans(tmp_path):
    be = JaxBackend()
    impl = be.routine_impl("elemental", "multiply")
    plan = _plan(impl, {"A": (32, 16), "B": (16, 8)})
    idx = compilecache.ExecutableIndex(str(tmp_path))
    assert idx.record("jax", plan, compile_s=0.5)
    assert not idx.record("jax", plan)       # re-record is a no-op
    assert len(idx) == 1
    # reload from disk and rebuild the plan against a live backend
    idx2 = compilecache.ExecutableIndex(str(tmp_path))
    [rec] = idx2.entries(backend="jax")
    assert rec["label"] == "elemental.multiply"
    rebuilt = compilecache.plan_from_record(rec, be)
    assert rebuilt is not None
    assert rebuilt.signature() == plan.signature()
    assert idx2.entries(backend="reference") == []


def test_executable_index_concurrent_engines_merge_not_clobber(tmp_path):
    """Two engines sharing a cache dir each loaded the index before the
    other recorded: without merge-on-write the second save clobbers the
    first engine's record (last-write-wins). Both must survive."""
    be = JaxBackend()
    impl = be.routine_impl("elemental", "multiply")
    plan_a = _plan(impl, {"A": (32, 16), "B": (16, 8)})
    plan_b = _plan(impl, {"A": (64, 32), "B": (32, 8)})
    idx1 = compilecache.ExecutableIndex(str(tmp_path))
    idx2 = compilecache.ExecutableIndex(str(tmp_path))  # both loaded empty
    assert idx1.record("jax", plan_a)
    assert idx2.record("jax", plan_b)   # must fold idx1's record in
    fresh = compilecache.ExecutableIndex(str(tmp_path))
    labels = sorted((r["key"] for r in fresh.entries()))
    assert len(fresh) == 2
    assert {r["key"] for r in idx1.entries()} <= set(labels)

    # threaded stress: interleaved writers through separate instances
    # never lose a record
    shapes = [( (16 * (i + 1), 8), (8, 4) ) for i in range(8)]
    plans = [_plan(impl, {"A": sa, "B": sb}) for sa, sb in shapes]
    writers = [compilecache.ExecutableIndex(str(tmp_path))
               for _ in range(2)]
    threads = [
        threading.Thread(target=lambda w=writers[i % 2], p=p:
                         w.record("jax", p))
        for i, p in enumerate(plans)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(compilecache.ExecutableIndex(str(tmp_path))) == 2 + len(plans)


def test_executable_index_skips_unserializable_plans(tmp_path):
    be = JaxBackend()
    impl = be.routine_impl("elemental", "multiply")
    plan = _plan(impl, {"A": (8, 8), "B": (8, 8)})
    plan.input_specs = None                  # shape-blind: not replayable
    idx = compilecache.ExecutableIndex(str(tmp_path))
    assert not idx.record("jax", plan)
    assert len(idx) == 0


# ---------------------------------------------------------------------------
# CompileLog accounting
# ---------------------------------------------------------------------------
def test_compile_log_separates_request_from_warmup():
    from repro.core.costmodel import CompileLog

    log = CompileLog()
    log.record(1, "elemental.multiply", "compile", aot=True,
               bucketed=True, compile_s=0.5)
    log.record(-1, "elemental.gram", "compile", aot=True,
               on_request_path=False, compile_s=0.2)
    log.record(1, "elemental.multiply", "hit", bucketed=True)
    log.record(1, "elemental.multiply", "evict", count=2)
    s = log.stats()
    assert s["compiles"] == 2
    assert s["hits"] == 1
    assert s["request_compiles"] == 1
    assert s["warmup_compiles"] == 1
    assert s["request_compile_s"] == pytest.approx(0.5)
    assert s["warmup_compile_s"] == pytest.approx(0.2)
    assert s["bucketed_executions"] == 2
    assert s["bucketed_request_compiles"] == 1
    assert s["evictions"] == 2
    assert s["hit_rate"] == pytest.approx(1 / 3)
    per = log.session_summary(1)
    assert per["compiles"] == 1 and per["warmup_compiles"] == 0
    assert set(log.sessions()) == {1, -1}


# ---------------------------------------------------------------------------
# engine warmup: catalog AOT off the request path
# ---------------------------------------------------------------------------
def test_warmup_precompiles_catalog_and_absorbs_first_calls():
    # engine bucket grid == warmup grid: every odd dim pads to 64, so
    # the warmed 64-combos absorb ALL first calls (a warmup grid
    # narrower than the bucket grid only absorbs its own buckets)
    engine = fresh(bucketing=True, bucket_grid=(64,))
    ac = AlchemistContext(engine=engine)
    try:
        stats = engine.warmup(grid=(64,))
        assert stats["catalog"] >= len(BUCKETABLE_CASES)
        assert stats["compiled"] >= len(BUCKETABLE_CASES)
        log0 = engine.compile_log.stats()
        assert log0["warmup_compiles"] == stats["compiled"]
        assert log0["request_compiles"] == 0
        # first tenant calls at odd shapes bucketing to 64: all absorbed
        ha = ac.send_matrix(ODD_A, dedup=False)
        hb = ac.send_matrix(ODD_B, dedup=False)
        ac.call("elemental", "multiply", A=ha, B=hb)
        ac.call("elemental", "gram", A=ha)
        ac.call("elemental", "transpose", A=ha)
        log = engine.compile_log.stats()
        assert log["request_compiles"] == 0, log
        assert log["bucketed_request_compiles"] == 0
        assert log["hits"] >= 3
    finally:
        ac.stop()
        engine.shutdown()


def test_warmup_on_load_runs_in_background():
    engine = AlchemistEngine(make_engine_mesh(1), cache_entries=0,
                             warmup_on_load=True, warmup_grid=(32,))
    try:
        engine.load_library("elemental", elemental)
        engine.wait_warmup()
        s = engine.compile_log.stats()
        assert s["warmup_compiles"] >= len(BUCKETABLE_CASES)
        assert s["request_compiles"] == 0
    finally:
        engine.shutdown()


# ---------------------------------------------------------------------------
# persistence: warm-restart zero-recompile round trip
# ---------------------------------------------------------------------------
def test_warm_restart_replays_index_and_absorbs_requests(persistent_cache):

    def serve_one(eng):
        ac = AlchemistContext(engine=eng)
        try:
            ha = ac.send_matrix(ODD_A, dedup=False)
            hb = ac.send_matrix(ODD_B, dedup=False)
            res = ac.call("elemental", "multiply", A=ha, B=hb)
            return ac.fetch(res["C"]).collect()
        finally:
            ac.stop()

    # cold engine: the request-path compile lands in the index
    eng1 = fresh(bucketing=True)
    try:
        assert eng1.compile_cache_dir == persistent_cache
        out1 = serve_one(eng1)
        assert eng1.compile_log.stats()["request_compiles"] == 1
        assert len(eng1._exec_index) >= 1
    finally:
        eng1.shutdown()

    # restarted engine, same dir: warmup replays the index; the same
    # tenant traffic then sees ZERO request-path compiles
    eng2 = fresh(bucketing=True)
    try:
        stats = eng2.warmup()
        assert stats["replayed"] >= 1
        out2 = serve_one(eng2)
        log = eng2.compile_log.stats()
        assert log["request_compiles"] == 0, log
        assert log["hits"] >= 1
        np.testing.assert_allclose(out2, out1, rtol=1e-5)
    finally:
        eng2.shutdown()


# ---------------------------------------------------------------------------
# fused chains: results unchanged bucketing on/off
# ---------------------------------------------------------------------------
def _burst_chain(ac, stages=3):
    el = ac.library("elemental")
    al = ac.send_matrix(ODD_SQ, dedup=False)
    ac.engine.scheduler.pause()
    x = al
    for _ in range(stages):
        x = el.multiply(A=x, B=al)
    ac.engine.scheduler.resume()
    return x.to_numpy()


def _settled_task_stats(engine, commands, timeout=5.0):
    """Task-log records land via the scheduler completion hook, slightly
    after the client sees the result — poll until every command's record
    arrived before asserting on the accounting."""
    import time as _time

    deadline = _time.monotonic() + timeout
    while _time.monotonic() < deadline:
        s = engine.task_log.stats()
        if s["commands"] >= commands:
            return s
        _time.sleep(0.01)
    return engine.task_log.stats()


@pytest.mark.parametrize("bucketing", [True, False])
def test_fused_chain_results_unchanged_by_bucketing(bucketing):
    engine = fresh(bucketing=bucketing)
    ac = AlchemistContext(engine=engine)
    try:
        got = _burst_chain(ac)
        want = ODD_SQ
        for _ in range(3):
            want = want @ ODD_SQ
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)
        stats = _settled_task_stats(engine, commands=3)
        assert stats["fused_tasks"] >= 1, stats   # the chain really fused
        log = engine.compile_log.stats()
        if bucketing:
            assert log["bucketed_executions"] >= 1
        else:
            assert log["bucketed_executions"] == 0
    finally:
        ac.stop()
        engine.shutdown()


def test_session_bucketing_override_vs_engine_default():
    engine = fresh(bucketing=True)
    ac_off = AlchemistContext(engine=engine, bucketing=False)
    ac_on = AlchemistContext(engine=engine)
    try:
        ha = ac_off.send_matrix(ODD_A, dedup=False)
        ac_off.call("elemental", "gram", A=ha)
        assert engine.compile_log.stats()["bucketed_executions"] == 0
        hb = ac_on.send_matrix(ODD_A, dedup=False)
        ac_on.call("elemental", "gram", A=hb)
        assert engine.compile_log.stats()["bucketed_executions"] == 1
    finally:
        ac_off.stop()
        ac_on.stop()
        engine.shutdown()


# ---------------------------------------------------------------------------
# configure wire surface
# ---------------------------------------------------------------------------
def _configure_raw(engine, session, options):
    """A configure request as any wire client could send it, bypassing
    the typed client signature."""
    from repro.core import protocol

    return protocol.decode_result(engine.configure(
        protocol.encode_configure(protocol.Configure(
            session=session, options=options))))


def test_configure_echoes_bucketing_and_cache_dir(persistent_cache):
    """The engine keeps its index in the one cache directory the process
    resolved; no configure option can repoint it."""
    engine = fresh()
    ac = AlchemistContext(engine=engine)
    try:
        eff = ac.configure(bucketing=False)
        assert eff["bucketing"] is False
        eff = ac.configure(bucketing=True)
        assert eff["bucketing"] is True
        assert "cache_dir" not in eff
        assert engine.compile_cache_dir == persistent_cache
        res = _configure_raw(engine, ac.session, {"cache_dir": "/elsewhere"})
        assert "unknown configure option" in res.error
        assert "cache_dir" in res.error
        assert engine.compile_cache_dir == persistent_cache
    finally:
        ac.stop()
        engine.shutdown()


def test_configure_warmup_over_the_wire_returns_counts():
    engine = fresh()
    ac = AlchemistContext(engine=engine)
    try:
        eff = ac.configure(warmup=[32])
        w = eff["warmup"]
        assert w["backend"] == "jax"
        assert w["catalog"] >= len(BUCKETABLE_CASES)
        assert engine.compile_log.stats()["request_compiles"] == 0
    finally:
        ac.stop()
        engine.shutdown()


def test_configure_rejects_bad_options_without_mutating():
    engine = fresh()
    ac = AlchemistContext(engine=engine)
    try:
        with pytest.raises(AlchemistError, match="bucketing"):
            ac.configure(bucketing="yes")
        with pytest.raises(AlchemistError, match="warmup"):
            ac.configure(warmup=[0])
        with pytest.raises(AlchemistError, match="warmup"):
            ac.configure(warmup="now")
        with pytest.raises(TypeError, match="cache_dir"):
            ac.configure(cache_dir=7)
        assert "cache_dir" in _configure_raw(
            engine, ac.session, {"cache_dir": 7}).error
        sess = engine.session(ac.session)
        assert sess.bucketing is None        # nothing half-applied
        assert engine.compile_cache_dir is None
    finally:
        ac.stop()
        engine.shutdown()


def test_compile_stats_builtin_over_the_wire():
    engine = fresh(bucketing=True)
    ac = AlchemistContext(engine=engine)
    try:
        ha = ac.send_matrix(ODD_A, dedup=False)
        ac.call("elemental", "gram", A=ha)
        stats = ac.call("_engine", "compile_stats")
        assert stats["session"]["session"] == ac.session
        assert stats["session"]["compiles"] == 1
        assert stats["engine"]["bucketed_executions"] == 1
        assert "program_caches" in stats["engine"]
    finally:
        ac.stop()
        engine.shutdown()


# ---------------------------------------------------------------------------
# the one cache-directory rule
# ---------------------------------------------------------------------------
def test_cache_dir_is_the_environment_variable_when_set(monkeypatch,
                                                        tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
    assert compilecache.cache_dir() == str(tmp_path / "x")


def test_cache_dir_is_the_fixed_checkout_path_when_unset(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compilecache.cache_dir() == os.path.join(root, ".jax_cache")
    # fixed: the same path on every call, in every process
    assert compilecache.cache_dir() == compilecache.DEFAULT_CACHE_DIR


def test_engine_without_a_persistent_cache_keeps_no_index():
    engine = fresh()
    try:
        assert engine.compile_cache_dir is None
        assert engine.compile_stats()["executable_index"] == 0
    finally:
        engine.shutdown()
