"""Backend ABI conformance suite: every cataloged routine served by both
backends from the same inputs, with numerically-close results and
identical output specs/layout metadata; layout negotiation (explicit
relayout, counted); backend selection over the ``configure`` endpoint;
cache isolation between backends; and the dist-sharding output
guarantee (no routine output drops the engine layout)."""
import numpy as np
import pytest

from repro.core import AlchemistContext, AlchemistEngine, backends
from repro.core.context import AlchemistError
from repro.core.engine import make_engine_mesh
from repro.core.handles import MatrixHandle
from repro.core.libraries import elemental, mllib, skylark

RNG = np.random.RandomState(7)

# deterministic float32 inputs; SVD-family cases get a well-separated
# spectrum so singular vectors are stable across implementations
X = (RNG.randn(48, 12) @ np.diag(np.geomspace(8.0, 0.1, 12))).astype(
    np.float32)
Y = RNG.randn(48, 3).astype(np.float32)
SQ = (RNG.randn(16, 16) / 4.0).astype(np.float32)
POS = np.abs(RNG.randn(24, 10)).astype(np.float32)

BUNDLED = (("elemental", elemental), ("skylark", skylark),
           ("mllib", mllib))


@pytest.fixture(scope="module")
def rig():
    engine = AlchemistEngine(make_engine_mesh(1), cache_entries=0)
    for name, module in BUNDLED:
        engine.load_library(name, module)
    ac_jax = AlchemistContext(engine=engine)            # engine default
    ac_ref = AlchemistContext(engine=engine, backend="reference")
    yield engine, ac_jax, ac_ref
    ac_jax.stop()
    ac_ref.stop()
    engine.shutdown()


def run_on(ac, library, routine, arrays, scalars):
    """Upload ``arrays``, invoke, fetch handle outputs; returns
    (raw result, fetched arrays, per-output (shape, dtype, layout))."""
    handles = {k: ac.send_matrix(v, dedup=False) for k, v in arrays.items()}
    res = ac.call(library, routine, **handles, **scalars)
    outs, meta = {}, {}
    for k, v in res.items():
        if isinstance(v, MatrixHandle):
            meta[k] = (tuple(v.shape), v.dtype, v.layout)
            outs[k] = ac.fetch(v).collect()
    return res, outs, meta


def run_both(rig, library, routine, arrays, scalars=None):
    _, ac_jax, ac_ref = rig
    scalars = scalars or {}
    _, out_j, meta_j = run_on(ac_jax, library, routine, arrays, scalars)
    _, out_r, meta_r = run_on(ac_ref, library, routine, arrays, scalars)
    # identical output specs/layout metadata — the ABI contract
    assert meta_j == meta_r, (library, routine, meta_j, meta_r)
    assert set(out_j) == set(out_r)
    return out_j, out_r


# ---------------------------------------------------------------------------
# ABI coverage
# ---------------------------------------------------------------------------
def test_every_cataloged_routine_registered_on_both_backends(rig):
    engine, _, _ = rig
    for backend in engine.backends.values():
        for lib, module in BUNDLED:
            for rn in module.ROUTINES:
                assert backend.supports(lib, rn), (backend.name, lib, rn)


def test_library_functions_are_catalog_only():
    """The engine never calls library functions; a direct call says so."""
    with pytest.raises(NotImplementedError, match="per-backend"):
        elemental.multiply(None, 1, 2)
    with pytest.raises(NotImplementedError, match="per-backend"):
        skylark.cg_solve(None, 1, 2)


def test_backend_registry_and_capabilities(rig):
    engine, _, _ = rig
    assert set(backends.available_backends()) >= {"jax", "reference"}
    caps_jax = engine.backends["jax"].capabilities()
    caps_ref = engine.backends["reference"].capabilities()
    assert caps_jax["supports_fusion"] and not caps_ref["supports_fusion"]
    assert "elemental.gram" in caps_jax["routines"]
    with pytest.raises(backends.BackendError, match="available"):
        backends.create_backend("nope")


# ---------------------------------------------------------------------------
# conformance: deterministic routines agree closely
# ---------------------------------------------------------------------------
def test_conformance_multiply(rig):
    out_j, out_r = run_both(rig, "elemental", "multiply",
                            {"A": X, "B": np.ascontiguousarray(X.T)})
    np.testing.assert_allclose(out_j["C"], out_r["C"], rtol=2e-4,
                               atol=1e-3)


def test_conformance_add(rig):
    out_j, out_r = run_both(rig, "elemental", "add", {"A": X, "B": X})
    np.testing.assert_allclose(out_j["C"], out_r["C"], rtol=1e-6)


def test_conformance_transpose(rig):
    out_j, out_r = run_both(rig, "elemental", "transpose", {"A": X})
    np.testing.assert_allclose(out_j["C"], out_r["C"], rtol=1e-6)
    np.testing.assert_allclose(out_j["C"], X.T, rtol=1e-6)


def test_conformance_replicate_cols(rig):
    out_j, out_r = run_both(rig, "elemental", "replicate_cols", {"A": X},
                            {"times": 3})
    np.testing.assert_allclose(out_j["A"], out_r["A"], rtol=1e-6)


def test_conformance_gram(rig):
    out_j, out_r = run_both(rig, "elemental", "gram", {"A": X})
    np.testing.assert_allclose(out_j["G"], out_r["G"], rtol=1e-3,
                               atol=1e-2)


def test_conformance_qr(rig):
    out_j, out_r = run_both(rig, "elemental", "qr", {"A": X})

    def canon(q, r):
        # fix the per-column sign ambiguity: make diag(R) positive
        s = np.sign(np.diag(r))
        s[s == 0] = 1.0
        return q * s, r * s[:, None]

    qj, rj = canon(out_j["Q"], out_j["R"])
    qr_, rr = canon(out_r["Q"], out_r["R"])
    np.testing.assert_allclose(qj, qr_, atol=2e-3)
    np.testing.assert_allclose(rj, rr, rtol=2e-3, atol=2e-3)


def _assert_svd_close(out_j, out_r, k, atol_v=2e-2):
    np.testing.assert_allclose(out_j["S"].ravel(), out_r["S"].ravel(),
                               rtol=2e-3)
    # singular vectors agree up to sign with a separated spectrum
    vj, vr = out_j["V"], out_r["V"]
    dots = np.abs(np.sum(vj * vr, axis=0))
    np.testing.assert_allclose(dots, np.ones(k), atol=atol_v)


def test_conformance_truncated_svd(rig):
    out_j, out_r = run_both(rig, "elemental", "truncated_svd", {"A": X},
                            {"k": 4})
    _assert_svd_close(out_j, out_r, 4)
    want = np.linalg.svd(X, compute_uv=False)[:4]
    np.testing.assert_allclose(out_j["S"].ravel(), want, rtol=1e-3)


def test_conformance_gram_svd(rig):
    out_j, out_r = run_both(rig, "elemental", "gram_svd", {"A": X},
                            {"k": 4})
    _assert_svd_close(out_j, out_r, 4)


def test_conformance_randomized_svd(rig):
    out_j, out_r = run_both(rig, "elemental", "randomized_svd", {"A": X},
                            {"k": 3, "power_iters": 3})
    # different PRNGs sketch differently; with power iteration both
    # recover the well-separated top singular values
    want = np.linalg.svd(X, compute_uv=False)[:3]
    np.testing.assert_allclose(out_j["S"].ravel(), want, rtol=1e-2)
    np.testing.assert_allclose(out_r["S"].ravel(), want, rtol=1e-2)


def test_conformance_cg_solve(rig):
    out_j, out_r = run_both(rig, "skylark", "cg_solve",
                            {"X": X, "Y": Y},
                            {"lam": 1e-3, "max_iters": 400, "tol": 1e-10})
    np.testing.assert_allclose(out_j["W"], out_r["W"], atol=1e-4)
    want = np.linalg.solve(
        X.T.astype(np.float64) @ X + 48 * 1e-3 * np.eye(12),
        X.T.astype(np.float64) @ Y)
    np.testing.assert_allclose(out_j["W"], want, atol=1e-3)


@pytest.mark.parametrize("n", [2048, 2500])
def test_cg_true_residual_by_row_blocks(n):
    """The residual the jax cg_solve reports is that of the normal
    equations on the W given, summed over whole row blocks and a ragged
    tail alike."""
    from repro.core.backends import jax_backend
    rng = np.random.default_rng(n)
    x = rng.standard_normal((n, 16)).astype(np.float32)
    y = rng.standard_normal((n, 3)).astype(np.float32)
    w = (1e-3 * rng.standard_normal((16, 3))).astype(np.float32)
    lam_n = 0.5
    x64, y64, w64 = (a.astype(np.float64) for a in (x, y, w))
    b64 = x64.T @ y64
    b_norm = np.linalg.norm(b64, axis=0)
    r64 = b64 - x64.T @ (x64 @ w64) - lam_n * w64
    want = np.max(np.linalg.norm(r64, axis=0) / b_norm)
    got = float(jax_backend._cg_residual(x, y, np.float32(lam_n),
                                         b_norm.astype(np.float32), w))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_conformance_random_matrix_distribution(rig):
    """Seeded creation: cross-backend bitwise equality is not promised
    (numpy cannot replay jax's counter PRNG) — the contract is the spec
    (shape/dtype/layout, asserted by run_both) plus the distribution."""
    out_j, out_r = run_both(rig, "elemental", "random_matrix", {},
                            {"rows": 256, "cols": 64, "seed": 3,
                             "scale": 2.0})
    for out in (out_j["A"], out_r["A"]):
        assert out.shape == (256, 64) and out.dtype == np.float32
        assert abs(float(out.mean())) < 0.1
        assert abs(float(out.std()) - 2.0) < 0.1


def test_conformance_random_features_distribution(rig):
    out_j, out_r = run_both(rig, "skylark", "random_features", {"X": X},
                            {"rf_dim": 64, "bandwidth": 2.0, "seed": 1})
    bound = np.sqrt(2.0 / 64) + 1e-6
    for out in (out_j["Z"], out_r["Z"]):
        assert out.shape == (48, 64)
        assert float(np.abs(out).max()) <= bound
    assert abs(float(out_j["Z"].std()) - float(out_r["Z"].std())) < 0.05


def test_conformance_nmf_invariants(rig):
    out_j, out_r = run_both(rig, "skylark", "nmf", {"A": POS},
                            {"k": 4, "max_iters": 60})
    for out in (out_j, out_r):
        assert (out["W"] >= 0).all() and (out["H"] >= 0).all()
    resid_j, _, _ = _nmf_resid(out_j)
    resid_r, _, _ = _nmf_resid(out_r)
    assert abs(resid_j - resid_r) < 0.15


def _nmf_resid(out):
    w, h = out["W"], out["H"]
    resid = float(np.linalg.norm(POS - w @ h) / np.linalg.norm(POS))
    return resid, w, h


def test_conformance_mllib_shared_baseline(rig):
    """mllib is backend-invariant by design (shared row-partitioned host
    math): both backends must agree to float precision, and report the
    same BSP accounting."""
    res_j, out_j, meta_j = run_on(rig[1], "mllib", "cg_solve",
                                  {"X": X, "Y": Y}, {"lam": 1e-3})
    res_r, out_r, meta_r = run_on(rig[2], "mllib", "cg_solve",
                                  {"X": X, "Y": Y}, {"lam": 1e-3})
    assert meta_j == meta_r
    np.testing.assert_allclose(out_j["W"], out_r["W"], atol=1e-5)
    assert res_j["bsp_rounds"] == res_r["bsp_rounds"]
    res_j, out_j, _ = run_on(rig[1], "mllib", "truncated_svd", {"A": X},
                             {"k": 3})
    res_r, out_r, _ = run_on(rig[2], "mllib", "truncated_svd", {"A": X},
                             {"k": 3})
    np.testing.assert_allclose(out_j["S"], out_r["S"], rtol=1e-5)
    assert res_j["lanczos_iters"] == res_r["lanczos_iters"]


# ---------------------------------------------------------------------------
# layouts: real tags, negotiation, dist-sharded outputs
# ---------------------------------------------------------------------------
def test_uploads_and_outputs_carry_real_layouts(rig):
    engine, ac, _ = rig
    al = ac.send_matrix(SQ, dedup=False)
    assert al.handle.layout == "rowblock"
    assert engine.layout(al.handle) == "rowblock"
    out = ac.call("elemental", "transpose", A=al)["C"]
    assert out.layout == "rowblock"


def test_routine_outputs_land_in_engine_dist_sharding(rig):
    """The satellite fix: transpose/add/multiply must not return
    host-materialized arrays that drop the distributed sharding — every
    output goes through the engine's dist-sharding put path."""
    engine, ac, ac_ref = rig
    al = ac.send_matrix(SQ, dedup=False)
    for routine, kwargs in (("transpose", {"A": al}),
                            ("add", {"A": al, "B": al}),
                            ("multiply", {"A": al, "B": al})):
        for ctx in (ac, ac_ref):
            a = ctx.send_matrix(SQ, dedup=False)
            kw = {k: a for k in kwargs}
            res = ctx.call("elemental", routine, **kw)
            arr = engine.get(res["C"], session=ctx.session)
            assert arr.sharding == engine.dist_sharding(arr.shape), \
                (routine, ctx.backend)


def test_foreign_layout_triggers_counted_relayout(rig):
    """An operand in a layout the implementation does not accept gets an
    explicit relayout step, charged to the task's accounting."""
    engine, ac, _ = rig
    import jax.numpy as jnp

    arr = jnp.asarray(SQ)
    h = engine.put(arr, session=ac.session, layout="block2d")
    before = engine.task_log.stats()
    res = ac.call("elemental", "gram", A=ac.wrap(h))
    after = engine.task_log.stats()
    assert after["relayouts"] == before["relayouts"] + 1
    assert after["relayout_bytes"] == before["relayout_bytes"] + SQ.nbytes
    g = ac.fetch(res["G"]).collect()
    np.testing.assert_allclose(g, SQ.T @ SQ, rtol=1e-3, atol=1e-3)


def test_accepted_layouts_do_not_relayout(rig):
    engine, ac, _ = rig
    al = ac.send_matrix(SQ, dedup=False)          # rowblock: accepted
    before = engine.task_log.stats()["relayouts"]
    ac.call("elemental", "gram", A=al)
    assert engine.task_log.stats()["relayouts"] == before


# ---------------------------------------------------------------------------
# backend selection (configure endpoint / context kwarg)
# ---------------------------------------------------------------------------
def test_configure_selects_backend_per_session(rig):
    engine, ac_jax, ac_ref = rig
    assert ac_jax.backend == "jax"
    assert ac_ref.backend == "reference"
    # per-session: the jax session is unaffected by the reference one
    sess = engine.session(ac_ref.session)
    assert sess.backend == "reference"
    assert engine.session(ac_jax.session).backend in ("", "jax")


def test_configure_rejects_unknown_backend_and_options(rig):
    engine, ac, _ = rig
    with pytest.raises(AlchemistError, match="available"):
        ac.configure(backend="cuda")
    with pytest.raises(AlchemistError, match="unknown configure option"):
        from repro.core import protocol
        res = protocol.decode_result(engine.configure(
            protocol.encode_configure(protocol.Configure(
                session=ac.session, options={"turbo": True}))))
        raise AlchemistError(res.error)
    # the failed attempts changed nothing
    assert ac.backend == "jax"


def test_configure_error_applies_nothing(rig):
    """A configure request that errors must be atomic: a valid backend
    option in the same message as a bad fusion option changes nothing."""
    engine, ac, _ = rig
    from repro.core import protocol
    res = protocol.decode_result(engine.configure(
        protocol.encode_configure(protocol.Configure(
            session=ac.session,
            options={"backend": "reference", "fusion": "yes"}))))
    assert "fusion" in res.error
    sess = engine.session(ac.session)
    assert sess.backend in ("", "jax") and sess.fusion is True


def test_bad_backend_at_construction_leaks_no_session():
    engine = AlchemistEngine(make_engine_mesh(1), cache_entries=0)
    try:
        before = len(engine.sessions())
        with pytest.raises(AlchemistError, match="available"):
            AlchemistContext(engine=engine, backend="nope")
        assert len(engine.sessions()) == before
    finally:
        engine.shutdown()


def test_configure_fusion_toggle_roundtrip(rig):
    engine, _, _ = rig
    ac = AlchemistContext(engine=engine, fusion=False)
    try:
        assert engine.session(ac.session).fusion is False
        assert ac.configure(fusion=True)["fusion"] is True
    finally:
        ac.stop()


def test_engine_rejects_unknown_default_backend():
    with pytest.raises(backends.BackendError, match="available"):
        AlchemistEngine(make_engine_mesh(1), backend="nope")


def test_system_session_cannot_be_configured(rig):
    engine, _, _ = rig
    from repro.core import protocol
    res = protocol.decode_result(engine.configure(
        protocol.encode_configure(protocol.Configure(
            session=0, options={"backend": "jax"}))))
    assert "system session" in res.error


# ---------------------------------------------------------------------------
# cache isolation between backends
# ---------------------------------------------------------------------------
def test_cache_keys_are_backend_scoped():
    """A jax-computed result must never be served to a reference
    session (recomputing with the other implementation is its point) —
    but each backend hits its own cache."""
    engine = AlchemistEngine(make_engine_mesh(1), cache_entries=64)
    engine.load_library("elemental", elemental)
    ac_j = AlchemistContext(engine=engine)
    ac_r = AlchemistContext(engine=engine, backend="reference")
    try:
        a = RNG.randn(16, 4).astype(np.float32)
        r1 = ac_j.call("elemental", "gram", A=ac_j.send_matrix(a))
        assert not r1["_cache_hit"]
        r2 = ac_j.call("elemental", "gram", A=ac_j.send_matrix(a))
        assert r2["_cache_hit"]                     # same backend: hit
        r3 = ac_r.call("elemental", "gram", A=ac_r.send_matrix(a))
        assert not r3["_cache_hit"]                 # other backend: miss
        r4 = ac_r.call("elemental", "gram", A=ac_r.send_matrix(a))
        assert r4["_cache_hit"]
    finally:
        ac_j.stop()
        ac_r.stop()
        engine.shutdown()


def test_legacy_ali_library_runs_on_any_backend():
    """Unregistered third-party routines dispatch through the ABI's
    legacy wrapper on every backend — old libraries keep working."""
    def doubled(eng, A):
        import jax.numpy as jnp
        return {"C": eng.put(jnp.asarray(eng.get(A)) * 2.0)}

    class _Lib:
        ROUTINES = {"doubled": doubled}

    engine = AlchemistEngine(make_engine_mesh(1), cache_entries=0)
    engine.load_library("thirdparty", _Lib)
    for backend in ("jax", "reference"):
        ac = AlchemistContext(engine=engine, backend=backend)
        try:
            al = ac.send_matrix(SQ, dedup=False)
            out = ac.call("thirdparty", "doubled", A=al)
            got = ac.fetch(out["C"]).collect()
            np.testing.assert_allclose(got, 2.0 * SQ, rtol=1e-6)
        finally:
            ac.stop()
    engine.shutdown()


def test_backend_registries_have_identical_catalog_metadata():
    """The parity invariant CAT001-004 gates in CI, asserted directly:
    both bundled backends serve the same (library, routine) set with
    matching fusible/bucketable flags and shape-rule coverage — the
    flags describe the routine, so which backend executes must never
    change what fuses or what warmup can bucket."""
    jax_be = backends.create_backend("jax")
    ref_be = backends.create_backend("reference")
    assert jax_be.routines() == ref_be.routines()
    for lib, rt in jax_be.routines():
        a = jax_be.routine_impl(lib, rt)
        b = ref_be.routine_impl(lib, rt)
        assert a.fusible == b.fusible, (lib, rt)
        assert a.bucketable == b.bucketable, (lib, rt)
        assert (a.out_shapes is None) == (b.out_shapes is None), (lib, rt)
        if a.bucketable:
            assert a.out_shapes is not None, (lib, rt)
