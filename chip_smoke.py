"""Smoke run of Alchemist's served path on a TPU.

One process holds the chip: an ``AlchemistServer`` on a localhost port
around an ``AlchemistEngine`` on the chip, and client
``AlchemistContext(address=...)`` sessions that drive it over real TCP.
Data is generated from ``--seed``; every result is checked against the
float64 reference (``core/backends/reference.py``) or a float64
evaluation on the host.

Phases, at the paper's widths with rows cut to fit one chip:

* ocean SVD (paper §4.2, Table 5): a 65,536 x 8,096 float32 field with a
  20-mode spectrum over noise, ``truncated_svd(k=20)``, U streamed back;
  then ``gram_svd(use_pallas=True)`` on the same handle, whose program
  must contain the compiled Gram kernel;
* speech CG (paper §4.1, Table 2): 131,072 x 440 features and 147-class
  one-hot labels, ``cg_solve(rf_dim=10_000)`` for a fixed iteration
  budget, on the default path and with ``use_pallas=True``;
* two tenants: while one session runs a long ``truncated_svd``, another
  runs a lazy ``(Q.T @ Q) + R`` chain and a ``gram`` at two shapes; the
  chains must fuse and be answered.

``--chips 4`` runs only the four-chip path: a 262,144 x 8,096 row-block
operand on ``make_engine_mesh(4)``, ``truncated_svd``, a fused bucketed
chain and ``gram``, each compared with the same call on a one-device
engine in the same process.

Lines that start with ``smoke`` are smoke output (timings, bytes,
compile counts, peak device memory), not benchmark results. The last
line is one JSON object naming the device. The script exits non-zero,
printing no such line, when JAX finds no TPU, when a phase raises or
returns an error, or when a check fails.

Run: ``python chip_smoke.py [--seed N] [--chips 4]``
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro import kernels  # noqa: E402
from repro.core import AlchemistContext, AlchemistEngine, \
    compilecache  # noqa: E402
from repro.core.backends import jax_backend, reference  # noqa: E402
from repro.core.engine import make_engine_mesh  # noqa: E402
from repro.core.libraries import elemental, skylark  # noqa: E402
from repro.core.server import AlchemistServer  # noqa: E402

# ---- sizes: published widths, rows cut to the chip ------------------------
# Ocean (Table 5): 6,177,583 x 8,096, rank 20. 65,536 rows are 2.1 GB of
# float32, the one-chip share that leaves room for gram_svd's padded copy.
OCEAN = {"rows": 65_536, "cols": 8_096, "k": 20}
# Speech (Table 2): 2,251,569 x 440 raw features, 147 classes, expanded
# to 10,000 random features (5.2 GB on the device at 131,072 rows).
SPEECH = {"rows": 131_072, "feats": 440, "classes": 147, "rf_dim": 10_000,
          "iters": 100, "bandwidth": 20.0, "lam": 1e-5}
# Two tenants: the heavy session's operand and the light chain's shapes.
TENANTS = {"heavy": (65_536, 4_096), "heavy_iters": 2_000,
           "shapes": ((1_000, 64), (4_096, 256))}
# Four chips: the ocean width at four times the rows (2.1 GB per chip).
FOUR = {"rows": 262_144, "cols": 8_096, "k": 20}

# ---- tolerances, each with its reason --------------------------------------
# Singular values vs the float64 references, relative. At full float32
# precision a v5e read 8.5e-8 (Lanczos) and 4.2e-8 (gram_svd); at the
# default precision the Pallas Gram kernel read 5.0e-6, which this
# rejects (the Lanczos matvec, a matrix-vector product, read the same).
S_RTOL = 1e-6
# sin of the largest principal angle between the engine's and the
# reference's rank-k right singular subspaces: float32 rounding over a
# spectral gap of >= 3x leaves it small (a v5e read 1.2e-6).
SUBSPACE_TOL = 1e-3
# ||A - U S V^T||_F, engine vs reference, relative. The noise beyond
# rank k dominates the error, so this checks the rank-k fit, not the
# precision: a v5e read 1.9e-14 at full and 8.4e-9 at default precision.
RECON_RTOL = 1e-4
# CG, reported vs float64: the residual the routine reports (its own
# float32 evaluation, by blocks of rows, on the W it returns) must agree,
# relatively, with a float64 evaluation on the host on the same
# features; a v5e read 0.16% and 0.056% apart. A residual carried by the
# CG recurrence (7e-8 against 1.9e-5 on a v5e), or taken in one float32
# product over all rows (1.3e-5), fails this.
CG_RESID_RTOL = 2e-2
# CG, float64 residual on features rebuilt in float64 (the problem as
# posed, not as the engine rounded it) after the fixed budget. At full
# float32 precision a v5e reached 1.9e-5 on both paths; at the default
# precision it stalled at 7.4e-3 and 1.1e-2, which this rejects.
CG_HOST_MAX = 1e-4
# the fixed budget must make progress: the reported residual starts at 1
CG_PROGRESS = 0.5
# Elemental chain and gram vs float64, relative to the largest entry:
# float32 accumulation over at most 4,096 rows. At the default precision
# a v5e's gram read 5.1e-4, which this rejects.
CHAIN_RTOL = 1e-4
# Four-chip vs one-device engine, relative to the largest entry: the same
# float32 programs, reduced in a different order across devices.
MESH_RTOL = 1e-4

WATCHDOG_S = 1_140


def _say(tag: str, **fields) -> None:
    print(f"smoke {tag} " + json.dumps(fields, default=float), flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"check failed: {what}")


def _peak_bytes() -> dict:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return {"peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_in_use": stats.get("bytes_in_use")}


# ---------------------------------------------------------------------------
# data, generated in bulk from the seed
# ---------------------------------------------------------------------------
def ocean_field(rows: int, cols: int, k: int, seed: int) -> np.ndarray:
    """A float32 field with k dominant modes over unit white noise. The
    modes' singular values fall geometrically from 16 sqrt(rows) to
    4 sqrt(rows), at least 3x the noise edge sqrt(rows) + sqrt(cols), so
    the top-k subspace is well separated (as a field's leading EOFs are)."""
    rng = np.random.default_rng(seed)
    left = rng.standard_normal((rows, k), dtype=np.float32)
    left /= np.linalg.norm(left, axis=0)
    right, _ = np.linalg.qr(rng.standard_normal((cols, k)))
    sigma = np.sqrt(rows) * np.geomspace(16.0, 4.0, k)
    field = rng.standard_normal((rows, cols), dtype=np.float32)
    field += (left * sigma.astype(np.float32)) @ right.T.astype(np.float32)
    return field


def speech_data(rows: int, feats: int, classes: int, seed: int
                ) -> tuple[np.ndarray, np.ndarray]:
    """Standardized features drawn around one Gaussian centre per class,
    and the one-hot label matrix (what the paper's TIMIT run solves)."""
    rng = np.random.default_rng(seed + 1)
    labels = rng.integers(0, classes, rows)
    centres = rng.standard_normal((classes, feats), dtype=np.float32)
    x = rng.standard_normal((rows, feats), dtype=np.float32)
    x += 0.5 * centres[labels]
    y = np.zeros((rows, classes), np.float32)
    y[np.arange(rows), labels] = 1.0
    return x, y


# ---------------------------------------------------------------------------
# host float64 checks
# ---------------------------------------------------------------------------
def _subspace_sin(v1: np.ndarray, v2: np.ndarray) -> float:
    q1, _ = np.linalg.qr(np.asarray(v1, np.float64))
    q2, _ = np.linalg.qr(np.asarray(v2, np.float64))
    cos = np.linalg.svd(q1.T @ q2, compute_uv=False)
    return float(np.sqrt(max(0.0, 1.0 - float(cos.min()) ** 2)))


def _recon_error(a64: np.ndarray, sq_a: float, u, s, v) -> float:
    """||A - U diag(s) V^T||_F without forming the product:
    ||A||^2 - 2 sum_i s_i u_i^T A v_i + ||U S V^T||^2."""
    u = np.asarray(u, np.float64)
    s = np.asarray(s, np.float64).ravel()
    v = np.asarray(v, np.float64)
    av = a64 @ v
    cross = float(np.sum(s * np.sum(u * av, axis=0)))
    usv = float(np.sum(((u.T @ u) * s[:, None] * s[None, :]) * (v.T @ v)))
    return float(np.sqrt(max(0.0, sq_a - 2.0 * cross + usv)))


def _cg_host_residuals(features, n: int, y: np.ndarray, lam: float,
                       ws: list, block: int = 8192) -> list[float]:
    """max over classes of ||Z^T Y - (Z^T Z + n lam I) W|| / ||Z^T Y|| for
    each W, in float64; ``features(lo, hi)`` gives rows lo:hi of Z."""
    w64 = np.concatenate([np.asarray(w, np.float64) for w in ws], axis=1)
    rhs = None
    for lo in range(0, n, block):
        z = np.asarray(features(lo, min(n, lo + block)), np.float64)
        if rhs is None:
            rhs = np.zeros((z.shape[1], y.shape[1]))
            gram_w = np.zeros_like(w64)
        rhs += z.T @ y[lo:lo + block]
        gram_w += z.T @ (z @ w64)
    out = []
    c = y.shape[1]
    for i in range(len(ws)):
        wi = w64[:, i * c:(i + 1) * c]
        r = rhs - gram_w[:, i * c:(i + 1) * c] - n * lam * wi
        out.append(float(np.max(np.linalg.norm(r, axis=0)
                                / np.linalg.norm(rhs, axis=0))))
    return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def start_server(engine: AlchemistEngine) -> AlchemistServer:
    """The served path: a TCP server around the engine, libraries loaded
    over the wire by a client."""
    server = AlchemistServer(engine=engine, host="127.0.0.1", port=0).start()
    with AlchemistContext(address=server.address,
                          client_name="loader") as ac:
        ac.register_library("elemental", elemental)
        ac.register_library("skylark", skylark)
    return server


def phase_ocean_svd(server: AlchemistServer, rows: int, cols: int, k: int,
                    seed: int) -> dict:
    """Truncated SVD of the ocean field over TCP, checked against the
    float64 Lanczos reference; then gram_svd with the Pallas Gram kernel
    on the same handle, checked against a float64 dense eigensolve."""
    t0 = time.perf_counter()
    a = ocean_field(rows, cols, k, seed)
    t_data = time.perf_counter() - t0
    out: dict = {"rows": rows, "cols": cols, "k": k}
    with AlchemistContext(address=server.address, client_name="ocean") as ac:
        el = ac.library("elemental")
        t0 = time.perf_counter()
        A = ac.send_matrix(a)
        A.result()
        out["send_s"] = time.perf_counter() - t0
        out["send_bytes"] = int(a.nbytes)
        out["wire_bytes"] = int(A.last_transfer.wire_nbytes)

        t0 = time.perf_counter()
        U, S, V = el.truncated_svd(A, k=k)
        stats = U.stats()
        out["svd_s"] = time.perf_counter() - t0
        out["lanczos_iters"] = stats["lanczos_iters"]
        t0 = time.perf_counter()
        u = U.to_numpy()
        out["fetch_u_s"] = time.perf_counter() - t0
        s = S.to_numpy().ravel()
        v = V.to_numpy()

        t0 = time.perf_counter()
        ref = reference._truncated_svd(a, k=k)
        out["reference_s"] = time.perf_counter() - t0
        s_ref = np.asarray(ref["S"], np.float64)
        out["s_max_rel_err"] = float(np.max(np.abs(s - s_ref) / s_ref))
        out["subspace_sin"] = _subspace_sin(v, ref["V"])
        a64 = a.astype(np.float64)
        sq_a = float(np.sum(a64 * a64))
        e_dev = _recon_error(a64, sq_a, u, s, v)
        e_ref = _recon_error(a64, sq_a, ref["U"], ref["S"], ref["V"])
        out["recon_rel_err"] = abs(e_dev - e_ref) / e_ref
        # gram_svd's reference: the float64 Gram matrix's eigenvalues,
        # by a dense solver that no engine routine shares
        t0 = time.perf_counter()
        evals = np.linalg.eigvalsh(a64.T @ a64)
        s_eig = np.sqrt(np.maximum(evals[::-1][:k], 0.0))
        out["gram_reference_s"] = time.perf_counter() - t0
        del a64

        # the Gram-kernel route on the same handle
        t0 = time.perf_counter()
        _, Sg, _ = el.gram_svd(A, k=k, use_pallas=True)
        sg = Sg.to_numpy().ravel()
        out["gram_svd_s"] = time.perf_counter() - t0
        out["gram_svd_s_max_rel_err"] = float(
            np.max(np.abs(sg - s_eig) / s_eig))
        out["gram_kernel_compiled"] = gram_svd_has_kernel(rows, cols)
    out["data_s"] = t_data
    out.update(_peak_bytes())
    out["compile"] = _compile_summary(server.engine)

    _check(out["s_max_rel_err"] <= S_RTOL, f"ocean S {out['s_max_rel_err']}")
    _check(out["subspace_sin"] <= SUBSPACE_TOL,
           f"ocean subspace {out['subspace_sin']}")
    _check(out["recon_rel_err"] <= RECON_RTOL,
           f"ocean reconstruction {out['recon_rel_err']}")
    _check(out["gram_svd_s_max_rel_err"] <= S_RTOL,
           f"gram_svd S {out['gram_svd_s_max_rel_err']}")
    # a kernel is compiled exactly when the platform compiles kernels
    _check(out["gram_kernel_compiled"] == (not kernels.interpret_mode()),
           "gram_svd program holds the compiled kernel iff on a TPU")
    return out


def gram_svd_has_kernel(rows: int, cols: int) -> bool:
    """Lower the device program of the engine's gram_svd (the Gram
    matrix) at the operand's shape and look for the compiled Pallas call
    in its text."""
    import jax
    import jax.numpy as jnp

    lowered = jax_backend._gram_matrix.lower(
        jax.ShapeDtypeStruct((rows, cols), jnp.float32), use_pallas=True)
    return "tpu_custom_call" in lowered.as_text()


def phase_speech_cg(server: AlchemistServer, rows: int, feats: int,
                    classes: int, rf_dim: int, iters: int, bandwidth: float,
                    lam: float, seed: int) -> dict:
    """CG on the random-feature expansion over TCP, default path and
    Pallas path. Each W is checked by float64 evaluations on the host of
    the normal-equations residual: on the features the engine solved
    with (fetched with ``random_features``), against the residual the
    routine reports, and on features rebuilt in float64, against the
    accuracy the budget must reach."""
    from repro.kernels.rf_map.ref import rf_weights

    x, y = speech_data(rows, feats, classes, seed)
    out: dict = {"rows": rows, "feats": feats, "classes": classes,
                 "rf_dim": rf_dim, "iters": iters,
                 "expanded_bytes": rows * rf_dim * 4}
    ws = []
    with AlchemistContext(address=server.address, client_name="speech") as ac:
        sk = ac.library("skylark")
        t0 = time.perf_counter()
        X = ac.send_matrix(x)
        Y = ac.send_matrix(y)
        Y.result()
        out["send_s"] = time.perf_counter() - t0
        for label, use_pallas in (("default", False), ("pallas", True)):
            t0 = time.perf_counter()
            W = sk.cg_solve(X, Y, lam=lam, rf_dim=rf_dim,
                            bandwidth=bandwidth, max_iters=iters, tol=0.0,
                            seed=seed, use_pallas=use_pallas)
            st = W.stats()
            ws.append(W.to_numpy())
            r = out[label] = {"s": time.perf_counter() - t0,
                              "iterations": st["iterations"],
                              "relative_residual": st["relative_residual"],
                              "kernels": st["kernels"]}
            out.update({f"{label}_{k}": v for k, v in _peak_bytes().items()})
            W.free()
            t0 = time.perf_counter()
            Z = sk.random_features(X, rf_dim=rf_dim, bandwidth=bandwidth,
                                   seed=seed, use_pallas=use_pallas)
            z = Z.to_numpy()
            Z.free()
            r["fetch_features_s"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            r["engine_features_residual"] = _cg_host_residuals(
                lambda lo, hi: z[lo:hi], rows, y, lam, ws[-1:])[0]
            r["engine_features_residual_s"] = time.perf_counter() - t0
            del z
    # the engine's random-feature weights, computed by the same function
    # the engine calls, then taken to float64 on the host
    rf_w, rf_b = (np.asarray(a, np.float64)
                  for a in rf_weights(feats, rf_dim, bandwidth, seed))
    scale = np.sqrt(2.0 / rf_dim)
    t0 = time.perf_counter()
    host = _cg_host_residuals(
        lambda lo, hi: scale * np.cos(x[lo:hi].astype(np.float64) @ rf_w
                                      + rf_b), rows, y, lam, ws)
    out["host_residual_s"] = time.perf_counter() - t0
    for label, res in zip(("default", "pallas"), host):
        out[label]["host_residual"] = res
    out["w_rel_diff"] = float(np.linalg.norm(ws[0] - ws[1])
                              / np.linalg.norm(ws[0]))
    out["compile"] = _compile_summary(server.engine)

    for label in ("default", "pallas"):
        r = out[label]
        _check(r["iterations"] == iters, f"{label} CG ran its budget")
        _check(r["relative_residual"] <= CG_PROGRESS,
               f"{label} CG made progress ({r['relative_residual']})")
        r["residual_gap"] = abs(r["engine_features_residual"]
                                - r["relative_residual"]) \
            / r["relative_residual"]
        _check(cg_residual_ok(r["relative_residual"],
                              r["engine_features_residual"]),
               f"{label} CG float64 residual on the engine's features "
               f"{r['engine_features_residual']} vs reported "
               f"{r['relative_residual']}")
        _check(r["host_residual"] <= CG_HOST_MAX,
               f"{label} CG float64 residual {r['host_residual']}")
    _check(out["pallas"]["kernels"]["rf_map"] == "pallas",
           "use_pallas expands with the rf_map kernel")
    return out


def cg_residual_ok(reported: float, host: float) -> bool:
    """The host's float64 residual agrees with the reported one."""
    return abs(host - reported) <= CG_RESID_RTOL * reported


def phase_two_tenants(server: AlchemistServer, heavy: tuple[int, int],
                      heavy_iters: int, shapes, seed: int) -> dict:
    """A light tenant's lazy chains fuse and are answered while a heavy
    tenant's truncated_svd holds a worker."""
    from repro.core import scheduler as scheduling

    engine = server.engine
    rng = np.random.default_rng(seed + 2)
    mats = [rng.standard_normal(s, dtype=np.float32) for s in shapes]
    out: dict = {"heavy": list(heavy), "shapes": [list(s) for s in shapes]}
    heavy_ac = AlchemistContext(address=server.address, client_name="heavy")
    light_ac = AlchemistContext(address=server.address, client_name="light")
    try:
        hel = heavy_ac.library("elemental")
        M = hel.random_matrix(rows=heavy[0], cols=heavy[1], seed=seed)
        M.result()
        t0 = time.perf_counter()
        Uh, _, _ = hel.truncated_svd(M, k=20, max_iters=heavy_iters)
        task = Uh.future.task
        deadline = time.monotonic() + 120
        while engine.scheduler.task(task).state == scheduling.QUEUED:
            _check(time.monotonic() < deadline, "heavy task started")
            time.sleep(0.005)

        lel = light_ac.library("elemental")
        As = [light_ac.send_matrix(m) for m in mats]
        for A in As:
            A.result()
        before = engine.task_log.stats()
        # one burst: claimed whole and fused (pausing only orders the
        # claim; the heavy task runs on)
        engine.scheduler.pause()
        try:
            outs = []
            for A in As:
                Q, R = lel.qr(A)
                outs.append(((Q.T @ Q) + R, lel.gram(A)))
        finally:
            engine.scheduler.resume()
        got = [(c.to_numpy(), g.to_numpy()) for c, g in outs]
        out["light_s"] = time.perf_counter() - t0
        out["heavy_running_when_light_answered"] = \
            engine.scheduler.task(task).state == scheduling.RUNNING
        after = engine.task_log.stats()
        out["fused_tasks"] = after["fused_tasks"] - before["fused_tasks"]
        out["fused_ops"] = after["fused_ops"] - before["fused_ops"]
        Uh.result()
        out["heavy_s"] = time.perf_counter() - t0
        out["heavy_lanczos_iters"] = Uh.stats()["lanczos_iters"]
    finally:
        light_ac.stop()
        heavy_ac.stop()

    errs = []
    for m, (chain, gram) in zip(mats, got):
        a64 = m.astype(np.float64)
        q, r = np.linalg.qr(a64)
        want = q.T @ q + r
        # QR is unique up to the sign of each row of R
        sign = np.sign(np.diag(chain - np.eye(len(r)))) * np.sign(np.diag(r))
        canon = np.eye(len(r)) + sign[:, None] * (chain - np.eye(len(r)))
        g64 = a64.T @ a64
        errs.append({
            "chain": float(np.max(np.abs(canon - want)) / np.max(np.abs(want))),
            "gram": float(np.max(np.abs(gram - g64)) / np.max(np.abs(g64)))})
    out["errors"] = errs
    out["compile"] = _compile_summary(engine)
    # the phase exists for this: the light tenant is answered while the
    # heavy routine still holds a worker
    _check(out["heavy_running_when_light_answered"]
           and out["heavy_s"] > out["light_s"],
           f"the light tenant was answered while the heavy one ran ({out})")
    # qr, transpose, multiply, add and gram per shape: all ran fused
    _check(out["fused_ops"] == 5 * len(shapes),
           f"the light tenant's burst fused ({out})")
    for e in errs:
        _check(e["chain"] <= CHAIN_RTOL and e["gram"] <= CHAIN_RTOL,
               f"light tenant vs float64 {e}")
    return out


def phase_four_chips(rows: int, cols: int, k: int, seed: int,
                     chips: int = 4) -> dict:
    """The engine mesh over ``chips`` devices against a one-device engine
    in the same process: a row-block operand (streamed in chunks onto
    the mesh), truncated_svd, a fused bucketed chain and gram. The
    one-device engine holds the whole
    operand, so it runs unbucketed (bucketing pads the 8,096 columns to
    8,192: a second copy that one chip cannot hold next to the first);
    zero padding does not change results."""
    import jax

    a = ocean_field(rows, cols, k, seed)
    out: dict = {"rows": rows, "cols": cols, "chips": chips}
    results = {}
    for label, mesh, bucketing in (("mesh", make_engine_mesh(chips), True),
                                   ("one", make_engine_mesh(1), False)):
        # result cache off: the standalone gram must compute, not be
        # answered from the chain's memoized gram
        engine = AlchemistEngine(mesh, bucketing=bucketing,
                                 cache_entries=0)
        engine.load_library("elemental", elemental)
        ac = AlchemistContext(engine=engine, client_name=label)
        try:
            el = ac.library("elemental")
            t0 = time.perf_counter()
            # one chip gets the whole operand device-resident: the chunked
            # upload would stage a second copy while it assembles (17 GB
            # at the four-chip size, over one chip's 16 GB)
            A = ac.send_matrix(a if label == "mesh" else jax.device_put(a),
                               dedup=False)
            arr = engine.get(A.handle, session=ac.session)
            placed = {"layout": A.layout,
                      "devices": len(arr.sharding.device_set),
                      "replicated": bool(arr.sharding.is_fully_replicated)}
            send_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            _, S, V = el.truncated_svd(A, k=k)
            s, v = S.to_numpy().ravel(), V.to_numpy()
            svd_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            before = engine.task_log.stats()["fused_tasks"]
            engine.scheduler.pause()
            try:
                G = el.gram(A)
                C = (G @ G) + G
            finally:
                engine.scheduler.resume()
            c = C.to_numpy()
            fused = engine.task_log.stats()["fused_tasks"] - before
            chain_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            g = el.gram(A).to_numpy()
            gram_s = time.perf_counter() - t0
            results[label] = {"s": s, "v": v, "chain": c, "gram": g}
            out[label] = {
                "placement": placed, "send_s": send_s, "svd_s": svd_s,
                "chain_s": chain_s, "gram_s": gram_s, "fused_tasks": fused,
                "compile": _compile_summary(engine),
                "replicated_stores": engine.placement_stats(),
                **_peak_bytes()}
        finally:
            ac.stop()
            engine.shutdown()
        del arr
    m, o = results["mesh"], results["one"]
    out["s_max_rel_err"] = float(np.max(np.abs(m["s"] - o["s"]) / o["s"]))
    out["subspace_sin"] = _subspace_sin(m["v"], o["v"])
    out["chain_rel_err"] = float(np.max(np.abs(m["chain"] - o["chain"]))
                                 / np.max(np.abs(o["chain"])))
    out["gram_rel_err"] = float(np.max(np.abs(m["gram"] - o["gram"]))
                                / np.max(np.abs(o["gram"])))
    out["devices"] = len(jax.devices())

    mp = out["mesh"]["placement"]
    _check(mp["layout"] == "rowblock" and mp["devices"] == chips
           and not mp["replicated"],
           f"operand row-blocked over {chips} devices ({mp})")
    _check(out["mesh"]["fused_tasks"] >= 1 and out["one"]["fused_tasks"] >= 1,
           "the chain fused on both engines")
    _check(out["s_max_rel_err"] <= MESH_RTOL,
           f"mesh S vs one device {out['s_max_rel_err']}")
    _check(out["subspace_sin"] <= SUBSPACE_TOL,
           f"mesh subspace vs one device {out['subspace_sin']}")
    _check(out["chain_rel_err"] <= MESH_RTOL,
           f"mesh chain vs one device {out['chain_rel_err']}")
    _check(out["gram_rel_err"] <= MESH_RTOL,
           f"mesh gram vs one device {out['gram_rel_err']}")
    return out


def _compile_summary(engine: AlchemistEngine) -> dict:
    cs = engine.compile_stats()
    return {k: cs[k] for k in ("compiles", "hits", "request_compiles",
                                "request_compile_s", "executable_index")}


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Drive the served path once on a TPU and check it")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh path and the "
                    "one-device engine it is compared with")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX found {device}); nothing was run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    _say("device", **device,
         compile_cache=compilecache.enable_persistent_cache())

    t_all = time.perf_counter()
    if args.chips == 4:
        _say("four_chips", **phase_four_chips(seed=args.seed, **FOUR))
    else:
        engine = AlchemistEngine(make_engine_mesh(1))
        server = start_server(engine)
        try:
            _say("ocean_svd", **phase_ocean_svd(server, seed=args.seed,
                                                **OCEAN))
            _say("speech_cg", **phase_speech_cg(server, seed=args.seed,
                                                **SPEECH))
            _say("two_tenants", **phase_two_tenants(
                server, heavy=TENANTS["heavy"],
                heavy_iters=TENANTS["heavy_iters"],
                shapes=TENANTS["shapes"], seed=args.seed))
        finally:
            server.stop(shutdown_engine=True)
    _say("total", seconds=time.perf_counter() - t_all)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    # a hung phase dumps every thread's stack and exits non-zero, inside
    # the run's 1,200 s limit
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    sys.exit(main())
