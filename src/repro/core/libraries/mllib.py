"""The "pure Spark" baseline — MLlib-style implementations restricted to the
client's row-partitioned layout.

The paper's comparison baseline (its Tables 2/5 'Spark' rows) runs the same
algorithms inside Spark: every CG iteration / Lanczos matvec is a
treeAggregate over row partitions, paying scheduler + task-launch overhead
per BSP round. We implement the identical math over RowMatrix partitions
and report its measured wall time and BSP round count.

Unlike the ALI modules (elemental/skylark) the *direct entry points*
(``spark_cg_solve``/``spark_truncated_svd``) never touch the engine or a
session: they run entirely in the client's row-partitioned world, which
is precisely the point of the comparison — no bridge, no sessions, no
transfer, just per-round BSP overhead.

The module additionally exports a ``ROUTINES`` dict so the baseline is a
first-class, *describable* ALI library like elemental/skylark: the
declarations below catalog an engine-hosted wrapper whose per-backend
implementation (shared by the jax and reference backends — the baseline
is row-partitioned host math by construction, accelerating it would
unmake the comparison; see ``core/backends/reference.py``) rebuilds the
row-partitioned RowMatrix from the resident array and runs the identical
baseline math, so catalogs, typed validation, and benchmark harnesses
can drive both sides of the paper's comparison through one façade API.
The measured comparison itself should keep using the direct entry points
(they are the no-bridge side by construction).
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.libraries.spec import routine, spec_only
from repro.frontend.rowmatrix import RowMatrix


def spark_cg_solve(x: RowMatrix, y: RowMatrix, lam: float = 1e-5,
                   max_iters: int = 200, tol: float = 1e-8):
    """CG on the normal equations, one BSP round per iteration.

    Returns (W, stats) where stats carries measured wall time and BSP
    round count.
    """
    n = x.shape[0]
    b = x.t_times(y)                             # X^T Y  (one BSP round)
    b_norm = np.linalg.norm(b, axis=0)
    w = np.zeros_like(b)
    r = b.copy()
    p = r.copy()
    rs = np.sum(r * r, axis=0)

    rounds = 1
    t0 = time.perf_counter()
    iters = 0
    rel = float(np.max(np.sqrt(rs) / np.maximum(b_norm, 1e-30)))
    while iters < max_iters and rel > tol:
        ap = x.gram_times(p) + n * lam * p       # one BSP round
        rounds += 1
        alpha = rs / np.sum(p * ap, axis=0)
        w = w + alpha * p
        r = r - alpha * ap
        rs_new = np.sum(r * r, axis=0)
        p = r + (rs_new / rs) * p
        rs = rs_new
        rel = float(np.max(np.sqrt(rs) / np.maximum(b_norm, 1e-30)))
        iters += 1
    measured = time.perf_counter() - t0

    stats = {
        "iterations": iters,
        "bsp_rounds": rounds,
        "relative_residual": rel,
        "measured_seconds": measured,
    }
    return w, stats


def spark_truncated_svd(x: RowMatrix, k: int, oversample: int = 32,
                        seed: int = 0):
    """MLlib-style truncated SVD: Lanczos on the Gram matrix where each
    matvec is a distributed treeAggregate over row partitions (MLlib's
    computeSVD does exactly this via ARPACK)."""
    d = x.shape[1]
    m = min(d, k + oversample)
    rng = np.random.RandomState(seed)
    q = rng.randn(d)
    q /= np.linalg.norm(q)
    Q = np.zeros((d, m))
    alpha = np.zeros(m)
    beta = np.zeros(m)
    q_prev = np.zeros(d)
    b_prev = 0.0
    rounds = 0
    t0 = time.perf_counter()
    for j in range(m):
        Q[:, j] = q
        w = x.gram_times(q[:, None])[:, 0]       # one BSP round
        rounds += 1
        a = float(q @ w)
        alpha[j] = a
        w = w - a * q - b_prev * q_prev
        for _ in range(2):
            w = w - Q[:, : j + 1] @ (Q[:, : j + 1].T @ w)
        b = float(np.linalg.norm(w))
        beta[j] = b
        if b < 1e-12:
            m = j + 1
            Q, alpha, beta = Q[:, :m], alpha[:m], beta[:m]
            break
        q_prev, b_prev, q = q, b, w / b
    T = np.diag(alpha) + np.diag(beta[: m - 1], 1) + np.diag(beta[: m - 1], -1)
    evals, evecs = np.linalg.eigh(T)
    order = np.argsort(evals)[::-1][:k]
    sigma = np.sqrt(np.maximum(evals[order], 0.0))
    V = Q @ evecs[:, order]
    measured = time.perf_counter() - t0

    stats = {
        "bsp_rounds": rounds,
        "measured_seconds": measured,
        "lanczos_iters": int(m),
    }
    return sigma, V, stats


# ---- ALI-hosted declarations (the describable catalog surface) ------------
@routine(outputs=("W",))
def _ali_cg_solve(engine, X, Y, lam: float = 1e-5, max_iters: int = 200,
                  tol: float = 1e-8, num_partitions: int = 8):
    """The pure-Spark CG baseline, driven through the ALI calling
    convention: handles resolve to the resident arrays, the identical
    row-partitioned math runs (one simulated BSP round per iteration),
    and the solution comes back as an engine handle plus the baseline's
    stats dict."""
    raise spec_only("mllib", "cg_solve")


@routine(outputs=("S", "V"))
def _ali_truncated_svd(engine, A, k: int, oversample: int = 32,
                       seed: int = 0, num_partitions: int = 8):
    """The MLlib-style Lanczos SVD baseline through the ALI calling
    convention (see :func:`_ali_cg_solve`): returns the top-k singular
    values and right singular vectors as engine handles."""
    raise spec_only("mllib", "truncated_svd")


ROUTINES = {
    "cg_solve": _ali_cg_solve,
    "truncated_svd": _ali_truncated_svd,
}
