"""Plain references for what the served path answers, written from the
mathematics and importing nothing of the program.

Each reference runs at a stated matmul precision. ``highest`` is what the
configurations state (float32 products at full precision) and is the
reference. ``high`` is the control: the same reference with every matrix
product in bfloat16 three-pass form (each float32 operand split into a
bfloat16 high and low part; hi*hi + hi*lo + lo*hi, summed in float32),
which is what a TPU's ``Precision.HIGH`` computes. The three passes are
written out, so that the control computes the same on any platform.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
HIGH = "high"
_NT = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


@jax.jit
def _split(a):
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def prepare(a, precision: str) -> tuple:
    """A matrix as the products of ``precision`` read it: itself, or its
    bfloat16 high and low parts, split in one program (no float32
    temporary) and once, so that a loop over a large operand holds one
    split copy."""
    if precision == HIGHEST:
        return (a,)
    if precision != HIGH:
        raise ValueError(f"unknown precision {precision!r}")
    return _split(a)


def _dot(a: tuple, b, dims):
    """The product of a prepared matrix ``a`` with ``b`` at ``a``'s
    precision."""
    if len(a) == 1:
        return jax.lax.dot_general(a[0], b, dims,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
    (ah, al), (bh, bl) = a, prepare(b, HIGH)

    def one(x, y):
        return jax.lax.dot_general(x, y, dims,
                                   preferred_element_type=jnp.float32)
    return one(ah, bh) + (one(ah, bl) + one(al, bh))


def mm(a: tuple, b):
    return _dot(a, b, _NT)


def mm_t(a: tuple, b):
    """a.T @ b without forming a.T."""
    return _dot(a, b, _TN)


# ---------------------------------------------------------------------------
# truncated SVD: block subspace iteration on A^T A, Rayleigh-Ritz in float64
# ---------------------------------------------------------------------------
@jax.jit
def _gram_block(a: tuple, q):
    return mm_t(a, mm(a, q))


@jax.jit
def _times(a: tuple, q):
    return mm(a, q)


def truncated_svd(a: tuple, k: int, seed: int, extra: int = 12,
                  iters: int = 12) -> dict:
    """Top-k singular triplets of the prepared device matrix ``a`` (n, d):
    ``iters`` steps of subspace iteration with ``k + extra`` vectors,
    orthonormalized on the host in float64, then Rayleigh-Ritz on A Q in
    float64. With the ocean spectrum (a gap of >= 3x in singular values
    past the k-th) each step shrinks the error by about 10x."""
    d = a[0].shape[1]
    p = min(d, k + extra)
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((d, p)))
    for _ in range(iters):
        z = _gram_block(a, jnp.asarray(q, jnp.float32))
        q, _ = np.linalg.qr(np.asarray(z, np.float64))
    q32 = q.astype(np.float32)
    b = np.asarray(_times(a, jnp.asarray(q32)), np.float64)
    # the basis the device multiplied is q32, orthonormal only to float32;
    # q32 = qf r, so A qf = b r^-1
    qf, r = np.linalg.qr(q32.astype(np.float64))
    bf = np.linalg.solve(r.T, b.T).T
    evals, evecs = np.linalg.eigh(bf.T @ bf)
    order = np.argsort(evals)[::-1][:k]
    sigma = np.sqrt(np.maximum(evals[order], 0.0))
    return {"S": sigma, "V": qf @ evecs[:, order],
            "U": (bf @ evecs[:, order]) / sigma}


# ---------------------------------------------------------------------------
# CG on random features: the features, the normal-equations residual, and
# the solve itself (the solve serves the control)
# ---------------------------------------------------------------------------
def rf_weights(feats: int, rf_dim: int, bandwidth: float, seed: int):
    """Rahimi-Recht RBF random features from ``seed``, drawn as the
    deployment draws them: W ~ N(0, 1/bandwidth^2) of (feats, rf_dim) and
    b ~ U[0, 2 pi) of rf_dim, from jax's threefry key ``seed`` split in
    two."""
    kw, kb = jax.random.split(jax.random.PRNGKey(seed))
    w = jax.random.normal(kw, (feats, rf_dim), jnp.float32) / bandwidth
    b = jax.random.uniform(kb, (rf_dim,), jnp.float32, 0.0, 2.0 * jnp.pi)
    return w, b


def _features(x, w, b, precision):
    return jnp.sqrt(2.0 / w.shape[1]) * jnp.cos(
        mm(prepare(x, precision), w) + b)


def _blocks(n: int, block: int):
    block = min(block, n)
    return block, n // block, n - (n // block) * block


@functools.partial(jax.jit, static_argnames="block")
def _residual(x, y, w, b, sol, lam_n, block):
    """Z^T Y and Z^T (Y - Z sol), each Kahan-summed over blocks of rows, at
    full precision; the residual's norm per class over Z^T Y's."""
    blk, full, rest = _blocks(x.shape[0], block)

    def part(xb, yb):
        z = (_features(xb, w, b, HIGHEST),)
        return mm_t(z, yb), mm_t(z, yb - mm(z, sol))

    def add(carry, new):
        out = []
        for (s, c), v in zip(carry, new):
            t = s + (v - c)
            out.append((t, (t - s) - (v - c)))
        return tuple(out)

    zero = jnp.zeros_like(sol)

    def body(i, carry):
        lo = i * blk
        return add(carry, part(jax.lax.dynamic_slice_in_dim(x, lo, blk),
                               jax.lax.dynamic_slice_in_dim(y, lo, blk)))

    carry = jax.lax.fori_loop(0, full, body, ((zero, zero), (zero, zero)))
    if rest:
        carry = add(carry, part(x[full * blk:], y[full * blk:]))
    (rhs, _), (res, _) = carry
    return jnp.max(jnp.linalg.norm(res - lam_n * sol, axis=0)
                   / jnp.linalg.norm(rhs, axis=0))


def cg_residual(x: np.ndarray, y: np.ndarray, sol: np.ndarray, lam: float,
                rf_dim: int, bandwidth: float, rf_seed: int,
                block: int = 1024) -> float:
    """max over classes of ||Z^T Y - (Z^T Z + n lam I) W|| / ||Z^T Y||, with
    Z = sqrt(2/D) cos(X W_rf + b) rebuilt here block by block at full
    float32 precision."""
    w, b = rf_weights(x.shape[1], rf_dim, bandwidth, rf_seed)
    lam_n = jnp.float32(x.shape[0] * lam)
    return float(_residual(jnp.asarray(x), jnp.asarray(y), w, b,
                           jnp.asarray(sol, jnp.float32), lam_n, block))


@functools.partial(jax.jit, static_argnames=("precision", "block"))
def _expand(x, w, b, precision, block):
    """Z block by block, returned prepared for ``precision``."""
    blk, full, rest = _blocks(x.shape[0], block)
    z = jnp.zeros((x.shape[0], w.shape[1]), jnp.float32)

    def body(i, z):
        xb = jax.lax.dynamic_slice_in_dim(x, i * blk, blk)
        return jax.lax.dynamic_update_slice_in_dim(
            z, _features(xb, w, b, precision), i * blk, 0)

    z = jax.lax.fori_loop(0, full, body, z)
    if rest:
        z = z.at[full * blk:].set(_features(x[full * blk:], w, b,
                                            precision))
    return prepare(z, precision)


@functools.partial(jax.jit, static_argnames="iters")
def _cg(z: tuple, y, lam_n, iters):
    b = mm_t(z, y)
    rs = jnp.sum(b * b, axis=0)

    def div(a, b):                 # a column that has converged stays put
        return jnp.where(b > 0, a / jnp.where(b > 0, b, 1.0), 0.0)

    def body(_, st):
        w, r, p, rs = st
        ap = mm_t(z, mm(z, p)) + lam_n * p
        alpha = div(rs, jnp.sum(p * ap, axis=0))
        w = w + alpha * p
        r = r - alpha * ap
        rs_new = jnp.sum(r * r, axis=0)
        return w, r, r + div(rs_new, rs) * p, rs_new

    return jax.lax.fori_loop(0, iters, body,
                             (jnp.zeros_like(b), b, b, rs))[0]


def cg_solve(x: np.ndarray, y: np.ndarray, lam: float, rf_dim: int,
             bandwidth: float, rf_seed: int, iters: int,
             precision: str = HIGHEST, block: int = 1024) -> np.ndarray:
    """CG on (Z^T Z + n lam I) W = Z^T Y for a fixed number of steps."""
    w, b = rf_weights(x.shape[1], rf_dim, bandwidth, rf_seed)
    z = _expand(jnp.asarray(x), w, b, precision, block)
    lam_n = jnp.float32(x.shape[0] * lam)
    return np.asarray(_cg(z, jnp.asarray(y), lam_n, iters))
