"""Public wrapper for sliding-window attention: (B,H,S,D) layout handling,
GQA head-group broadcast, jnp fallback."""
from __future__ import annotations

import jax.numpy as jnp

from repro import kernels
from repro.kernels.swa.ref import swa_ref
from repro.kernels.swa.swa import swa_pallas


def swa_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  window: int, use_pallas: bool = False, bq: int = 128,
                  bk: int = 128) -> jnp.ndarray:
    """q: (B, H, S, D); k, v: (B, K, S, D) with H % K == 0 (GQA broadcast)."""
    b, h, s, d = q.shape
    kh = k.shape[1]
    if kh != h:
        rep = h // kh
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if not use_pallas:
        return swa_ref(q, k, v, window)
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, s, d)
    vf = v.reshape(b * h, s, d)
    out = swa_pallas(qf, kf, vf, window=window, bq=min(bq, s),
                     bk=min(bk, s), interpret=kernels.interpret_mode())
    return out.reshape(b, h, s, d)
