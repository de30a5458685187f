"""Public wrapper for the random-feature map (padding + jnp fallback)."""
from __future__ import annotations

import jax.numpy as jnp

from repro import kernels
from repro.kernels.rf_map.ref import rf_map_ref, rf_weights
from repro.kernels.rf_map.rf_map import rf_map_pallas


def rf_map(x: jnp.ndarray, rf_dim: int, *, bandwidth: float = 1.0,
           seed: int = 0, use_pallas: bool = False) -> jnp.ndarray:
    """Z = sqrt(2/D) cos(X W + b) with internally generated (W, b)."""
    w, b = rf_weights(x.shape[1], rf_dim, bandwidth, seed)
    return rf_map_apply(x, w, b, use_pallas=use_pallas)


def rf_map_apply(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, *,
                 use_pallas: bool = False) -> jnp.ndarray:
    if not use_pallas:
        return rf_map_ref(x, w, b)
    bk = 128

    def pad(a, axis):
        rem = a.shape[axis] % bk
        if rem == 0:
            return a
        padspec = [(0, 0)] * a.ndim
        padspec[axis] = (0, bk - rem)
        return jnp.pad(a, padspec)

    # only the contraction dim needs zeros; the kernel writes (n, D)
    # exactly, so nothing is sliced (or rescaled) afterwards
    return rf_map_pallas(pad(x, 1), pad(w, 0), b, bk=bk,
                         interpret=kernels.interpret_mode())
