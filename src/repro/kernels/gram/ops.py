"""Public wrapper for the Gram kernel: padding, dtype handling, and the
jnp fallback used when the Pallas path is not requested. The kernel runs
compiled on a TPU and interpreted elsewhere (``kernels.interpret_mode``)."""
from __future__ import annotations

import jax.numpy as jnp

from repro import kernels
from repro.kernels.gram.gram import gram_pallas
from repro.kernels.gram.ref import gram_ref


def _pad_to(x, m, axis):
    rem = x.shape[axis] % m
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, m - rem)
    return jnp.pad(x, pad)


def gram(a: jnp.ndarray, *, use_pallas: bool = False, bm: int = 512,
         bn: int = 256) -> jnp.ndarray:
    """G = A^T A (fp32 accumulation)."""
    if not use_pallas:
        return gram_ref(a)
    d = a.shape[1]
    ap = _pad_to(_pad_to(a, bm, 0), bn, 1)
    g = gram_pallas(ap, bm=bm, bn=bn, interpret=kernels.interpret_mode())
    return g[:d, :d]
