"""Public wrapper for the fused normal-equations matvec."""
from __future__ import annotations

import jax.numpy as jnp

from repro import kernels
from repro.kernels.normal_matvec.normal_matvec import normal_matvec_pallas
from repro.kernels.normal_matvec.ref import normal_matvec_ref

# one row block must fit VMEM: bm * d * 4B; cap d so bm=128 stays ~4 MiB
_MAX_FUSED_D = 8192


def uses_kernel(d: int, use_pallas: bool) -> bool:
    """Whether :func:`normal_matvec` runs the fused kernel for width
    ``d``: only when requested and a row block fits VMEM. Callers report
    this so a requested kernel that gave way to the two-pass reference
    is never silent."""
    return use_pallas and d <= _MAX_FUSED_D


def normal_matvec(x: jnp.ndarray, w: jnp.ndarray, *,
                  use_pallas: bool = False, bm: int = 128) -> jnp.ndarray:
    """w -> X^T (X w) with fp32 accumulation."""
    n, d = x.shape
    if not uses_kernel(d, use_pallas):
        return normal_matvec_ref(x, w)
    rem = n % bm
    if rem:
        pad = bm - rem
        x = jnp.pad(x, ((0, pad), (0, 0)))      # zero rows: no-op for X^T X
    return normal_matvec_pallas(x, w, bm=bm,
                                interpret=kernels.interpret_mode())
