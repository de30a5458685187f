"""Public wrapper for the gated linear recurrence (padding + fallback).

The Pallas path is forward-only (inference/prefill of recurrent blocks);
training keeps the associative-scan reference, whose VJP JAX derives.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro import kernels
from repro.kernels.lru_scan.lru_scan import lru_scan_pallas
from repro.kernels.lru_scan.ref import lru_scan_ref


def lru_scan(a: jnp.ndarray, b: jnp.ndarray, h0: jnp.ndarray, *,
             use_pallas: bool = False, bt: int = 128, bw: int = 512
             ) -> jnp.ndarray:
    """h_t = a_t h_{t-1} + b_t over (B, S, W); returns (B, S, W) fp32."""
    bb, s, w = a.shape
    if not use_pallas:
        return lru_scan_ref(a, b, h0)
    btt = min(bt, s)
    while s % btt:
        btt -= 1
    bww = min(bw, w)
    while w % bww:
        bww -= 1
    return lru_scan_pallas(a, b, h0, bt=btt, bw=bww,
                           interpret=kernels.interpret_mode())
