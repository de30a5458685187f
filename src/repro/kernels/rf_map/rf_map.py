"""Fused random-feature map kernel: Z = sqrt(2/D) cos(X W + b).

The paper expands the TIMIT feature matrix engine-side (n x 440 -> n x 60k).
Unfused, that is a matmul writing an (n, D) fp32 intermediate to HBM, then an
elementwise pass reading+writing it again — 3 extra HBM touches of the
largest tensor in the workload. This kernel keeps each (bm, bn) output tile
in VMEM across the d-reduction (innermost grid axis) and applies
cos(.+b)*scale in-register before the single HBM write.

VMEM per step: bm*bk + bk*bn + bm*bn fp32 (defaults ~ 0.9 MiB); all block
dims multiples of 128 for the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rf_kernel(x_ref, w_ref, b_ref, o_ref, *, nk: int, scale: float):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _finish():
        o_ref[...] = scale * jnp.cos(o_ref[...] + b_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def rf_map_pallas(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, *,
                  bm: int = 256, bn: int = 256, bk: int = 128,
                  interpret: bool) -> jnp.ndarray:
    """x: (n, d), w: (d, D), b: (D,); d % bk == 0 (ops zero-pads the
    contraction). Returns exactly (n, D): edge blocks along n and D are
    partial (reads past the edge only feed output cells that are never
    written), so the largest tensor of the workload is written once, at
    its own size, with no pad-and-slice copy."""
    n, d = x.shape
    d2, dd = w.shape
    assert d == d2 and d % bk == 0, (x.shape, w.shape, bk)
    nk = d // bk
    scale = float((2.0 / dd) ** 0.5)
    kernel = functools.partial(_rf_kernel, nk=nk, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, bm), pl.cdiv(dd, bn), nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, dd), jnp.float32),
        interpret=interpret,
    )(x, w, b.reshape(1, -1))
