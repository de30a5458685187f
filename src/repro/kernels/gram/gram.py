"""Blocked Gram matrix kernel: G = A^T A, A: (n, d).

TPU adaptation of the paper's Gram hot spot (every Lanczos/CG step is built
on A^T(A v); the explicit Gram path is used by gram_svd and benchmarks):
rows are streamed HBM->VMEM in (bm, bn) tiles; each (i, j) output tile of
size (bn, bn) accumulates partial A_ki^T A_kj products on the MXU in fp32.
The k (row-chunk) grid axis is innermost so each output tile stays resident
in VMEM across the whole reduction (revisiting semantics).

VMEM budget per step: 2 * bm*bn + bn*bn fp32 tiles; defaults
(bm=512, bn=256) ~ 1.3 MiB, far under the ~128 MiB/core VMEM of v5e, and
all dims are multiples of the 128-lane MXU tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gram_kernel(a_i_ref, a_j_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        a_i_ref[...].astype(jnp.float32).T,
        a_j_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def gram_pallas(a: jnp.ndarray, *, bm: int = 512, bn: int = 256,
                interpret: bool) -> jnp.ndarray:
    """G = A^T A. Requires n % bm == 0 and d % bn == 0 (ops.py pads)."""
    n, d = a.shape
    assert n % bm == 0 and d % bn == 0, (a.shape, bm, bn)
    grid = (d // bn, d // bn, n // bm)
    return pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (k, i)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bn, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d, d), jnp.float32),
        interpret=interpret,
    )(a, a)
