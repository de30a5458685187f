"""Fused normal-equations matvec kernel: w -> X^T (X w).

This is THE inner loop of the paper's CG workload (§4.1): every iteration
streams the (n x d) data matrix. Done as two separate matmuls, X is read
from HBM twice per iteration (once for t = Xw, once for X^T t). This kernel
keeps each (bm x d) row block resident in VMEM and performs BOTH products
per block before moving on — halving CG's dominant HBM traffic:

    per row block i:  t_i = X_i @ w          (bm, c)   MXU
                      acc += X_i^T @ t_i     (d, c)    MXU, fp32 in VMEM

Constraint: a full row block must fit VMEM — bm * d * 4 bytes (e.g.
bm=128, d<=8192 ~ 4 MiB), which covers the paper's raw-feature regime
(d=440) and the Gram-side of the expanded problems. ops.py falls back to
the two-pass reference when d is too large.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _nm_kernel(x_ref, w_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    t = jnp.dot(x, w_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    o_ref[...] += jnp.dot(x.T, t, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def normal_matvec_pallas(x: jnp.ndarray, w: jnp.ndarray, *, bm: int = 128,
                         interpret: bool) -> jnp.ndarray:
    """x: (n, d), w: (d, c); n % bm == 0 (ops pads). Returns (d, c) fp32."""
    n, d = x.shape
    c = w.shape[1]
    assert n % bm == 0, (n, bm)
    return pl.pallas_call(
        _nm_kernel,
        grid=(n // bm,),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((d, c), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((d, c), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((d, c), jnp.float32),
        interpret=interpret,
    )(x, w)
