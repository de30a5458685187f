"""Gated linear recurrence kernel: h_t = a_t * h_{t-1} + b_t  (RG-LRU core).

TPU adaptation of the recurrence hot spot (mamba/griffin-style): the time
axis is processed in sequential chunks (grid axis, revisiting semantics);
within a chunk the (bt, bw) tile of a and b is resident in VMEM and the
per-channel carry h lives in VMEM scratch across the whole time sweep —
the recurrence never round-trips HBM between steps, unlike a lax.scan of
small element-wise ops which writes h_t out every step. Channels are
independent, so the (batch x width-block) grid axes are embarrassingly
parallel; time is the innermost (sequential) axis.

VMEM per step: 2 * bt*bw + bw fp32 (defaults bt=128, bw=512 ~ 0.5 MiB).
The in-chunk loop is a fori_loop of VPU element-wise ops over rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lru_kernel(a_ref, b_ref, h0_ref, o_ref, h_ref, *, bt: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)          # (bt, bw)
    b = b_ref[0].astype(jnp.float32)

    def step(i, h):
        h = a[i] * h + b[i]
        o_ref[0, i, :] = h
        return h

    h_ref[...] = jax.lax.fori_loop(0, bt, step, h_ref[...])


@functools.partial(jax.jit, static_argnames=("bt", "bw", "interpret"))
def lru_scan_pallas(a: jnp.ndarray, b: jnp.ndarray, h0: jnp.ndarray, *,
                    bt: int = 128, bw: int = 512,
                    interpret: bool) -> jnp.ndarray:
    """a, b: (B, S, W); h0: (B, W). Requires S % bt == 0 and W % bw == 0.
    Returns all states (B, S, W) fp32."""
    bb, s, w = a.shape
    assert s % bt == 0 and w % bw == 0, (a.shape, bt, bw)
    kernel = functools.partial(_lru_kernel, bt=bt)
    return pl.pallas_call(
        kernel,
        grid=(bb, w // bw, s // bt),
        in_specs=[
            pl.BlockSpec((1, bt, bw), lambda i, j, t: (i, t, j)),
            pl.BlockSpec((1, bt, bw), lambda i, j, t: (i, t, j)),
            pl.BlockSpec((1, bw), lambda i, j, t: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, bt, bw), lambda i, j, t: (i, t, j)),
        out_shape=jax.ShapeDtypeStruct((bb, s, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bw,), jnp.float32)],
        interpret=interpret,
    )(a, b, h0)
