"""Sliding-window flash attention kernel (online softmax over windowed KV).

Used by recurrentgemma's local-attention layers and the qwen3-4b-sw
long-context variant. For window w and query block bq, a query block at
block-row i only touches kv blocks j in [i - ceil(w/bk), i] — the kv grid
axis has constant extent nkv = w//bk + 1 regardless of S, so prefill compute
is O(S * w) rather than O(S^2).

Online-softmax state (m, l, acc) lives in VMEM scratch and persists across
the kv axis (innermost grid dim); out-of-range kv blocks are skipped with
pl.when, and the final kv step normalizes and writes the output tile once.
VMEM per step: q/k/v tiles + acc (bq x d fp32) — defaults ~0.5 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e38


def _swa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                bq: int, bk: int, nkv: int, window: int, scale: float):
    i = pl.program_id(1)          # query block row
    jj = pl.program_id(2)         # kv step within the window span

    @pl.when(jj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # highest kv block a query in this q-block can see, in bk units
    hi = i * (bq // bk) + (bq // bk) - 1
    j = hi - (nkv - 1) + jj       # global kv block column (may be < 0)

    @pl.when(j >= 0)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = (kpos <= qpos) & (kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p, v_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(jj == nkv - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "bq", "bk", "interpret"))
def swa_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
               window: int, bq: int = 128, bk: int = 128,
               interpret: bool) -> jnp.ndarray:
    """q,k,v: (BH, S, D) flattened over batch*heads. S % bq == 0 == S % bk."""
    bh, s, d = q.shape
    assert s % bq == 0 and s % bk == 0 and bq % bk == 0, (s, bq, bk)
    # kv blocks each q block can see: the window tail plus the q block itself
    nkv = -(-(window - 1) // bk) + bq // bk
    scale = d ** -0.5
    kernel = functools.partial(_swa_kernel, bq=bq, bk=bk, nkv=nkv,
                               window=window, scale=scale)

    def kv_index(b, i, jj):
        hi = i * (bq // bk) + (bq // bk) - 1
        j = hi - (nkv - 1) + jj
        return (b, jnp.maximum(j, 0))         # clamped; masked in-kernel

    return pl.pallas_call(
        kernel,
        grid=(bh, s // bq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, jj: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, jj: (*kv_index(b, i, jj), 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, jj: (*kv_index(b, i, jj), 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, jj: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
