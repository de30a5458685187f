"""One-pass Gram matvec for one vector: v -> X^T (X v), reading X once.

The Lanczos loop of the truncated SVD applies X^T X to one vector per
step. XLA runs that as two reductions, and each streams all of X from
HBM. This kernel keeps each block of X in VMEM and uses it twice, for
t = X_i v and for acc += X_i^T t, so X is read once per matvec.

With one vector the work is two multiply-adds per element, so both
products run on the VPU in float32 (exact, as ``"highest"`` asks); the
MXU kernel beside this one (``normal_matvec``) is for many right-hand
sides. The kernel reads its block in strips, so no temporary of the
block's size is held beside it, and keeps its accumulator in a VMEM
scratch that is written out once, at the last step. X is never copied:
when n is not a multiple of the block, the columns of the last block
that lie past n are masked, never padded.

XLA lays a float32 matrix out column-major when its width is not a
multiple of 128 and its height is (the ocean field, 193,536 x 8,096).
The kernel takes X in that layout: ``X.T`` is then a bitcast, and the
kernel reads ``A = X^T`` row-major in blocks of ``(d, block)`` columns
of A, so t = v^T A_i and acc += A_i t^T.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: a float32 vreg: sublanes by lanes
_SUB, _LANE = 8, 128
#: 8-row strips of A per loop step
_STRIPS = 8


def vmem_bytes(d: int, block: int) -> int:
    """VMEM the kernel asks for at width ``d``: two buffered blocks of
    ``block`` rows of X, and six ``(d, 128)`` buffers besides (v and the
    output, each double-buffered, v's broadcast and the accumulator),
    every row of them counted at its lane-padded width."""
    row = -(-d // _LANE) * _LANE * 4
    return (2 * block + 6 * _LANE) * row


def _kernel(a_ref, v_ref, o_ref, acc_ref, vb_ref, *, last_cols):
    """One ``(d, bn)`` block of A = X^T. ``v_ref`` is v as a column
    ``(d, 1)``; ``acc_ref`` holds A t^T as lane-partial sums ``(d, 128)``
    and ``vb_ref`` v broadcast along the lanes."""
    d, bn = a_ref.shape
    i, last = pl.program_id(0), pl.num_programs(0) - 1
    full = d // _SUB * _SUB

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        vb_ref[...] = jnp.broadcast_to(v_ref[...], vb_ref.shape)

    def lanes(p):                 # (r, bn) -> (r, 128): lane tiles summed
        out = p[:, :_LANE]
        for c in range(_LANE, bn, _LANE):
            out = out + p[:, c:c + _LANE]
        return out

    def block(keep):
        def a(rows):
            blk = a_ref[rows, :]
            return blk if keep is None else jnp.where(keep, blk, 0.0)

        def v(rows):
            return jnp.concatenate([vb_ref[rows, :]] * (bn // _LANE), axis=1)

        def strips(step, carry):
            """``step`` over the full 8-row strips, ``_STRIPS`` a loop step."""
            loops = full // _SUB // _STRIPS

            def body(s, c):
                for u in range(_STRIPS):
                    r0 = pl.multiple_of((s * _STRIPS + u) * _SUB, _SUB)
                    c = step(pl.ds(r0, _SUB), c)
                return c

            if loops:
                carry = lax.fori_loop(0, loops, body, carry)
            for r0 in range(loops * _STRIPS * _SUB, full, _SUB):
                carry = step(pl.ds(r0, _SUB), carry)
            return carry

        # t = v^T A_i: sublane-partial sums, then one sublane reduction
        tacc = strips(lambda rows, c: c + a(rows) * v(rows),
                      jnp.zeros((_SUB, bn), jnp.float32))
        t = jnp.sum(tacc, axis=0, keepdims=True)
        if full < d:
            tail = pl.ds(full, d - full)
            t = t + jnp.sum(a(tail) * v(tail), axis=0, keepdims=True)

        # acc += A_i t^T, still lane-partial
        def add(rows, c):
            acc_ref[rows, :] += lanes(a(rows) * t)
            return c

        strips(add, 0)
        if full < d:
            add(pl.ds(full, d - full), 0)

    if last_cols == bn:
        block(None)
    else:
        @pl.when(i < last)
        def _body():
            block(None)

        @pl.when(i == last)
        def _masked():
            block(lax.broadcasted_iota(jnp.int32, (1, bn), 1) < last_cols)

    @pl.when(i == last)
    def _out():
        o_ref[...] = jnp.sum(acc_ref[...], axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def gram_matvec_pallas(x: jnp.ndarray, v: jnp.ndarray, *, block: int,
                       interpret: bool) -> jnp.ndarray:
    """x: (n, d), v: (d,). Returns X^T (X v), (d,) float32. ``block`` rows
    of X per grid step, a multiple of 128; X is read as ``x.T``, which is
    a bitcast where X is column-major."""
    n, d = x.shape
    steps = pl.cdiv(n, block)
    last = n - (steps - 1) * block
    out = pl.pallas_call(
        functools.partial(_kernel, last_cols=last),
        grid=(steps,),
        in_specs=[pl.BlockSpec((d, block), lambda i: (0, i)),
                  pl.BlockSpec((d, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((d, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((d, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d, _LANE), jnp.float32),
                        pltpu.VMEM((d, _LANE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(d, block)),
        interpret=interpret,
    )(x.T, v.reshape(d, 1))
    return out.reshape(d)
