"""Public wrappers for the fused normal-equations matvec and the one-pass
Gram matvec."""
from __future__ import annotations

import functools

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.kernels.normal_matvec.gram_matvec import gram_matvec_pallas, \
    vmem_bytes
from repro.kernels.normal_matvec.normal_matvec import normal_matvec_pallas
from repro.kernels.normal_matvec.ref import gram_matvec_ref, \
    normal_matvec_ref

# one row block must fit VMEM: bm * d * 4B; cap d so bm=128 stays ~4 MiB
_MAX_FUSED_D = 8192

#: rows of X per step of the Gram matvec kernel: at the ocean field's
#: width on a v5e, 256 to 1,024 all read X at 752 GB/s
_GRAM_BLOCK = 512


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


@functools.cache
def vmem_capacity() -> int:
    """VMEM of one TensorCore of this process's TPU, as Pallas describes
    the chip; 0 where it describes none (a CPU, or a TPU it does not
    know)."""
    try:
        return pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:
        return 0


def gram_path(d: int, dtype, *, compiled: bool, devices: int,
              row_blocks: bool, column_major: bool, vmem: int) -> str:
    """How :func:`gram_matvec` applies X^T X to one vector for an (n, d)
    operand: ``"cols"``, the one-pass kernel over the rows of X^T, or
    ``"xla"``, two passes of XLA. The kernel needs a platform that
    compiles it (on the CPU it would run interpreted), X on one device or
    its rows in equal blocks over one mesh axis (``row_blocks``: each
    device then runs the kernel on its own block; GSPMD cannot partition
    a kernel, so on any other split it would gather X), float32 (its
    tiles), each device's block column-major (the layout it reads without
    a copy) and a block that fits three quarters of the chip's ``vmem``
    bytes."""
    if not compiled or (devices != 1 and not row_blocks) \
            or jnp.dtype(dtype) != jnp.float32 or not column_major \
            or 4 * vmem_bytes(d, _GRAM_BLOCK) > 3 * vmem:
        return "xla"
    return "cols"


def gram_matvec(x: jnp.ndarray, v: jnp.ndarray, *,
                path: str = "xla") -> jnp.ndarray:
    """v -> X^T (X v) by ``path`` (:func:`gram_path`); never materializes
    X^T X."""
    if path == "xla":
        return gram_matvec_ref(x, v)
    return gram_matvec_pallas(x, v, block=_GRAM_BLOCK,
                              interpret=kernels.interpret_mode())
