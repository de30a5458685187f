# Pallas TPU kernels for the paper's compute hot spots:
#   gram          — blocked A^T A (Lanczos/CG matvec substrate)
#   normal_matvec — fused w -> X^T (X w) (the CG inner loop), and for one
#                   vector the VPU gram_matvec (the Lanczos matvec)
#   rf_map        — fused random-feature expansion cos(XW + b)
#   swa           — sliding-window flash attention (recurrentgemma / qwen3-sw)
# Each package: kernel (pl.pallas_call + BlockSpec), ops (wrapper with jnp
# fallback), ref (pure-jnp oracle used by the allclose test sweeps).
import jax


def interpret_mode() -> bool:
    """Whether the Pallas kernels run in interpret mode.

    The platform decides, not the caller: compiled on a TPU, interpreted
    everywhere else (the CPU runs of the test suite). Read at trace time
    by every kernel's ``ops`` wrapper.
    """
    return jax.default_backend() != "tpu"
