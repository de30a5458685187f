"""Pure-jnp oracles for the fused normal-equations matvec and the
one-pass Gram matvec."""
import jax.numpy as jnp


def normal_matvec_ref(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """w -> X^T (X w), fp32. x: (n, d), w: (d, c)."""
    xf = x.astype(jnp.float32)
    return xf.T @ (xf @ w.astype(jnp.float32))


def gram_matvec_ref(x: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """v -> X^T (X v) in two passes over X. x: (n, d), v: (d,)."""
    return x.T @ (x @ v)
