"""Inputs made from ``--seed``: the operands, pools and small matrices
the traffic sends. The generators follow ``chip_smoke.py``'s
``ocean_field`` and ``speech_data``; the ocean field is built on the
device, one block of rows at a time, so that set-up holds one copy and
makes no host array of its size. Over several chips each chip builds
its own rows."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from harness.traffic import rng_for

OCEAN_BLOCK = 1024
ROWS = "rows"


def row_blocks(chips: int) -> NamedSharding:
    """Rows in equal blocks over the first ``chips`` devices, on a mesh of
    the benchmark's own (the program's mesh is not the yardstick's)."""
    mesh = Mesh(np.array(jax.devices()[:chips]), (ROWS,))
    return NamedSharding(mesh, P(ROWS, None))


def ocean_factors(rows: int, cols: int, k: int, seed: int):
    """The field's k modes: unit-norm left factors scaled by singular
    values falling geometrically from 16 sqrt(rows) to 4 sqrt(rows), at
    least 3x the noise edge sqrt(rows) + sqrt(cols), and orthonormal right
    factors. Returns (left * sigma, right^T), float32."""
    rng = rng_for(seed, "ocean")
    left = rng.standard_normal((rows, k), dtype=np.float32)
    left /= np.linalg.norm(left, axis=0)
    right, _ = np.linalg.qr(rng.standard_normal((cols, k)))
    sigma = np.sqrt(rows) * np.geomspace(16.0, 4.0, k)
    return ((left * sigma.astype(np.float32)).astype(np.float32),
            np.ascontiguousarray(right.T.astype(np.float32)))


def _ocean_blocks(noise_seed, scaled_left, right_t, cols: int, block: int,
                  first=None):
    """The rows of ``scaled_left``, block by block: block ``i``'s noise
    comes from the key folded with its index in the whole field, which is
    ``first + i`` where these rows are one chip's share of it."""
    rows = scaled_left.shape[0]
    key = jax.random.key(noise_seed)

    def body(i, field):
        lo = i * block
        index = i if first is None else first + i
        noise = jax.random.normal(jax.random.fold_in(key, index),
                                  (block, cols), jnp.float32)
        modes = jnp.matmul(
            jax.lax.dynamic_slice_in_dim(scaled_left, lo, block), right_t,
            precision=jax.lax.Precision.HIGHEST)
        return jax.lax.dynamic_update_slice_in_dim(field, noise + modes,
                                                   lo, 0)

    return jax.lax.fori_loop(0, rows // block, body,
                             jnp.zeros((rows, cols), jnp.float32))


def row_block_program(rows: int, cols: int, block: int,
                      sharding: NamedSharding):
    """The field's build in row blocks, each chip's rows on that chip:
    a jitted function of (noise seed, scaled left factors, right factors
    transposed) that runs ``_ocean_blocks`` over each chip's share of the
    left factors, numbering its blocks from the share's first block in
    the whole field."""
    axis = sharding.spec[0] if sharding.spec else None
    if not isinstance(axis, str) or any(sharding.spec[1:]):
        raise ValueError(f"the field is built in row blocks over one mesh "
                         f"axis, not as {sharding.spec}")
    chips = sharding.mesh.shape[axis]
    if rows % (chips * block):
        raise ValueError(f"rows {rows} do not split into {chips} shares "
                         f"of whole {block}-row blocks")
    blocks = rows // chips // block

    def share(noise_seed, left, right_t):
        first = jax.lax.axis_index(axis) * blocks
        return _ocean_blocks(noise_seed, left, right_t, cols, block, first)

    return jax.jit(jax.shard_map(share, mesh=sharding.mesh,
                                 in_specs=(P(), P(axis, None), P()),
                                 out_specs=P(axis, None), check_vma=False))


def ocean_field(rows: int, cols: int, k: int, seed: int, sharding=None):
    """The ocean field on the device: unit white noise plus k dominant
    modes, as a float32 ``jax.Array`` placed with ``sharding`` (default:
    the default device); the same seed gives the same array, bit for bit,
    on one device or in row blocks over several (``row_blocks``)."""
    block = min(OCEAN_BLOCK, rows)
    if rows % block:
        raise ValueError(f"rows {rows} is not a multiple of {block}")
    scaled_left, right_t = ocean_factors(rows, cols, k, seed)
    noise_seed = int(rng_for(seed, "ocean-noise").integers(0, 2 ** 31 - 1))
    if sharding is not None and len(sharding.device_set) > 1:
        make = row_block_program(rows, cols, block, sharding)
        return make(noise_seed, jax.device_put(scaled_left, sharding),
                    jnp.asarray(right_t))
    make = jax.jit(_ocean_blocks, static_argnames=("cols", "block"),
                   out_shardings=sharding)
    return make(noise_seed, jnp.asarray(scaled_left), jnp.asarray(right_t),
                cols=cols, block=block)


class SpeechPool:
    """Distinct (X, Y) jobs of the speech deployment: standardized features
    around one Gaussian centre per class, and one-hot labels. Each job is
    the run's one data set with its rows rolled by a shift of its own and
    its classes renamed by a permutation of its own: no two jobs hold the
    same content or pose the same normal equations (a job's W is another
    job's with its columns permuted), and every job is the same work."""

    def __init__(self, rows: int, feats: int, classes: int, seed: int,
                 jobs: int):
        rng = rng_for(seed, "speech")
        labels = rng.integers(0, classes, rows)
        centres = rng.standard_normal((classes, feats), dtype=np.float32)
        x = rng.standard_normal((rows, feats), dtype=np.float32)
        x += 0.5 * centres[labels]
        shifts = rng.choice(np.arange(1, rows), jobs, replace=False)
        perms: list[np.ndarray] = []
        while len(perms) < jobs:
            perm = rng.permutation(classes)
            if not any(np.array_equal(perm, p) for p in perms):
                perms.append(perm)
        self.jobs = []
        for s, perm in zip(shifts, perms):
            y = np.zeros((rows, classes), np.float32)
            y[np.arange(rows), perm[labels]] = 1.0
            self.jobs.append((np.roll(x, int(s), axis=0),
                              np.roll(y, int(s), axis=0)))
