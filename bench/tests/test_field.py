"""The ocean field's build: in row blocks over four devices each device
builds its own rows, holds no more than its share and one block of
temporaries, and the field is bit for bit the one-device build; on one
device the build is the one the benchmark always ran."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import data
from harness.traffic import rng_for

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2_700_000_011

#: run on four virtual CPU devices (the device count is fixed when JAX
#: starts): the field of 65,536 x 128 built whole on one device and in
#: row blocks over four, and the row-block program's compiled memory
FOUR = r"""
import json, sys
sys.path.insert(0, {bench!r})
import jax, jax.numpy as jnp, numpy as np
from harness import data

rows, cols, k, seed = 65536, 128, 8, {seed}
sharding = data.row_blocks(4)
one = np.asarray(data.ocean_field(rows, cols, k, seed))
four = data.ocean_field(rows, cols, k, seed, sharding=sharding)
left, right_t = data.ocean_factors(rows, cols, k, seed)
program = data.row_block_program(rows, cols, data.OCEAN_BLOCK, sharding)
mem = program.lower(1, jax.device_put(left, sharding),
                    jnp.asarray(right_t)).compile().memory_analysis()
try:
    data.row_block_program(5 * data.OCEAN_BLOCK, cols, data.OCEAN_BLOCK,
                           sharding)
    uneven = None
except ValueError as e:
    uneven = str(e)
print(json.dumps({{
    "equal": bool(np.array_equal(one, np.asarray(four))),
    "spec": str(four.sharding.spec),
    "shards": sorted([s.device.id, *s.data.shape]
                     for s in four.addressable_shards),
    "temp": mem.temp_size_in_bytes,
    "share": rows // 4 * cols * 4,
    "block": data.OCEAN_BLOCK * cols * 4,
    "uneven": uneven}}))
"""


def test_row_blocks_over_four_devices_equal_the_one_device_field():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c",
                        FOUR.format(bench=BENCH, seed=SEED)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["equal"]
    assert out["spec"] == "PartitionSpec('rows',)"
    assert out["shards"] == [[i, 16384, 128] for i in range(4)]
    # the whole field is 33.5 MB: a build that gathers it onto each
    # device needs that much again in temporaries
    assert out["temp"] < out["share"] + out["block"]
    assert "do not split into 4 shares" in out["uneven"]


def todays_field(rows: int, cols: int, k: int, seed: int):
    """The one-device build as the benchmark has always made it: a copy,
    so that a change to ``data`` cannot move the field ``ocean.svd``
    reads."""
    block = min(1024, rows)
    scaled_left, right_t = data.ocean_factors(rows, cols, k, seed)
    noise_seed = int(rng_for(seed, "ocean-noise").integers(0, 2 ** 31 - 1))

    def build(noise_seed, scaled_left, right_t):
        key = jax.random.key(noise_seed)

        def body(i, field):
            lo = i * block
            noise = jax.random.normal(jax.random.fold_in(key, i),
                                      (block, cols), jnp.float32)
            modes = jnp.matmul(
                jax.lax.dynamic_slice_in_dim(scaled_left, lo, block),
                right_t, precision=jax.lax.Precision.HIGHEST)
            return jax.lax.dynamic_update_slice_in_dim(field, noise + modes,
                                                       lo, 0)

        return jax.lax.fori_loop(0, rows // block, body,
                                 jnp.zeros((rows, cols), jnp.float32))

    return np.asarray(jax.jit(build)(noise_seed, jnp.asarray(scaled_left),
                                     jnp.asarray(right_t)))


@pytest.mark.parametrize("sharding", [None, "row_blocks(1)"])
def test_one_device_field_is_unchanged(sharding):
    place = data.row_blocks(1) if sharding else None
    got = np.asarray(data.ocean_field(4096, 256, 8, SEED, sharding=place))
    assert np.array_equal(got, todays_field(4096, 256, 8, SEED))
