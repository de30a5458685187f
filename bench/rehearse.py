"""Compile the cells' device programs at the timed sizes for a described
TPU v5e, without a chip, and print each one's ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--only NAME ...]

What it compiles: the ocean field's on-device build, the program's Gram
matvec and U = X V at 193,536 x 8,096; the same, and the control's
split, row-blocked over a 2x2 mesh at ``ocean_4chip``'s 774,144 rows
(the bytes are per chip); the program's random-feature expansion, CG
right-hand side, step and residual at 140,800 x 10,000; and the
references and controls the check runs. A compile that passes is not a
chip run: it says the compiler accepts the program and how much memory
it asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def programs(cfg_dir: str, topo):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from harness import data, reference
    from repro.core.backends import jax_backend

    with open(os.path.join(cfg_dir, "ocean.json")) as f:
        oc = json.load(f)
    with open(os.path.join(cfg_dir, "ocean_4chip.json")) as f:
        oc4 = json.load(f)
    with open(os.path.join(cfg_dir, "speech.json")) as f:
        sp = json.load(f)
    f32 = jnp.float32
    n, d, k = oc["rows"], oc["cols"], oc["rank"]
    m, feats, c, width = sp["rows"], sp["feats"], sp["classes"], sp["rf_dim"]
    p = k + 12
    S = jax.ShapeDtypeStruct

    def step(x, lam_n, st):
        return jax_backend._cg_step(x, lam_n, st)

    state = (S((width, c), f32),) * 3 + (S((c,), f32),)

    def prepared(shape, precision):
        return jax.eval_shape(lambda a: reference.prepare(a, precision),
                              S(shape, f32))

    yield "ocean_build", jax.jit(data._ocean_blocks,
                                 static_argnames=("cols", "block")), (
        S((), jnp.int32), S((n, k), f32), S((k, d), f32)), {
        "cols": d, "block": data.OCEAN_BLOCK}
    yield "gram_matvec", jax_backend._gram_matvec, (S((n, d), f32),
                                                    S((d,), f32)), {}
    yield "svd_u", jax.jit(lambda x, v: x @ v), (S((n, d), f32),
                                                 S((d, k), f32)), {}
    yield "rf_expand", jax_backend._rf_expand, (S((m, feats), f32),), {
        "rf_dim": width, "bandwidth": sp["bandwidth"], "seed": 0,
        "use_pallas": False}
    yield "cg_rhs", jax_backend._cg_rhs, (S((m, width), f32),
                                          S((m, c), f32)), {}
    yield "cg_step", jax.jit(step), (S((m, width), f32), S((), f32),
                                     state), {}
    yield "cg_residual", jax_backend._cg_residual, (
        S((m, width), f32), S((m, c), f32), S((), f32), S((c,), f32),
        S((width, c), f32)), {}
    for precision in (reference.HIGHEST, reference.HIGH):
        yield f"ref_svd_block_{precision}", reference._gram_block, (
            prepared((n, d), precision), S((d, p), f32)), {}

    mesh = Mesh(np.array(topo.devices), (data.ROWS,))
    rows, whole = NamedSharding(mesh, P(data.ROWS, None)), \
        NamedSharding(mesh, P())
    n4 = oc4["rows"]

    def spread(shape, dtype=f32):
        return S(shape, dtype, sharding=rows)

    def copied(shape, dtype=f32):
        return S(shape, dtype, sharding=whole)

    yield "ocean_build_4chip", data.row_block_program(
        n4, d, data.OCEAN_BLOCK, rows), (
        copied((), jnp.int32), spread((n4, k)), copied((k, d))), {}
    yield "gram_matvec_4chip", jax_backend._gram_matvec, (
        spread((n4, d)), copied((d,))), {}
    yield "svd_u_4chip", jax.jit(lambda x, v: x @ v), (
        spread((n4, d)), copied((d, k))), {}
    yield "ctl_split_4chip", reference._split, (spread((n4, d)),), {}
    for precision in (reference.HIGHEST, reference.HIGH):
        yield f"ref_svd_block_{precision}_4chip", reference._gram_block, (
            jax.tree.map(lambda s: spread(s.shape, s.dtype),
                         prepared((n4, d), precision)), copied((d, p))), {}
    yield "ref_cg_residual", reference._residual, (
        S((m, feats), f32), S((m, c), f32), S((feats, width), f32),
        S((width,), f32), S((width, c), f32), S((), f32)), {"block": 1024}
    yield "ctl_cg_expand_high", reference._expand, (
        S((m, feats), f32), S((feats, width), f32), S((width,), f32)), {
        "precision": reference.HIGH, "block": 1024}
    yield "ctl_cg_high", reference._cg, (
        prepared((m, width), reference.HIGH), S((m, c), f32),
        S((), f32)), {"iters": 100}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda s: s if s.sharding is not None else
                            jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                 sharding=chip), tree)

    for name, fn, shapes, static in programs(os.path.join(HERE, "configs"),
                                             topo):
        if args.only and name not in args.only:
            continue
        with jax.default_matmul_precision("highest"):
            compiled = fn.lower(*place(shapes), **static).compile()
        mem = compiled.memory_analysis()
        print(json.dumps({
            "program": name,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
