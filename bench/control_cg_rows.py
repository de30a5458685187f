"""The CG control in row blocks over several chips, for a configuration
whose expanded features do not fit one chip.

    python3 bench/control_cg_rows.py --config speech_60k_4chip --chips 4 \
        --seeds 11 12 13

``control.py``'s CG control expands all of Z on one device. Here each
chip expands its own rows of the seed's first job (``data.row_blocks``,
a mesh of the benchmark's own), block by block, straight into the form
its products read at the stated precision; the reference's CG runs on
those row blocks, each step summing the chips' Z^T (Z P). Written
from the mathematics with the reference's own features and products
(``harness.reference``), it imports nothing of the program. At ``high``
(the default: one step below the ``highest`` the configurations state)
it is the control, and its W goes through the cell's comparison; each
seed prints the numbers beside their limits and ``correct``, which has
to be false. At ``highest`` it is the reference's solve and has to be
correct. The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from harness import data, reference  # noqa: E402
from harness.main import judge, within  # noqa: E402
from harness.spec import Benchmark, load_module  # noqa: E402


def _expand(x, w, b, precision: str, block: int):
    """One chip's rows of Z, prepared for ``precision``, built block by
    block so that no float32 copy of the chip's Z is held beside them."""
    blk, full, rest = reference._blocks(x.shape[0], block)
    parts = jax.eval_shape(lambda a: reference.prepare(a, precision),
                           jax.ShapeDtypeStruct((1, w.shape[1]),
                                                jnp.float32))
    z = tuple(jnp.zeros((x.shape[0], w.shape[1]), p.dtype) for p in parts)

    def put(z, lo, rows):
        new = reference.prepare(reference._features(rows, w, b, precision),
                                precision)
        return tuple(jax.lax.dynamic_update_slice_in_dim(a, v, lo, 0)
                     for a, v in zip(z, new))

    z = jax.lax.fori_loop(
        0, full,
        lambda i, z: put(z, i * blk,
                         jax.lax.dynamic_slice_in_dim(x, i * blk, blk)), z)
    if rest:
        z = put(z, full * blk, x[full * blk:])
    return z


@functools.lru_cache(maxsize=None)
def _expansion(chips: int, precision: str, block: int):
    """Each chip's rows of Z, prepared, from X in row blocks over
    ``chips`` devices. The expansion alone takes a ``shard_map``: sliced
    block by block on a split row axis, Z would be gathered onto every
    chip."""
    sharding = data.row_blocks(chips)
    rows = P(data.ROWS, None)
    return sharding, jax.jit(jax.shard_map(
        lambda x, w, b: _expand(x, w, b, precision, block),
        mesh=sharding.mesh, in_specs=(rows, P(), P()), out_specs=rows,
        check_vma=False))


def cg_solve(x: np.ndarray, y: np.ndarray, cfg: dict, iters: int,
             chips: int, precision: str = reference.HIGH,
             block: int = 1024) -> np.ndarray:
    """W after ``iters`` CG steps on the configuration's normal equations
    for the job (x, y), in row blocks over the first ``chips`` devices."""
    sharding, expand = _expansion(chips, precision, block)
    w_rf, b_rf = reference.rf_weights(x.shape[1], cfg["rf_dim"],
                                      cfg["bandwidth"], cfg["rf_seed"])
    z = expand(jax.device_put(x, sharding), w_rf, b_rf)
    lam_n = jnp.float32(x.shape[0] * cfg["lam"])
    # the reference's solve, partitioned over the row blocks: each step
    # sums the chips' partial Z^T (Z P) in one all-reduce
    return np.asarray(reference._cg(z, jax.device_put(y, sharding), lam_n,
                                    iters))


def checks(cfg: dict, seed: int, iters: int, chips: int,
           precision: str) -> dict:
    """The solve of the seed's first job, judged by that job's
    normal-equations residual, as a run's W is."""
    cg = load_module(os.path.join(HERE, "kinds", "cg_job.py"))
    pool = data.SpeechPool(cfg["rows"], cfg["feats"], cfg["classes"], seed,
                           jobs=1)
    x, y = pool.jobs[0]
    w = cg_solve(x, y, cfg, iters, chips, precision)
    return judge(cg.compare(cfg, pool, [{"job": 0, "W": w}]), cfg["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--precision", default=reference.HIGH,
                    choices=(reference.HIGH, reference.HIGHEST))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cfg = Benchmark().config({"config": args.config})
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "chips": args.chips, "precision": args.precision}),
          flush=True)
    failed = 0
    for seed in args.seeds:
        t0 = time.perf_counter()
        found = checks(cfg, seed, args.iters, args.chips, args.precision)
        failed += not within(found)
        print(json.dumps({"seed": seed, "seconds": time.perf_counter() - t0,
                          "correct": within(found), "checks": found}),
              flush=True)
    print(f"control: correct false on {failed} of {len(args.seeds)} seeds",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
