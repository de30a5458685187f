"""The correctness controls, at a cell's own sizes, on the chip.

    python3 bench/control.py --config ocean --kind svd --seeds 11 12 13
    python3 bench/control.py --config ocean_4chip --kind svd --chips 4 \
        --seeds 11 12 13

The control is the reference put in the program's place, with its matrix
products one precision step below what the configuration states
(``high``: bfloat16 three-pass instead of float32 at full precision).
Its answers go through the cell's own comparison and are held to the
configuration's limits, as a run's answers are; each seed prints the
numbers beside their limits and ``correct``, which has to be false. The
benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from harness import data, reference  # noqa: E402
from harness.main import judge, within  # noqa: E402
from harness.spec import Benchmark, load_module  # noqa: E402


def _kind(name: str):
    return load_module(os.path.join(HERE, "kinds", name + ".py"))


def svd_checks(cfg: dict, seed: int, chips: int = 1) -> dict:
    """The control's triplets against the reference's, each on the field
    in row blocks over ``chips`` devices, as a run's SVD answers are
    compared."""
    svd = _kind("svd")
    shape, k = (cfg["rows"], cfg["cols"]), cfg["rank"]
    ref = svd.triplets(shape, k, seed, chips)
    ctl = svd.triplets(shape, k, seed, chips, reference.HIGH)
    return judge(svd.compare([ctl], ref), cfg["limits"])


def cg_checks(cfg: dict, seed: int, iters: int) -> dict:
    """The control's CG solve of the seed's first job, judged by that job's
    normal-equations residual, as a run's W is."""
    cg = _kind("cg_job")
    pool = data.SpeechPool(cfg["rows"], cfg["feats"], cfg["classes"], seed,
                           jobs=1)
    x, y = pool.jobs[0]
    w = reference.cg_solve(x, y, cfg["lam"], cfg["rf_dim"], cfg["bandwidth"],
                           cfg["rf_seed"], iters, reference.HIGH)
    return judge(cg.compare(cfg, pool, [{"job": 0, "W": w}]), cfg["limits"])


CHECKS = {"svd": svd_checks, "cg_job": cg_checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--kind", required=True, choices=sorted(CHECKS))
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--chips", type=int, default=1,
                    help="devices the svd field is row-blocked over")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cfg = Benchmark().config({"config": args.config})
    import jax

    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    failed = 0
    for seed in args.seeds:
        t0 = time.perf_counter()
        extra = (args.iters,) if args.kind == "cg_job" else (args.chips,)
        checks = CHECKS[args.kind](cfg, seed, *extra)
        failed += not within(checks)
        print(json.dumps({"seed": seed, "seconds": time.perf_counter() - t0,
                          "correct": within(checks), "checks": checks}),
              flush=True)
    print(f"control: correct false on {failed} of {len(args.seeds)} seeds",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
