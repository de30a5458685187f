"""Compile warmup: first-call latency with and without the
compile-latency subsystem (``core/compilecache.py``).

The offload pitch (Gittens et al., KDD 2018) prices the overheads
*around* the fast kernel. PR 5's fused ``jax.jit`` chains moved the
arithmetic into single compiled programs — but every new (chain
structure x operand shape) pays the full XLA trace+compile on the
critical path of the first call that exhibits it, and the compiled
program cache dies with the engine process. Under a shape-diverse
tenant mix that is a p99 killer.

This benchmark serves the same tenant mix (odd-shaped multiply / gram /
transpose / add plus a 3-stage fused multiply chain — every shape off
the bucket grid) against two engines sharing one persistent cache dir,
:data:`CACHE_DIR` (a fixed subdirectory of the one compile-cache
directory, ``compilecache.cache_dir()``), emptied before the cold phase:

* **cold** — a fresh engine, bucketing on, empty cache: each first call
  eats its own trace+compile (recorded in the executable index);
* **warm restart** — a *new* engine on the same cache dir after
  ``warmup()``: catalog AOT pre-compiles the bucketable routines for
  the bucket grid and the index replays every previously-served
  signature (including the fused chain) through JAX's disk cache — so
  the same tenant mix sees ZERO request-path compiles
  (``CompileLog.bucketed_request_compiles == 0``).

Reported per mix item: cold vs warm first-call wall seconds and the
aggregate speedup; plus warmup cost (off the request path) and the
CompileLog/executable-index accounting.

Run: ``PYTHONPATH=src:. python benchmarks/compile_warmup.py``
(``--smoke`` asserts the >=5x warm speedup, the zero-request-path
contract, and the index replay; ``--two-process`` proves the
executables survive a real process boundary; ``--json PATH`` writes the
machine-readable result).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import header, row
from repro.core import AlchemistContext, AlchemistEngine, compilecache
from repro.core.engine import make_engine_mesh
from repro.core.libraries import elemental

RNG = np.random.RandomState(42)

# shape-diverse tenant mix: every dimension off the pow2 bucket grid
MIX = [
    ("multiply", {"A": (37, 53), "B": (53, 29)}),
    ("gram", {"A": (100, 45)}),
    ("transpose", {"A": (77, 10)}),
    ("add", {"A": (19, 23), "B": (19, 23)}),
]
CHAIN_SHAPE = (19, 19)
CHAIN_STAGES = 3

# the buckets the mix lands in — warmup covers exactly what tenant
# traffic will ask for (a narrower warmup grid only absorbs its own
# buckets; request-path compiles on the rest still register in the
# executable index for the next warmup)
GRID = (32, 64, 128)

ARRAYS = {(routine, name): RNG.randn(*shape).astype(np.float32)
          for routine, shapes in MIX for name, shape in shapes.items()}
CHAIN_ARRAY = (RNG.randn(*CHAIN_SHAPE) / 4.0).astype(np.float32)

#: this benchmark's own cache: fixed (the path is part of JAX's cache
#: key), and its own, because the cold phase needs it empty
CACHE_DIR = os.path.join(compilecache.cache_dir(), "compile_warmup")


def _use_cache() -> None:
    """Turn JAX's persistent cache on at :data:`CACHE_DIR`, through the
    variable every entry point resolves its cache from."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    compilecache.enable_persistent_cache()


def _empty_cache() -> None:
    shutil.rmtree(CACHE_DIR, ignore_errors=True)


def _fresh() -> AlchemistContext:
    # result cache off: this benchmark prices compiles, not memoization
    engine = AlchemistEngine(make_engine_mesh(1), cache_entries=0,
                             bucketing=True, bucket_grid=GRID)
    engine.load_library("elemental", elemental)
    return AlchemistContext(engine=engine)


def _first_calls(ac: AlchemistContext) -> dict[str, float]:
    """Serve every mix item once, timing each blocking first call."""
    latencies: dict[str, float] = {}
    for routine, shapes in MIX:
        handles = {k: ac.send_matrix(ARRAYS[(routine, k)], dedup=False)
                   for k in shapes}
        t0 = time.perf_counter()
        ac.call("elemental", routine, **handles)
        latencies[routine] = time.perf_counter() - t0
    # the fused-chain signature (a multi-step program of its own)
    el = ac.library("elemental")
    al = ac.send_matrix(CHAIN_ARRAY, dedup=False)
    t0 = time.perf_counter()
    ac.engine.scheduler.pause()
    x = al
    for _ in range(CHAIN_STAGES):
        x = el.multiply(A=x, B=al)
    ac.engine.scheduler.resume()
    x.result()
    latencies["chain3"] = time.perf_counter() - t0
    return latencies


def _serve(warm: bool) -> dict:
    """One engine lifetime against :data:`CACHE_DIR`: optionally warm up,
    then serve the tenant mix; returns latencies + compile accounting."""
    ac = _fresh()
    engine = ac.engine
    try:
        warmup = engine.warmup(grid=GRID) if warm else None
        latencies = _first_calls(ac)
        stats = engine.compile_stats()
        return {"latencies": latencies, "warmup": warmup,
                "compile_stats": stats}
    finally:
        ac.stop()
        engine.shutdown()


def run(smoke: bool = False, json_path: str | None = None) -> dict:
    header("compile warmup: cold vs warm-restart first-call latency")
    _empty_cache()
    _use_cache()
    cold = _serve(warm=False)
    warm = _serve(warm=True)

    cold_total = sum(cold["latencies"].values())
    warm_total = sum(warm["latencies"].values())
    speedup = cold_total / warm_total if warm_total else float("inf")
    for name in cold["latencies"]:
        row(f"first_call_cold_{name}", cold["latencies"][name] * 1e6)
        row(f"first_call_warm_{name}", warm["latencies"][name] * 1e6,
            f"{cold['latencies'][name] / warm['latencies'][name]:.1f}x")
    row("first_call_cold_total", cold_total * 1e6)
    row("first_call_warm_total", warm_total * 1e6, f"{speedup:.1f}x")
    row("warmup_off_request_path", warm["warmup"]["warmup_s"] * 1e6,
        f"catalog={warm['warmup']['catalog']} "
        f"replayed={warm['warmup']['replayed']}")

    cs_cold = cold["compile_stats"]
    cs_warm = warm["compile_stats"]
    results = {
        "name": "compile_warmup",
        "grid": list(GRID),
        "cold_first_call_s": cold["latencies"],
        "warm_first_call_s": warm["latencies"],
        "cold_total_s": cold_total,
        "warm_total_s": warm_total,
        "speedup": speedup,
        "warmup_s": warm["warmup"]["warmup_s"],
        "warmup_catalog": warm["warmup"]["catalog"],
        "warmup_replayed": warm["warmup"]["replayed"],
        "cold_request_compiles": cs_cold["request_compiles"],
        "cold_request_compile_s": cs_cold["request_compile_s"],
        "warm_request_compiles": cs_warm["request_compiles"],
        "warm_bucketed_request_compiles":
            cs_warm["bucketed_request_compiles"],
        "warm_compile_hit_rate": cs_warm["hit_rate"],
        "executable_index": cs_warm["executable_index"],
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(results, f, indent=2)
        print(f"# wrote {json_path}")

    if smoke:
        # the cold engine really did pay per-signature compiles...
        assert cs_cold["request_compiles"] >= len(MIX) + 1, cs_cold
        # ...the warm restart replayed them from the index...
        assert warm["warmup"]["replayed"] >= len(MIX) + 1, warm["warmup"]
        # ...and then absorbed the whole mix: zero request-path compiles
        # for bucketed shapes after warmup (the CompileLog contract)
        assert cs_warm["request_compiles"] == 0, cs_warm
        assert cs_warm["bucketed_request_compiles"] == 0, cs_warm
        # warm first calls >=5x faster than cold
        assert speedup >= 5.0, (cold_total, warm_total, speedup)
        print("# smoke OK: warm-restart absorbed the tenant mix "
              f"({speedup:.1f}x faster first calls, zero request-path "
              "compiles)")
    return results


# ---------------------------------------------------------------------------
# two-process persistence round trip (the restart story, for real)
# ---------------------------------------------------------------------------
def _phase(warm: bool) -> None:
    """Subprocess body: one engine lifetime, printing its accounting."""
    _use_cache()
    out = _serve(warm=warm)
    summary = {
        "request_compiles": out["compile_stats"]["request_compiles"],
        "bucketed_request_compiles":
            out["compile_stats"]["bucketed_request_compiles"],
        "replayed": out["warmup"]["replayed"] if out["warmup"] else 0,
        "total_first_call_s": sum(out["latencies"].values()),
    }
    if warm:
        assert summary["request_compiles"] == 0, summary
        assert summary["replayed"] >= len(MIX) + 1, summary
    print("PHASE_RESULT " + json.dumps(summary))


def run_two_process() -> dict:
    """Serve the mix in one process, then prove a *separate* process
    warm-restarts from the same cache dir with zero request-path
    compiles (JAX disk cache + executable index across a real process
    boundary — the in-process version cannot distinguish disk reuse
    from leftover in-memory jit caches)."""
    header("compile warmup: two-process persistent-cache round trip")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root,
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)

    def spawn(phase: str) -> dict:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), f"--{phase}"],
            capture_output=True, text=True, env=env, cwd=root,
            timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(
                f"{phase} subprocess failed:\n{proc.stdout}\n{proc.stderr}")
        for line in proc.stdout.splitlines():
            if line.startswith("PHASE_RESULT "):
                return json.loads(line[len("PHASE_RESULT "):])
        raise RuntimeError(f"{phase} printed no PHASE_RESULT:\n"
                           f"{proc.stdout}")

    # the parent never touches a device: each child holds it in turn
    _empty_cache()
    first = spawn("persist-phase1")
    second = spawn("persist-phase2")
    row("two_process_cold_total", first["total_first_call_s"] * 1e6)
    row("two_process_warm_total", second["total_first_call_s"] * 1e6,
        f"replayed={second['replayed']}")
    assert first["request_compiles"] >= len(MIX) + 1, first
    assert second["request_compiles"] == 0, second
    print("# two-process OK: restarted process reused persisted "
          "executables without recompiling")
    return {"first": first, "second": second}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run with hard assertions")
    ap.add_argument("--json", metavar="PATH",
                    help="write machine-readable results to PATH")
    ap.add_argument("--two-process", action="store_true",
                    help="run the cross-process persistence round trip")
    ap.add_argument("--persist-phase1", action="store_true",
                    help=argparse.SUPPRESS)      # subprocess entry
    ap.add_argument("--persist-phase2", action="store_true",
                    help=argparse.SUPPRESS)      # subprocess entry
    args = ap.parse_args()
    if args.persist_phase1:
        _phase(warm=False)
        return
    if args.persist_phase2:
        _phase(warm=True)
        return
    if args.two_process:
        run_two_process()
        return
    print("name,us_per_call,derived")
    run(smoke=args.smoke, json_path=args.json)


if __name__ == "__main__":
    main()
