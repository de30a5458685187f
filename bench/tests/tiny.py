"""A copy of the benchmark at sizes the CPU runs in seconds, and a way to
run one of its cells there with the look for a TPU skipped. A cell on
several chips runs on as many virtual CPU devices."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

OCEAN = {"rows": 4096, "cols": 256, "rank": 8, "oversample": 16}
SIZES = {"ocean": OCEAN, "ocean_4chip": OCEAN,
         "speech": {"rows": 2048, "feats": 16, "classes": 5,
                    "rf_dim": 256}}
MIXES = {"cg": {"pool": 3}}

#: runs a cell as ``bench/run.py`` does, on whatever JAX finds, with
#: the chip check answered as a TPU v5e would answer it where JAX finds
#: as many devices as the cell asks for; ``FAULT`` is Python run first,
#: to break the program underneath
WRAPPER = """
import sys, time
T0 = time.perf_counter()
sys.path.insert(0, {bench!r})
sys.path.insert(0, {src!r})
from harness import device, main
def _chip(chips, table):
    import jax
    if len(jax.devices()) < chips:
        raise device.NoChip(f"the cell needs {{chips}} chips")
    return {{"platform": "tpu", "kind": "TPU v5 lite", "count": chips}}
device.require_chip = _chip
{fault}
sys.exit(main.main(sys.argv[1:], t_start=T0))
"""


def edit_json(path: str, **changes) -> None:
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def copy(dest: str) -> str:
    """The benchmark's files under ``dest``, shrunk; returns ``dest``."""
    shutil.copytree(BENCH, os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    for name, sizes in SIZES.items():
        edit_json(os.path.join(dest, "bench", "configs", name + ".json"),
                  **sizes)
    for name, changes in MIXES.items():
        edit_json(os.path.join(dest, "bench", "traffic", name + ".json"),
                  **changes)
    return dest


def cpu_env(dest: str, workload: str) -> dict:
    """The environment of a process that runs ``workload`` of the copy on
    the CPU: as many virtual devices as the cell asks for chips."""
    with open(os.path.join(dest, "BENCHMARK.json")) as f:
        cells = {c["name"]: c for c in json.load(f)["workloads"]}
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    chips = cells[workload]["chips"] if workload in cells else 1
    if chips > 1:
        env["XLA_FLAGS"] = " ".join(filter(None, [
            env.get("XLA_FLAGS", ""),
            f"--xla_force_host_platform_device_count={chips}"]))
    return env


def run(dest: str, workload: str, seconds: float = 2.0, trace: int = 0,
        seed: int = 3_000_000_019, fault: str = "",
        src: str = os.path.join(ROOT, "src")) -> tuple[int, str, str]:
    """Run one cell of the copy in a process of its own on the CPU, with
    the program under test imported from ``src``."""
    script = os.path.join(dest, "run_here.py")
    with open(script, "w") as f:
        f.write(WRAPPER.format(bench=os.path.join(dest, "bench"), src=src,
                               fault=fault))
    env = cpu_env(dest, workload)
    p = subprocess.run(
        [sys.executable, script, "--workload", workload, "--seed",
         str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        capture_output=True, text=True, env=env, timeout=600)
    return p.returncode, p.stdout, p.stderr


def result(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])
