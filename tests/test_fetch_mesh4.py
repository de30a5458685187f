"""Fetching a matrix that spans four devices, rehearsed on four virtual CPU
devices in a subprocess (the device count is fixed when JAX starts).

A matrix in row blocks, or a full copy on each device, crosses to the
client one device block at a time: fetched over TCP and in memory, in
chunks that straddle the blocks, it comes back exact and compiles no
program (sliced on the device, every chunk shape of a sharded array
compiled a gather). Column blocks still slice on the device."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ,
       "PYTHONPATH": os.pathsep.join([os.path.join(REPO, "src"), REPO]),
       "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}

CODE = r"""
import json
import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import AlchemistContext, AlchemistEngine, costmodel
from repro.core.engine import make_engine_mesh
from repro.core.server import AlchemistServer

assert len(jax.devices()) == 4
engine = AlchemistEngine(make_engine_mesh(4), cache_entries=0)
server = AlchemistServer(engine=engine, host="127.0.0.1", port=0).start()
specs = {"rowblock": P("workers", None), "replicated": P(),
         "columns": P(None, "workers")}
rng = np.random.default_rng(0)
out = {}
for name, spec in specs.items():
    x = rng.standard_normal((1000, 24)).astype(np.float32)
    handle = engine.put(jax.device_put(x, NamedSharding(engine.mesh, spec)))
    got = {}
    compiles = costmodel.JIT_LOG.stats()["executables"]
    with AlchemistContext(address=server.address) as ac:
        got["tcp"] = ac.fetch(handle, num_partitions=3,
                              chunk_rows=97).collect()
    got["memory"] = AlchemistContext(engine=engine).fetch(
        handle, num_partitions=3, chunk_rows=97).collect()
    out[name] = {
        "devices": len(engine.get(handle).sharding.device_set),
        "exact": {k: bool(np.array_equal(v, x)) for k, v in got.items()},
        "compiles": costmodel.JIT_LOG.stats()["executables"] - compiles}
server.stop(shutdown_engine=True)
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def fetched():
    proc = subprocess.run([sys.executable, "-c", CODE], env=ENV, cwd=REPO,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    [line] = [ln for ln in proc.stdout.splitlines()
              if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("layout", ["rowblock", "replicated", "columns"])
def test_fetch_from_four_devices_is_exact(fetched, layout):
    got = fetched[layout]
    assert got["devices"] == 4
    assert got["exact"] == {"tcp": True, "memory": True}, got


@pytest.mark.parametrize("layout", ["rowblock", "replicated"])
def test_fetch_of_whole_rows_compiles_nothing(fetched, layout):
    """Each device's block crosses whole: no program runs on the device."""
    assert fetched[layout]["compiles"] == 0, fetched[layout]
