"""The engine mesh over four devices, rehearsed on four virtual CPU
devices in a subprocess (the device count is fixed when JAX starts).

Row-block operands must reach every compiled program in the sharding
they live in: ``gram``, ``A @ A.T`` and a fused chain go through the
AOT (bucketed) path, ``truncated_svd`` through the host-loop driver. Each
is checked against the float64 reference, and ``chip_smoke``'s four-chip
phase runs at a tiny size against its one-device engine."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ,
       "PYTHONPATH": os.pathsep.join([os.path.join(REPO, "src"), REPO]),
       "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}

CODE = r"""
import json
import numpy as np
import jax

import chip_smoke
from repro.core import AlchemistContext, AlchemistEngine
from repro.core.backends import reference
from repro.core.engine import make_engine_mesh
from repro.core.libraries import elemental


def rel(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float64) - want))
                 / np.max(np.abs(want)))


assert len(jax.devices()) == 4
engine = AlchemistEngine(make_engine_mesh(4), cache_entries=0)
engine.load_library("elemental", elemental)
ac = AlchemistContext(engine=engine)
el = ac.library("elemental")
a = np.random.default_rng(0).standard_normal((256, 64), dtype=np.float32)
a64 = a.astype(np.float64)
A = ac.send_matrix(a)
arr = engine.get(A.handle, session=ac.session)
out = {"layout": A.layout, "devices": len(arr.sharding.device_set),
       "full_copy": bool(arr.sharding.is_fully_replicated)}
g64 = a64.T @ a64
out["gram"] = rel(el.gram(A).to_numpy(), g64)
out["aat"] = rel((A @ A.T).to_numpy(), a64 @ a64.T)
before = engine.task_log.stats()["fused_tasks"]
engine.scheduler.pause()
try:
    G = el.gram(A)
    C = (G @ G) + G
finally:
    engine.scheduler.resume()
out["chain"] = rel(C.to_numpy(), g64 @ g64 + g64)
out["fused_tasks"] = engine.task_log.stats()["fused_tasks"] - before
_, S, V = el.truncated_svd(A, k=5)
s_ref = reference._truncated_svd(a, k=5)["S"].astype(np.float64)
out["svd_s"] = float(np.max(np.abs(S.to_numpy().ravel() - s_ref) / s_ref))
# rows that do not divide the mesh: a full copy on every device, counted
before = engine.placement_stats()
ac.send_matrix(a[:250]).result()
after = engine.placement_stats()
out["replicated"] = [after["stores"] - before["stores"],
                     after["bytes"] - before["bytes"]]
ac.stop()
engine.shutdown()
phase = chip_smoke.phase_four_chips(rows=512, cols=96, k=4, seed=0)
out["phase_s_max_rel_err"] = phase["s_max_rel_err"]
print("RESULT " + json.dumps(out))
"""


def test_row_block_operands_run_on_a_four_device_mesh():
    proc = subprocess.run([sys.executable, "-c", CODE], env=ENV, cwd=REPO,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    [line] = [ln for ln in proc.stdout.splitlines()
              if ln.startswith("RESULT ")]
    out = json.loads(line[len("RESULT "):])
    assert out["layout"] == "rowblock"
    assert out["devices"] == 4 and not out["full_copy"]
    assert out["replicated"] == [1, 250 * 64 * 4]
    assert out["fused_tasks"] >= 1
    for key in ("gram", "aat", "chain"):
        assert out[key] <= 1e-5, (key, out)
    assert out["svd_s"] <= 1e-4, out
    assert out["phase_s_max_rel_err"] <= 1e-4, out
