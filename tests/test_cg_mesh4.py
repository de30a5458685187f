"""``cg_solve`` on the engine mesh over four devices, rehearsed on four
virtual CPU devices in a subprocess (the device count is fixed when JAX
starts), beside the same solve on one device.

X and Y land in row blocks; the random-feature expansion, the right-hand
side and the step run as GSPMD partitions them, and the true residual
runs on each device's own rows with one sum across them. W and the
reported residual are checked against the float64 reference backend's
``cg_solve`` on the same features."""
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

from repro.core import AlchemistContext, AlchemistEngine
from repro.core.backends import reference
from repro.core.engine import make_engine_mesh
from repro.core.libraries import skylark
from repro.kernels.rf_map import ops as rf_ops

N, FEATS, CLASSES, D = 1024, 16, 5, 512
LAM, BANDWIDTH, SEED, ITERS = 1e-3, 4.0, 3, 60
assert len(jax.devices()) == 4
rng = np.random.default_rng(0)
x = rng.standard_normal((N, FEATS), dtype=np.float32)
y = np.eye(CLASSES, dtype=np.float32)[rng.integers(0, CLASSES, N)]
# the reference backend draws its own features; give it the program's
z = np.asarray(rf_ops.rf_map(x, D, bandwidth=BANDWIDTH, seed=SEED),
               np.float64)
y64 = y.astype(np.float64)
ref = reference._cg_solve(z, y64, lam=LAM, max_iters=ITERS, tol=0.0)
b = z.T @ y64


def residual64(w):
    w = np.asarray(w, np.float64)
    r = b - (z.T @ (z @ w) + N * LAM * w)
    return float(np.max(np.linalg.norm(r, axis=0)
                        / np.linalg.norm(b, axis=0)))


out = {}
for devices in (4, 1):
    engine = AlchemistEngine(make_engine_mesh(devices), cache_entries=0)
    engine.load_library("skylark", skylark)
    ac = AlchemistContext(engine=engine)
    sk = ac.library("skylark")
    X, Y = ac.send_matrix(x), ac.send_matrix(y)
    spread = [engine.get(h.handle, session=ac.session).sharding
              for h in (X, Y)]
    W = sk.cg_solve(X, Y, lam=LAM, rf_dim=D, bandwidth=BANDWIDTH,
                    seed=SEED, max_iters=ITERS, tol=0.0)
    w = W.to_numpy()
    stats = W.stats()
    out[devices] = {
        "layouts": [X.layout, Y.layout],
        "devices": [len(s.device_set) for s in spread],
        "full_copy": [bool(s.is_fully_replicated) for s in spread],
        "replicated": engine.placement_stats()["stores"],
        "w_rel": float(np.max(np.abs(w - ref["W"]))
                       / np.max(np.abs(ref["W"]))),
        "reported": stats["relative_residual"],
        "true64": residual64(w),
        "iterations": stats["iterations"],
        "shards": stats["shards"],
        "reduce_bytes": stats["reduce_bytes"],
        "w": w.tolist()}
    ac.stop()
    engine.shutdown()
w4, w1 = (np.asarray(out[d].pop("w")) for d in (4, 1))
out["w_4_vs_1"] = float(np.max(np.abs(w4 - w1)) / np.max(np.abs(w1)))
print("RESULT " + json.dumps(out))
"""


def test_cg_solve_runs_on_row_blocks_over_four_devices():
    proc = subprocess.run([sys.executable, "-c", CODE], env=ENV, cwd=REPO,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    [line] = [ln for ln in proc.stdout.splitlines()
              if ln.startswith("RESULT ")]
    out = json.loads(line[len("RESULT "):])
    four, one = out["4"], out["1"]
    # X and Y in row blocks, one a device, and no full copy anywhere
    assert four["layouts"] == ["rowblock", "rowblock"]
    assert four["devices"] == [4, 4]
    assert four["full_copy"] == [False, False]
    assert four["replicated"] == 0
    assert (four["shards"], four["reduce_bytes"]) == (4, 4 * 512 * 5)
    assert (one["shards"], one["reduce_bytes"]) == (1, 0)
    for got in (four, one):
        assert got["iterations"] == 60
        # converged in float32 to the float64 solution: the difference is
        # float32's rounding times the system's conditioning (6e-6 and
        # 4e-6 read here), well inside 1e-4
        assert got["w_rel"] <= 1e-4, got
        # the reported residual is the float32 evaluation, in blocks of
        # rows, of the true one, which float64 reads at the floor float32
        # leaves (1.6e-7 and 1.8e-7 here): both lie under 1e-6, and the
        # evaluation's own rounding moved it by 4% and 0.4%, well inside
        # half of it
        assert got["reported"] <= 1e-6 and got["true64"] <= 1e-6, got
        assert abs(got["reported"] - got["true64"]) <= 0.5 * got["true64"]
    # four devices and one answer the same problem alike; only the order
    # of the sums differs
    assert out["w_4_vs_1"] <= 1e-4, out
