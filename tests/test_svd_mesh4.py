"""The Lanczos Gram matvec on a row-block X over four devices, rehearsed
on four virtual CPU devices in a subprocess (the device count is fixed
when JAX starts).

With X in row blocks the one-pass kernel (interpreted here) runs on each
device's own rows under a ``shard_map``, and one all-reduce adds the
partials. The product is checked against the two-pass reference in
float32, and a served ``truncated_svd`` with the kernel forced (as
``test_extensions.test_truncated_svd_on_the_fused_matvec`` forces it on
one device) against the float64 reference backend's singular values."""
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
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import AlchemistContext, AlchemistEngine
from repro.core.backends import jax_backend, reference
from repro.core.engine import make_engine_mesh
from repro.core.libraries import elemental
from repro.kernels.normal_matvec import ops as nm_ops
from repro.kernels.normal_matvec.ref import gram_matvec_ref

# 600 rows a device: one whole 512-row block and a masked tail
N, D, K = 2400, 200, 5
assert len(jax.devices()) == 4
mesh = make_engine_mesh(4)
rng = np.random.default_rng(3)
scales = np.linspace(40.0, 10.0, K)
x = (rng.standard_normal((N, K)) * scales) \
    @ np.linalg.qr(rng.standard_normal((D, K)))[0].T
x = (x + rng.standard_normal((N, D))).astype(np.float32)
v = rng.standard_normal(D).astype(np.float32)
out = {}

splits = {"rows": P("workers", None), "replicated": P(),
          "cols": P(None, "workers")}
arrays = {k: jax.device_put(x, NamedSharding(mesh, s))
          for k, s in splits.items()}
out["row_split"] = {k: jax_backend._row_split(a) is not None
                    for k, a in arrays.items()}
rows = jax_backend._row_split(arrays["rows"])
got = jax_backend._gram_matvec(arrays["rows"], jnp.asarray(v), path="cols",
                               rows=rows)
want = gram_matvec_ref(jnp.asarray(x), jnp.asarray(v))
exact = x.astype(np.float64).T @ (x.astype(np.float64) @ v)
out["dtype"] = str(got.dtype)
out["replicated_out"] = bool(got.sharding.is_fully_replicated)
out["vs_ref"] = float(np.max(np.abs(np.asarray(got, np.float64)
                                    - np.asarray(want, np.float64)))
                      / np.max(np.abs(exact)))
out["vs_exact"] = float(np.max(np.abs(np.asarray(got, np.float64) - exact))
                        / np.max(np.abs(exact)))

# the served SVD, its matvec forced onto the kernel
picked, seen = [], []
nm_ops.gram_path = lambda *a, **k: picked.append("cols") or "cols"
inner = jax_backend._gram_matvec


def spy(x, v, path="xla", rows=None):
    seen.append((path, None if rows is None else rows[1]))
    return inner(x, v, path=path, rows=rows)


jax_backend._gram_matvec = spy
engine = AlchemistEngine(mesh, cache_entries=0)
engine.load_library("elemental", elemental)
ac = AlchemistContext(engine=engine)
el = ac.library("elemental")
A = ac.send_matrix(x)
out["layout"] = A.layout
U, S, V = el.truncated_svd(A, k=K, seed=1)
s = S.to_numpy().ravel()
ac.stop()
engine.shutdown()
ref = reference._truncated_svd(x, k=K, seed=1)["S"]
out["picked"] = picked
out["seen"] = sorted(set(map(tuple, seen)))
out["s_rel"] = float(np.max(np.abs(s - ref) / ref))
print("RESULT " + json.dumps(out))
"""


def _run():
    proc = subprocess.run([sys.executable, "-c", CODE], env=ENV, cwd=REPO,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    [line] = [ln for ln in proc.stdout.splitlines()
              if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def test_gram_matvec_runs_the_kernel_on_each_devices_rows():
    out = _run()
    # only whole rows in equal blocks, one a device, take the kernel
    assert out["row_split"] == {"rows": True, "replicated": False,
                                "cols": False}
    # float32, on every device, within the float32 rounding of the
    # product (``test_extensions.GRAM_TOL``: 16 ulps of its largest
    # entry), as the two-pass reference is
    assert out["dtype"] == "float32" and out["replicated_out"]
    assert out["vs_exact"] <= 2e-6 and out["vs_ref"] <= 2e-6, out
    # served: the engine lays X in row blocks, the routine picks the
    # kernel once per call and runs every matvec on the row blocks
    assert out["layout"] == "rowblock"
    assert out["picked"] == ["cols"]
    assert out["seen"] == [["cols", "workers"]]
    # the float64 reference backend's singular values, to float32's
    # rounding (1e-5, as on one device)
    assert out["s_rel"] <= 1e-5, out
