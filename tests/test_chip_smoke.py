"""``chip_smoke.py`` rehearsed on the CPU: each phase at a tiny size
(kernels interpreted), the same functions ``main`` runs on the chip at
the paper's widths; and ``main``'s platform gate, which refuses to run
or print a result without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke
from repro.core import AlchemistEngine
from repro.core.engine import make_engine_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def server():
    srv = chip_smoke.start_server(AlchemistEngine(make_engine_mesh(1)))
    yield srv
    srv.stop(shutdown_engine=True)


def test_ocean_svd_phase(server):
    out = chip_smoke.phase_ocean_svd(server, rows=512, cols=96, k=4, seed=0)
    assert out["s_max_rel_err"] <= chip_smoke.S_RTOL
    assert out["recon_rel_err"] <= chip_smoke.RECON_RTOL
    assert out["send_bytes"] == 512 * 96 * 4
    assert out["wire_bytes"] >= out["send_bytes"]
    # interpreted on the CPU: the program holds no compiled kernel
    assert out["gram_kernel_compiled"] is False


def test_speech_cg_phase(server):
    # 50 iterations bring the residual to ~1.5e-5, where the chip run
    # stops, and well above the rounding of its float32 evaluation
    out = chip_smoke.phase_speech_cg(
        server, rows=512, feats=32, classes=5, rf_dim=300, iters=50,
        bandwidth=4.0, lam=1e-4, seed=0)
    assert out["default"]["kernels"] == {"rf_map": "jnp",
                                         "normal_matvec": "jnp"}
    # d=300 fits a VMEM row block: the fused kernel ran
    assert out["pallas"]["kernels"] == {"rf_map": "pallas",
                                        "normal_matvec": "pallas"}
    for label in ("default", "pallas"):
        r = out[label]
        assert chip_smoke.cg_residual_ok(r["relative_residual"],
                                         r["engine_features_residual"])
        assert r["host_residual"] <= chip_smoke.CG_HOST_MAX


@pytest.mark.parametrize("reported,host,ok", [
    (1e-2, 1.01e-2, True),      # they agree
    (1e-2, 1.5e-2, False),      # they disagree
    (1.97e-5, 1.96e-5, True),   # a stalled float32 solve, reported truly
    (7e-8, 2e-5, False),        # a drifted recurrence reported as true
])
def test_cg_residual_rule(reported, host, ok):
    assert chip_smoke.cg_residual_ok(reported, host) is ok


def test_two_tenants_phase(server):
    # the heavy SVD runs ~5 s on the CPU, a few times the light burst's
    # cold compile, so the two overlap as the phase checks
    out = chip_smoke.phase_two_tenants(
        server, heavy=(8192, 512), heavy_iters=512,
        shapes=((100, 8), (300, 24)), seed=0)
    assert out["fused_tasks"] >= 1 and out["fused_ops"] == 10
    assert len(out["errors"]) == 2


def test_main_refuses_a_platform_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 2
    captured = capsys.readouterr()
    assert "no TPU" in captured.err
    for line in captured.out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_script_alone_fails_without_the_repository(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
