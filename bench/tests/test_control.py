"""The controls at a size a test run holds: the reference put in the
program's place with its products one precision step down (``high``,
bfloat16 three-pass) goes through the cell's own comparison and reads
``correct`` false, where the program's run, held to the same limits,
reads true.

The four-chip cell's control runs ``control.py`` on four virtual CPU
devices, as its run does. The limits here are the committed ones scaled
to the test's size. At the
cells' sizes the chip separates program and control by 88x or more, and
the committed limits sit between them (PERF.md). At this size the two
lie 2x to 6x apart on this seed, so each limit is set between the two
readings on the CPU by the same rule: ``s_rel`` program 8.7e-8, control
5.0e-7; ``u_dist`` 3.4e-7 and 1.9e-6; ``v_dist`` 1.2e-6 and 1.3e-6, not
separated, so held above the program only; ``cg.residual`` 3.4e-6 and
2.1e-5."""
import json
import os
import subprocess
import sys

import pytest

import tiny
from harness.main import within
from harness.spec import load_module

SEED = 2_500_000_003
ITERS = 400
LIMITS = {"ocean": {"svd.s_rel": 2e-7, "svd.v_dist": 3e-6,
                    "svd.u_dist": 8e-7},
          "speech": {"cg.residual": 8e-6}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny copy, with a speech problem large enough and solved long
    enough that full precision converges past where ``high`` stalls (at
    100 steps of the tiniest problem both stop short of their floors),
    and the limits at this size."""
    root = tiny.copy(str(tmp_path_factory.mktemp("bench")))
    bench = os.path.join(root, "bench")
    tiny.edit_json(os.path.join(bench, "configs", "speech.json"),
                   rows=4096, feats=32, classes=8, rf_dim=512,
                   limits=LIMITS["speech"])
    for name in ("ocean", "ocean_4chip"):
        tiny.edit_json(os.path.join(bench, "configs", name + ".json"),
                       limits=LIMITS["ocean"])
    tiny.edit_json(os.path.join(bench, "traffic", "cg.json"),
                   requests=[{"kind": "cg_job", "share": 1.0,
                              "max_iters": ITERS, "tol": 0.0}])
    return root


@pytest.fixture(scope="module")
def control(root):
    return load_module(os.path.join(root, "bench", "control.py"))


def cfg(root, name):
    with open(os.path.join(root, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def program_is_correct(root, cell):
    rc, out, err = tiny.run(root, cell, seed=SEED)
    assert rc == 0, err
    res = tiny.result(out)
    assert res["correct"], err
    return res["checks"]


def control_on_chips(root, cell, config, chips):
    """``control.py`` on ``chips`` devices, in a process of its own, with
    the program imported from this checkout."""
    env = tiny.cpu_env(root, cell)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [os.path.join(tiny.ROOT, "src"),
                      env.get("PYTHONPATH", "")]))
    p = subprocess.run(
        [sys.executable, os.path.join(root, "bench", "control.py"),
         "--config", config, "--kind", "svd", "--chips", str(chips),
         "--seeds", str(SEED)],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False, p.stdout
    return line["checks"]


@pytest.mark.parametrize("cell,config,kind", [
    ("ocean.svd", "ocean", "svd"), ("speech.cg", "speech", "cg_job"),
    ("ocean.svd_4chip", "ocean_4chip", "svd")])
def test_control_is_not_correct(root, control, cell, config, kind):
    extra = (ITERS,) if kind == "cg_job" else ()
    if cell == "ocean.svd_4chip":
        checks = control_on_chips(root, cell, config, 4)
    else:
        checks = control.CHECKS[kind](cfg(root, config), SEED, *extra)
    assert not within(checks), checks
    prog = program_is_correct(root, cell)
    assert set(prog) == set(checks)
    assert all(prog[k]["limit"] == checks[k]["limit"] for k in checks)
