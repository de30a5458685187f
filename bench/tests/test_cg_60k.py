"""The 60,000-feature speech cell on four chips (``speech.cg_60k_4chip``),
at a tiny size on four virtual CPU devices: a sound run reads
``correct``; a run whose chips leave their partial products unsummed
(the step's Z_i^T (Z_i P), or the right-hand side's Z_i^T Y) does not;
the cell's readers read what synthetic traces hold; and the row-block
control (``control_cg_rows.py``) solves as the one-device reference
does at full precision and reads ``correct`` false at ``high``."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

import tiny
from harness.served import Call
from harness.spec import load_module
from harness.trace import Interval, Trace

CELL = "speech.cg_60k_4chip"
CONFIG = "speech_60k_4chip"
#: four row blocks of 256 rows, and more features (512) than a block has
SIZES = {"rows": 1024, "feats": 16, "classes": 5, "rf_dim": 512}
METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "metrics")


def reader(name):
    return load_module(os.path.join(METRICS, name + ".py")).read


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.copy(str(tmp_path_factory.mktemp("bench")))
    tiny.edit_json(os.path.join(root, "bench", "configs", CONFIG + ".json"),
                   **SIZES)
    return root


# ---- runs ----------------------------------------------------------------
def test_sound_run_is_correct_on_four_devices(root):
    rc, out, err = tiny.run(root, CELL, trace=0)
    assert rc == 0, err
    res = tiny.result(out)
    assert res["correct"] and res["failed"] == 0, err
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"cg_s", "setup_s"}
    assert set(res["checks"]) == {"cg.residual"}


def test_traced_run_reports_the_cells_metrics(root):
    """The device's readings need a TPU's trace, which the CPU does not
    write: on the CPU the trace holds no chip, and the readers of the
    step, its sum and the residual read nothing."""
    rc, out, err = tiny.run(root, CELL, trace=1)
    assert rc == 0, err
    res = tiny.result(out)
    assert res["correct"], err
    assert set(res["metrics"]) == {"cg.device_idle.4chip", "cg.send_s.4chip",
                                   "cg.compiles.4chip"}
    assert res["metrics"]["cg.compiles.4chip"]["value"] >= 1


#: each chip's Z_i^T (Z_i P) in the step, never summed across the chips:
#: what the step goes on with is the first chip's partial product
STEP_UNSUMMED = """
import jax
from jax.sharding import PartitionSpec as P
from repro.core.backends import jax_backend
from repro.core.engine import make_engine_mesh
MESH = make_engine_mesh(4)
def local_only(x, p, use_pallas=False):
    return jax.shard_map(lambda xb, pb: xb.T @ (xb @ pb), mesh=MESH,
                         in_specs=(P("workers", None), P()), out_specs=P(),
                         check_vma=False)(x, p)
jax_backend.nm_ops.normal_matvec = local_only
"""

#: each chip's Z_i^T Y, never summed: the right-hand side is the first
#: chip's
RHS_UNSUMMED = """
import jax
from jax.sharding import PartitionSpec as P
from repro.core.backends import jax_backend
from repro.core.engine import make_engine_mesh
MESH = make_engine_mesh(4)
rows = P("workers", None)
jax_backend._cg_rhs = jax.jit(jax.shard_map(
    lambda xb, yb: xb.T @ yb, mesh=MESH, in_specs=(rows, rows),
    out_specs=P(), check_vma=False))
"""


@pytest.mark.parametrize("fault", [STEP_UNSUMMED, RHS_UNSUMMED],
                         ids=["step", "rhs"])
def test_partials_left_unsummed_are_not_correct(root, fault):
    rc, out, err = tiny.run(root, CELL, fault=fault)
    assert rc == 0, err
    res = tiny.result(out)
    assert res["correct"] is False, err
    assert res["failed"] == 0
    assert res["checks"]["cg.residual"]["value"] > \
        res["checks"]["cg.residual"]["limit"]


# ---- readers on synthetic traces -----------------------------------------
def _chip(shift: float):
    """One chip's programs in a [0, 10] s window: two CG steps in it
    (10 ms each) and one before it, and one residual (4 ms). The first
    step holds an all-reduce as an async start and done that overlap
    (2 ms together), the second a 1 ms reduce-scatter and a 1 ms
    all-gather; an all-reduce in the residual, and one outside any
    program, count nothing."""
    t = shift
    modules = [Interval("jit__lambda", -2, -1.99),
               Interval("jit__lambda", 1 + t, 1.01 + t),
               Interval("jit__lambda", 2 + t, 2.01 + t),
               Interval("jit__cg_residual", 3 + t, 3.004 + t)]
    ops = [Interval("all-reduce", -1.999, -1.995),
           Interval("fusion", 1 + t, 1.008 + t),
           Interval("all-reduce-start", 1.008 + t, 1.0095 + t),
           Interval("all-reduce-done", 1.009 + t, 1.010 + t),
           Interval("fusion.1", 2 + t, 2.008 + t),
           Interval("reduce-scatter.3", 2.008 + t, 2.009 + t),
           Interval("all-gather.1", 2.009 + t, 2.010 + t),
           Interval("all-reduce.2", 3.003 + t, 3.004 + t),
           Interval("all-reduce", 5, 5.1)]
    return modules, ops


def _run(chips, rows=4096, answered=1):
    pairs = [_chip(0.01 * i) for i in range(chips)]
    calls = [Call({"kind": "cg_job"}, 0.5, 4.0, answer={"send_s": 0.25})
             for _ in range(answered)]
    return SimpleNamespace(
        trace=Trace(modules=[m for m, _ in pairs], ops=[o for _, o in pairs],
                    spans=[Interval("bench.window", 0, 10)]),
        config={"rows": rows, "rf_dim": 512, "classes": 5},
        peaks={"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12},
        calls=calls + [Call({"kind": "cg_job"}, 4, 5, error="failed")])


@pytest.mark.parametrize("chips", [1, 4])
def test_reduce_ms_is_collective_time_per_step_per_chip(chips):
    # per chip: 2 ms of the first step (the union of start and done) and
    # 2 ms of the second, over two steps in the window
    assert reader("cg.reduce_ms")(_run(chips)) == pytest.approx(2.0)


def test_reduce_ms_reads_nothing_without_steps():
    assert reader("cg.reduce_ms")(SimpleNamespace(trace=None)) is None
    empty = Trace(modules=[[]], ops=[[]],
                  spans=[Interval("bench.window", 0, 10)])
    assert reader("cg.reduce_ms")(SimpleNamespace(trace=empty)) is None


def test_step_roofline_4chip_reads_each_chips_rows():
    """Four chips over n rows read as one chip over n / 4: each run of
    the step reads its chip's rows of Z once."""
    four = reader("cg.step_roofline.4chip")(_run(4, rows=4096))
    one = reader("cg.step_roofline")(_run(1, rows=1024))
    least = max(1024 * 512 * 4 / 819e9, 4 * 1024 * 512 * 5 / 197e12)
    assert four == pytest.approx(100.0 * least / 0.01)
    assert one == pytest.approx(four)
    # the one-chip formula over four chips holds each chip's run to all n
    # rows: four times each chip's share
    assert reader("cg.step_roofline")(_run(4, rows=4096)) == \
        pytest.approx(4 * four)
    assert reader("cg.step_roofline.4chip")(SimpleNamespace(trace=None)) \
        is None


@pytest.mark.parametrize("chips,answered", [(1, 1), (4, 1), (4, 2)])
def test_residual_ms_is_per_answered_job_per_chip(chips, answered):
    got = reader("cg.residual_ms")(_run(chips, answered=answered))
    assert got == pytest.approx(4.0 / answered)


def test_residual_ms_reads_nothing_without_an_answer_or_a_run():
    assert reader("cg.residual_ms")(_run(4, answered=0)) is None
    run = _run(4)
    run.trace.modules = [[m for m in chip if m.name != "jit__cg_residual"]
                         for chip in run.trace.modules]
    assert reader("cg.residual_ms")(run) is None


@pytest.mark.parametrize("name", ["cg.device_idle", "cg.send_s"])
def test_four_chip_names_read_as_the_one_chip_readers(name):
    run = _run(4)
    assert reader(name + ".4chip")(run) == reader(name)(run)
    assert reader(name + ".4chip")(run) is not None


# ---- the row-block control -----------------------------------------------
#: a problem at which the control's rounding shows past the program's, and
#: the limit there: at this size, seed and the cell's 100 steps on four
#: CPU devices, the program read 5.4e-6 and the row-block control at
#: ``high`` 1.0e-5 (the row-block reference at ``highest`` 3.5e-6; the
#: one-device control 3.4e-5), so the limit sits between them, 1.35x from
#: each (at the cell's size the chip separates them further: PERF.md)
CONTROL_SIZES = {"rows": 4096, "feats": 32, "classes": 8, "rf_dim": 512,
                 "limits": {"cg.residual": 7.3e-6}}
ITERS = 100
SEED = 2_500_000_003

CONTROL = r"""
import json, sys
import numpy as np
sys.path.insert(0, {bench!r})
import control_cg_rows as rows
from harness import data, reference
from harness.spec import Benchmark
cfg = Benchmark({root!r}).config({{"config": {config!r}}})
x, y = data.SpeechPool(cfg["rows"], cfg["feats"], cfg["classes"], {seed},
                       jobs=1).jobs[0]
args = (cfg["lam"], cfg["rf_dim"], cfg["bandwidth"], cfg["rf_seed"], {iters})
one = reference.cg_solve(x, y, *args)
four = rows.cg_solve(x, y, cfg, {iters}, 4, reference.HIGHEST)
high = rows.checks(cfg, {seed}, {iters}, 4, reference.HIGH)
print("RESULT " + json.dumps({{
    "w_rel": float(np.max(np.abs(four - one)) / np.max(np.abs(one))),
    "high": high}}))
"""


@pytest.fixture(scope="module")
def control_root(tmp_path_factory):
    root = tiny.copy(str(tmp_path_factory.mktemp("bench")))
    tiny.edit_json(os.path.join(root, "bench", "configs", CONFIG + ".json"),
                   **CONTROL_SIZES)
    return root


def test_row_block_control_solves_as_the_reference_and_reads_not_correct(
        control_root):
    bench = os.path.join(control_root, "bench")
    env = tiny.cpu_env(control_root, CELL)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [os.path.join(tiny.ROOT, "src"),
                      env.get("PYTHONPATH", "")]))
    p = subprocess.run(
        [sys.executable, "-c", CONTROL.format(
            bench=bench, root=control_root, config=CONFIG, seed=SEED,
            iters=ITERS)],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    [line] = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    out = json.loads(line[len("RESULT "):])
    # at full precision the row blocks solve as one device does, up to
    # the order of the sums, which moved W by 4.0e-5 of its largest entry
    assert out["w_rel"] <= 2e-4, out
    high = out["high"]["cg.residual"]
    assert high["value"] > high["limit"], out
    # the program, held to the same limit at the same size, is within it
    rc, run_out, err = tiny.run(control_root, CELL, seed=SEED)
    assert rc == 0, err
    res = tiny.result(run_out)
    assert res["correct"], err
    assert res["checks"]["cg.residual"]["limit"] == high["limit"]
