"""The harness finds what it runs by name, and refuses to measure without
a TPU or on a chip missing from the peak table."""
import hashlib
import json
import os
import subprocess
import sys

import tiny


def digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = tiny.copy(str(tmp_path))
    before = digests(root)
    bench = os.path.join(root, "bench")
    # a configuration, a traffic mix and a per-layer metric, as new files
    with open(os.path.join(bench, "configs", "ocean.json")) as f:
        cfg = json.load(f)
    cfg.update(name="ocean_narrow", cols=128, rank=4, oversample=8)
    with open(os.path.join(bench, "configs", "ocean_narrow.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "svd_sample.json"), "w") as f:
        json.dump({"loop": "closed", "check": 2,
                   "requests": [{"kind": "svd", "share": 1.0}]}, f)
    with open(os.path.join(bench, "metrics", "svd.answered.py"), "w") as f:
        f.write("def read(run):\n"
                "    return sum(c.answer is not None for c in run.calls)\n")
    # ... and the entries that name them
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "ocean_narrow", "source": "test",
                            "file": "bench/configs/ocean_narrow.json",
                            "reduced": ["rows", "cols"], "why": "test"})
    spec["workloads"].append({"name": "ocean_narrow.svd_sample",
                              "config": "ocean_narrow",
                              "traffic": "svd_sample", "chips": 1,
                              "why": "test"})
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if m["name"] in ("svd_s", "svd.matvecs"):
                m["workloads"].append("ocean_narrow.svd_sample")
    spec["per_layer"].append({"name": "svd.answered", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "client + wire", "moves": "svd_s",
                              "workloads": ["ocean_narrow.svd_sample"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    rc, out, err = tiny.run(root, "ocean_narrow.svd_sample", trace=1)
    assert rc == 0, err
    res = tiny.result(out)
    assert res["correct"], err
    assert res["metrics"]["svd.answered"]["value"] == res["attempted"] > 0
    assert res["metrics"]["svd.matvecs"]["value"] == 12    # rank 4 + 8
    assert list(res)[-1] == "checks"
    rc, out, err = tiny.run(root, "ocean_narrow.svd_sample", trace=0)
    res = tiny.result(out)
    assert set(res["metrics"]) == {"svd_s", "setup_s"}
    # nothing that was there changed
    after = digests(root)
    assert {k: after[k] for k in before} == before


def test_four_chip_cell_reports_its_own_metrics(tmp_path):
    """On four virtual devices the cell reports its own end-to-end metric
    and the per-layer metrics that move it; the device's readings need a
    TPU's trace, which the CPU does not write."""
    root = tiny.copy(str(tmp_path))
    rc, out, err = tiny.run(root, "ocean.svd_4chip", trace=0)
    assert rc == 0, err
    res = tiny.result(out)
    assert res["correct"] and res["device"]["count"] == 4, err
    assert set(res["metrics"]) == {"svd_s.4chip", "setup_s"}
    rc, out, err = tiny.run(root, "ocean.svd_4chip", trace=1)
    res = tiny.result(out)
    assert res["correct"], err
    assert set(res["metrics"]) == {
        "svd.device_idle.4chip", "svd.matvecs.4chip", "svd.compiles.4chip",
        "svd.fused_matvecs.4chip", "svd.fetch_s.4chip"}
    assert res["metrics"]["svd.matvecs.4chip"]["value"] == 24  # 8 + 16


def run_bench(root, env_extra, prelude="", workload="ocean.svd"):
    code = (prelude + "import runpy, sys\n"
            f"sys.argv = ['run.py', '--workload', {workload!r}, '--seed', "
            f"'1', '--seconds', '1', '--trace', '0']\n"
            f"runpy.run_path({os.path.join(root, 'bench', 'run.py')!r}, "
            "run_name='__main__')\n")
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300,
                          env=dict(os.environ, **env_extra))


def test_no_tpu_fails_and_prints_no_result(tmp_path):
    root = tiny.copy(str(tmp_path))
    p = run_bench(root, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_unknown_device_kind_fails(tmp_path):
    root = tiny.copy(str(tmp_path))
    fake = ("import jax\n"
            "class Chip:\n"
            "    platform = 'tpu'\n"
            "    device_kind = 'TPU v99'\n"
            "jax.devices = lambda *a, **k: [Chip()]\n")
    p = run_bench(root, {"JAX_PLATFORMS": "cpu"}, prelude=fake)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "TPU v99" in p.stderr and "peak table" in p.stderr


def test_too_few_chips_fails(tmp_path):
    root = tiny.copy(str(tmp_path))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for cell in spec["workloads"]:
        cell["chips"] = 4
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    fake = ("import jax\n"
            "class Chip:\n"
            "    platform = 'tpu'\n"
            "    device_kind = 'TPU v5 lite'\n"
            "jax.devices = lambda *a, **k: [Chip()]\n")
    p = run_bench(root, {"JAX_PLATFORMS": "cpu"}, prelude=fake)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "needs 4 chips" in p.stderr


def test_four_chip_cell_on_one_chip_fails(tmp_path):
    root = tiny.copy(str(tmp_path))
    fake = ("import jax\n"
            "class Chip:\n"
            "    platform = 'tpu'\n"
            "    device_kind = 'TPU v5 lite'\n"
            "jax.devices = lambda *a, **k: [Chip()]\n")
    p = run_bench(root, {"JAX_PLATFORMS": "cpu"}, prelude=fake,
                  workload="ocean.svd_4chip")
    assert p.returncode == 2
    assert p.stdout == ""
    assert "needs 4 chips, JAX found 1" in p.stderr


def test_without_the_program_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under its
    paths has no program to measure: the run fails and prints no result,
    even past the look for a chip."""
    root = tiny.copy(str(tmp_path))
    rc, out, err = tiny.run(root, "ocean.svd", src=str(tmp_path / "none"))
    assert rc != 0
    assert out == ""
    assert "repro" in err
