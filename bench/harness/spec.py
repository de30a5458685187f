"""Finding things by name. ``BENCHMARK.json`` lists the cells and metrics;
everything else is a file named after what it holds:

* a configuration: the ``file`` its entry in ``BENCHMARK.json`` names;
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a request kind the mix sends: ``bench/kinds/<kind>.py`` (``Kind``);
* a metric: ``bench/metrics/<name>.py`` (``read(run)``, which returns a
  number, or None when the run holds nothing to read). A metric that a
  cell of its own reads under another name, because there it moves
  another end-to-end metric, takes the first one's reader
  (``reader_of``).

Adding a cell, configuration, mix, kind or metric adds files and entries
and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    """The benchmark's files do not describe the cell asked for."""


def _json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"no file {os.path.relpath(path, ROOT)}") from None


def load_module(path: str):
    if not os.path.isfile(path):
        raise SpecError(f"no file {os.path.relpath(path, ROOT)}")
    name = "bench_" + re.sub(r"\W", "_", os.path.relpath(path, BENCH_DIR))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def reader_of(metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return load_module(os.path.join(BENCH_DIR, "metrics",
                                    metric + ".py")).read


class Benchmark:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "bench")
        self.spec = _json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> dict:
        for c in self.spec["workloads"]:
            if c["name"] == name:
                return c
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, cell: dict) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == cell["config"]:
                return _json(os.path.join(self.root, c["file"]))
        raise SpecError(f"no config {cell['config']!r} in BENCHMARK.json")

    def traffic(self, cell: dict) -> dict:
        return _json(os.path.join(self.bench_dir, "traffic",
                                  cell["traffic"] + ".json"))

    def kind(self, name: str):
        return load_module(os.path.join(self.bench_dir, "kinds",
                                        name + ".py")).Kind

    def metrics(self, cell: dict, traced: bool) -> list[dict]:
        """The metrics a run of ``cell`` reports: its end-to-end metrics,
        or with tracing its per-layer metrics."""
        group = self.spec["per_layer" if traced else "end_to_end"]
        return [m for m in group
                if cell["name"] in m.get("workloads", [cell["name"]])]

    def reader(self, metric: str):
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        metric + ".py")).read
