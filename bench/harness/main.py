"""One run of one cell: set up, measure for ``--seconds``, check the
answers against the reference, print the result line.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``), and last ``checks``: each number compared, with its
limit. The same numbers end standard error. Without a TPU, with fewer
chips than the cell asks for, or on a chip missing from the peak table,
the run prints no result and exits 2.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import glob
import json
import os
import resource
import shutil
import sys
import tempfile
import time

from harness import device as devices
from harness import spec as specs
from harness import stats, traffic

#: the most requests a closed loop prepares; the window ends first
CLOSED_LIMIT = 10_000


@dataclasses.dataclass
class Run:
    """What a run measured: what the metric readers read."""
    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    device: dict
    peaks: dict
    served: object = None
    spans: object = None
    setup_s: float = 0.0
    calls: list = dataclasses.field(default_factory=list)
    trace: object = None


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare(bench: specs.Benchmark, workload: str, seed: int,
            seconds: float) -> Run:
    """The cell's files and the chip it needs; raises ``SpecError`` or
    ``NoChip`` before anything is measured."""
    cell = bench.cell(workload)
    config, mix = bench.config(cell), bench.traffic(cell)
    table = devices.peaks_table(bench.bench_dir)
    # the compile cache stays inside the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(bench.root,
                                                           ".jax_cache")
    dev = devices.require_chip(cell["chips"], table)
    return Run(cell, config, mix, seed, seconds, dev,
               devices.peaks_for(dev["kind"], table))


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    bench = specs.Benchmark()
    try:
        run = prepare(bench, args.workload, args.seed, args.seconds)
    except (specs.SpecError, devices.NoChip) as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 2
    result = measure(bench, run, traced=bool(args.trace), t_start=t_start)
    checks = result.pop("checks")
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


def measure(bench: specs.Benchmark, run: Run, traced: bool,
            t_start: float) -> dict:
    sys.path.insert(0, os.path.join(bench.root, "src"))
    from repro.core import compilecache

    from harness import served as serving

    compilecache.enable_persistent_cache()
    run.spans = serving.Spans()
    run.served = serving.Served(run.cell["chips"])
    t_served = time.perf_counter()
    mix = run.mix
    try:
        kinds = {}
        for entry in mix["requests"]:
            if entry["kind"] not in kinds:
                kinds[entry["kind"]] = bench.kind(entry["kind"])(
                    run, entry["kind"], entry)
        requests = traffic.closed_loop(mix, run.seed,
                                       mix.get("pool", CLOSED_LIMIT))
        t_warm = time.perf_counter()
        for k in kinds.values():
            k.warm()
        run.setup_s = time.perf_counter() - t_start
        print(f"bench: set-up {run.setup_s:.3f} s: to the served path "
              f"{t_served - t_start:.3f} s, data and sessions "
              f"{t_warm - t_served:.3f} s, warm-up "
              f"{t_start + run.setup_s - t_warm:.3f} s", file=sys.stderr)

        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") \
            if traced else None
        if traced:
            _start_trace(trace_dir)
        usage = resource.getrusage(resource.RUSAGE_SELF)
        spans_before = len(run.spans.items)
        with run.spans("window"):
            run.calls = serving.closed_loop(
                requests, lambda req: kinds[req["kind"]].do(req),
                run.seconds, run.spans)
        if traced:
            import jax

            jax.profiler.stop_trace()
        _print_host(run.spans.items[spans_before:], usage)
        memory_peak = devices.memory_peak_bytes(run.cell["chips"])
    finally:
        run.served.stop()
    run.served = None
    gc.collect()            # the engine's arrays go before the reference's

    calls = run.calls
    result = {"correct": False, "attempted": len(calls),
              "failed": sum(c.error is not None for c in calls)}
    result["device"] = dict(run.device, memory_peak_bytes=memory_peak)
    if traced:
        from harness import trace as traces

        run.trace = traces.load(_trace_file(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = traces.window(run.trace)
        result["device"].update(busy_s=traces.busy_seconds(run.trace),
                                window_s=hi - lo)
        result["breakdown"] = {"device_ops": traces.top_ops(run.trace),
                               "idle_gaps": traces.idle_gaps(run.trace)}

    metrics = {}
    for m in bench.metrics(run.cell, traced):
        value = bench.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics

    in_order = [c.end - c.start for c in calls if c.error is None]
    done = sorted(in_order)
    if done:
        print(f"bench: {len(done)} answered in "
              f"{done[0]:.4f} / {done[len(done) // 2]:.4f} / "
              f"{done[-1]:.4f} s (min / median / max); in order: "
              f"{' '.join(f'{t:.3f}' for t in in_order)}", file=sys.stderr)
    checks = check(run, kinds)
    result["correct"] = bool(run.calls and result["failed"] == 0
                             and within(checks))
    result["checks"] = checks
    return result


def judge(numbers: dict, limits: dict) -> dict:
    """Each number beside the configuration's limit for it."""
    return {key: {"value": value, "limit": limits[key]}
            for key, value in numbers.items()}


def within(checks: dict) -> bool:
    return bool(checks) and all(c["value"] <= c["limit"]
                                for c in checks.values())


def check(run: Run, kinds: dict) -> dict:
    """Each number compared, with its limit: the worst over the answers
    that the mix's sample draws, per request kind."""
    out = {}
    for name, kind in kinds.items():
        answered = [c.answer for c in run.calls
                    if c.answer is not None and c.request["kind"] == name]
        if not answered:
            continue
        picked = traffic.sample(run.mix, len(answered), run.seed)
        out.update(judge(kind.numbers([answered[i] for i in picked]),
                         run.config["limits"]))
    return out


def _print_host(spans: list, before) -> None:
    """Where the window's host time went, to standard error: the median
    seconds of each ``bench.*`` span per call, and the process's CPU
    seconds and involuntary context switches over the window (a window
    slowed by other load on the host's cores shows more of these, not
    more CPU seconds)."""
    after = resource.getrusage(resource.RUSAGE_SELF)
    by_name = {}
    for name, t0, t1 in spans:
        by_name.setdefault(name, []).append(t1 - t0)
    parts = " ".join(f"{n[len('bench.'):]} {stats.percentile(v, 50):.4f}"
                     for n, v in sorted(by_name.items())
                     if n not in ("bench.window",))
    window = by_name.get("bench.window", [0.0])[-1]
    cpu = (after.ru_utime - before.ru_utime) + (after.ru_stime
                                                 - before.ru_stime)
    print(f"bench: median s per span: {parts}; window {window:.3f} s, "
          f"cpu {cpu:.3f} s, involuntary switches "
          f"{after.ru_nivcsw - before.ru_nivcsw}, load "
          f"{' '.join(f'{x:.2f}' for x in os.getloadavg())}",
          file=sys.stderr)


def _start_trace(trace_dir: str) -> None:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def _trace_file(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return found[0]
