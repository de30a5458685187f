"""Run one cell traced and say who owns each idle gap of the chip.

    python3 bench/idle.py --workload NAME --seed N --seconds S [--out FILE]

The same run as ``bench/run.py ... --trace 1``, whose trace also keeps
the program's own host spans (``alchemist.*``, named in
``repro.core.tracing``). It prints the run's result line, then one JSON
line of its own (also written to ``--out``):

* ``readings``: the span metrics of the cell, per answered call;
* ``idle_by_span``: every idle second of the window, summed by the
  innermost span around each gap (``shares`` sums those by owner: the
  program's spans, the benchmark's fetch and send, and the labels that
  name no cause, ``host``, ``bench.call`` and ``bench.solve``);
* ``lag_s``: the median lag from a host span to its program's run on the
  device; when it is negative the device's clock reads behind the
  host's, and the gaps are labelled again on the corrected clock;
* ``span_s``: seconds per answered call of every program span.

With no TPU it exits 2, as ``run.py`` does. On a program without its own
spans the readings are empty.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from harness import device as devices  # noqa: E402
from harness import main as mains  # noqa: E402
from harness import spans  # noqa: E402
from harness import spec as specs  # noqa: E402
from harness import trace as traces  # noqa: E402
from repro.core import tracing  # noqa: E402

P = tracing.PREFIX

#: per traffic mix: its span metrics, each a sum over the window's calls,
#: and the host span and device program whose lag puts the clocks side by
#: side; every cell of the mix reads them
MIXES = {
    "svd": {
        "readings": {
            "svd.call_start_s": lambda t: sum(spans.time_to_next(
                t, P + tracing.CLIENT_SUBMIT, P + tracing.LANCZOS_MATVEC)),
            "svd.loop_idle_s": lambda t: spans.idle_within(
                t, P + tracing.LANCZOS),
        },
        "lag": (P + tracing.LANCZOS_MATVEC, "jit__gram_matvec"),
    },
    "cg": {
        "readings": {
            "cg.hash_s": lambda t: spans.span_seconds(
                t, P + tracing.CLIENT_HASH),
            "cg.stream_s": lambda t: spans.span_seconds(
                t, P + tracing.CLIENT_STREAM),
            "cg.ingest_s": lambda t: spans.span_seconds(
                t, P + tracing.server_frame("UPLOAD_COMMIT")),
            "cg.loop_idle_s": lambda t: spans.idle_within(
                t, P + tracing.CG),
        },
        "lag": (P + tracing.CG_STEP, "jit__lambda"),
    },
}

#: idle labels that name no cause
UNOWNED = ("host", "bench.call", "bench.solve")
#: the benchmark's own spans that do name one
OWNED = ("bench.fetch", "bench.send")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    return ap.parse_args(argv)


@contextlib.contextmanager
def program_spans():
    """``measure`` loads its trace with ``trace.load``, which keeps the
    host spans named with ``trace.SPAN_PREFIX``, the ``bench.*`` spans
    alone: while this holds, keep the program's too."""
    original = traces.SPAN_PREFIX
    traces.SPAN_PREFIX = (original, tracing.PREFIX)
    try:
        yield
    finally:
        traces.SPAN_PREFIX = original


def shares(idle: list) -> dict:
    total = sum(secs for _, secs in idle) or 1.0
    out = {"program": 0.0, "bench": 0.0, "unowned": 0.0}
    for name, secs in idle:
        if name.startswith(P):
            out["program"] += secs / total
        elif name in OWNED:
            out["bench"] += secs / total
        else:
            out["unowned"] += secs / total
    return out


def report(run, result: dict) -> dict:
    t = run.trace
    cell = MIXES.get(run.cell["traffic"], {"readings": {}, "lag": None})
    answered = sum(1 for c in run.calls if c.error is None) or 1
    out = {"workload": run.cell["name"], "seed": run.seed,
           "answered": answered,
           "readings": {name: fn(t) / answered
                        for name, fn in cell["readings"].items()}}
    idle = spans.idle_by_label(t)
    out["idle_s"] = sum(secs for _, secs in idle)
    lag = spans.device_lag(t, *cell["lag"]) if cell["lag"] else None
    out["lag_s"] = lag
    if lag is not None and lag < 0:
        out["idle_by_span_raw"], out["shares_raw"] = idle, shares(idle)
        idle = spans.idle_by_label(spans.shifted(t, -lag))
    out["idle_by_span"], out["shares"] = idle, shares(idle)
    names = sorted({s.name for s in t.spans if s.name.startswith(P)})
    out["span_s"] = {n: spans.span_seconds(t, n) / answered for n in names}
    metrics = result.get("metrics", {})
    if "cg.send_s" in metrics and out["readings"]:
        parts = sum(out["readings"][k] for k in
                    ("cg.hash_s", "cg.stream_s", "cg.ingest_s"))
        out["send_parts_over_send_s"] = parts / metrics["cg.send_s"]["value"]
    return out


def main(argv=None, t_start=None) -> int:
    t_start = T_START if t_start is None else t_start
    args = parse(argv)
    bench = specs.Benchmark()
    try:
        run = mains.prepare(bench, args.workload, args.seed, args.seconds)
    except (specs.SpecError, devices.NoChip) as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 2
    with program_spans():
        result = mains.measure(bench, run, traced=True, t_start=t_start)
    print(json.dumps(result), flush=True)
    out = report(run, result)
    for name, secs in out["idle_by_span"]:
        print(f"idle {secs:12.6f} s  {name}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
