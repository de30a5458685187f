"""Benchmark harness driver — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run table2     # one table
"""
import sys

from benchmarks import (
    backend_fusion,
    cache_amortization,
    chain_pipelining,
    compile_warmup,
    fig3_weak_scaling,
    kernel_bench,
    multiclient_throughput,
    roofline_table,
    table2_cg,
    table3_transfer,
    table4_cg_features,
    table5_svd,
)
from repro.core import compilecache

ALL = {
    "table2": table2_cg.run,
    "table3": table3_transfer.run,
    "table4": table4_cg_features.run,
    "table5": table5_svd.run,
    "fig3": fig3_weak_scaling.run,
    "kernels": kernel_bench.run,
    "roofline": roofline_table.run,
    # smoke-sized here; the standalone script exposes the full sweep
    "multiclient": lambda: multiclient_throughput.run(
        [1, 2, 4], duration_s=2.0, k=8, workers=2),
    # the same tenant mix over real TCP (core/server.py + SocketBridge)
    "multiclient_socket": lambda: multiclient_throughput.run(
        [1, 2, 4], duration_s=2.0, k=8, workers=2, bridge="socket"),
    "cache": lambda: cache_amortization.run(
        3, (512, 128), k=8, smoke=False),
    "cache_socket": lambda: cache_amortization.run(
        3, (512, 128), k=8, smoke=False, bridge="socket"),
    "chain": lambda: chain_pipelining.run([4, 16, 64]),
    # smoke-sized here; the standalone script exposes the full sweep
    "fusion": lambda: (backend_fusion.run([4, 16]),
                       backend_fusion.run_routine_table(dim=96)),
    # machine-readable output tracked across PRs
    "compile_warmup": lambda: compile_warmup.run(
        json_path="BENCH_compile_warmup.json"),
    # multi-tenant QoS: light-tenant p99 vs solo baseline, asserted
    "qos_fairness": lambda: multiclient_throughput.run_qos(
        duration_s=2.0, k=8, workers=2, smoke=True,
        json_path="BENCH_qos_fairness.json"),
}


def main() -> None:
    which = sys.argv[1:] or list(ALL)
    compilecache.enable_persistent_cache()
    print("name,us_per_call,derived")
    for name in which:
        ALL[name]()


if __name__ == "__main__":
    main()
