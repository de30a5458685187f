"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

The cells, configurations and metrics are listed in ``BENCHMARK.json``
at the root of the checkout; see ``bench/harness/main.py`` for what a
run prints.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
