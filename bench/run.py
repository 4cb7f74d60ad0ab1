"""Run one benchmark cell once, on the chips of this machine.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout; ``bench/harness.py`` says
how a run goes.  Exits non-zero, printing no result, without a TPU, with
fewer chips than the cell asks for, or without the program (``src/``).
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402

import harness  # noqa: E402

if __name__ == "__main__":
    harness.add_paths()
    sys.exit(harness.main(t_start=T_START))
