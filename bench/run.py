"""Run one cell of the benchmark on the chip this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cells are ``bench/workloads/<cell>.json``; see ``bench/harness.py``
for what a run prints. Exits 2, printing no result, where JAX finds fewer
TPU chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.run(sys.argv[1:], T_START))
