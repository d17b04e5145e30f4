"""The benchmark's entry point.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from BENCHMARK.json (see
benchmark/README.md); this file only fixes the import path and notes
when the process started, which is where `setup_s` counts from.
"""
import time

T_START = time.perf_counter()

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], ROOT, T_START))
