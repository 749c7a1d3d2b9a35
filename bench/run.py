"""Benchmark entry point for one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  It needs a TPU: with none, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.  The last line of standard output is the result object; the
last lines of standard error are the compared numbers beside their
limits.
"""
import time

_T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import run_cell  # noqa: E402

if __name__ == "__main__":
    sys.exit(run_cell(sys.argv[1:], _T0))
