"""Run one benchmark cell once on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON result line last on standard output, and each number the
correctness comparison checked, beside its limit, last on standard error.
Exits non-zero, with no result, when JAX finds no TPU or fewer chips than
the cell asks for, or when a file the cell names is missing.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
