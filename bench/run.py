#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip and print its result line.

    python3 bench/run.py --workload sift1m.batch --seed 7 --seconds 20 --trace 0

It exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for, or where the program is not beside it. See
``bench/harness.py`` for what a run does and ``PERF.md`` for the cells.
"""
import time

T_START = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# the benchmark's modules import as ``bench.*``; the program from ``src``
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    sys.path[0] = ROOT
else:
    sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))
# the TPU runtime's logs stay inside the checkout
os.environ.setdefault("TPU_LOG_DIR", os.path.join(BENCH, ".cache", "tpu_logs"))

if __name__ == "__main__":
    from bench import harness

    sys.exit(harness.main(t_start=T_START))
