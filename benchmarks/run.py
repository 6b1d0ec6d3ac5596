#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, in this process, on this machine.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Sets up, warms up, measures for --seconds, checks correctness outside the
measured window and prints the result as one JSON object on the last line of
standard output.  Without a TPU (or with fewer chips than the cell asks for)
it exits 2 and prints no result line.  See benchmarks/README.md.
"""

import time

T_START = time.perf_counter()  # set-up counts from here

import os  # noqa: E402
import sys  # noqa: E402

# the checkout, for `import lightgbm_tpu`; benchmarks/ itself is sys.path[0]
sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
