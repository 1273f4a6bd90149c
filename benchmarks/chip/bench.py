#!/usr/bin/env python3
"""Run one cell of the chip benchmark described by ``BENCHMARK.json``.

Usage (from the root of a checkout, on a machine with the cell's chips):

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Prints diagnostics and the compared numbers with their limits on standard
error, and one JSON result line as the last line of standard output.
Exits non-zero, with no result line, where JAX finds no TPU of a kind the
peaks table knows, or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
