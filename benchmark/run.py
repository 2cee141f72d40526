#!/usr/bin/env python3
"""Entry point of the benchmark: see benchmark/harness.py."""

import time

T_START = time.perf_counter()

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
