#!/usr/bin/env python3
"""The benchmark's one command (see BENCHMARK.json):

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the run's result as the last line of stdout; the numbers it
compared, each with its limit, are the last lines of stderr.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

from harness import runner  # noqa: E402

if __name__ == "__main__":
    sys.exit(runner.main(t_start=T_START))
