#!/usr/bin/env python3
"""The benchmark's one command: one run of one cell of BENCHMARK.json.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints progress and the compared numbers on standard error, and one
JSON object as the last line of standard output.  Refuses (exit code 2,
no result) off a TPU or off the compiled Pallas lane.
"""
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)

from limsbench.cell import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
