#!/usr/bin/env python3
"""One run of one benchmark cell: ``python3 bench/run.py --workload
<cell> --seed <n> --seconds <s> --trace <0|1>``.  See
``bench/benchlib/harness.py``."""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
