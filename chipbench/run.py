#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark and print its result as the last
line of standard output (see ``cb/bench.py``).

    python chipbench/run.py --workload qwen3-8b.sysprompt-chat --seed 7 \
        --seconds 30 --trace 0
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from cb import bench  # noqa: E402

if __name__ == "__main__":
    sys.exit(bench.main(sys.argv[1:], T_START))
