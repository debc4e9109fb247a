"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload dense16k.closure --seed 7 --seconds 45 --trace 0

Run from the repository root (or a checkout of it).  Exits 3 without
printing a result when JAX finds no TPU, fewer chips than the cell asks
for, or a chip that ``bench/peaks.json`` does not list.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
