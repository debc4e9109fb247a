"""The control of the reachability cells: the plain reference closure over
only the first n/2 pivots, in the program's place.  Reachability is exact,
so there is no lower precision to compute it in; a closure that stops
halfway is the nearest wrong answer, and the comparison has to reject it.

    python3 bench/reach_control.py --workload reach16k.packed --seeds 1,2,3

runs, for each seed, the cell's set-up, a short window and its check with
the control standing in for the program (``reach_reference.pack_lanes``
then ``reach_reference.fw_words`` over pivots 0 .. n/2 - 1), at the cell's
own size, and prints the numbers compared as one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def control_for(cell: harness.Cell):
    """``(src, dst) -> words``, the edge lists as one tuple: the
    half-pivot reference closure."""
    from bench import reach_reference

    n = int(cell.config["n"])

    def control(edges):
        src, dst = edges
        return reach_reference.fw_words(
            reach_reference.pack_lanes(src, dst, n), n // 2)
    return control


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    cell = harness.find_cell(harness.load_spec(), args.workload)
    jax = harness.configure_jax()
    harness.chip_devices(jax, cell.chips)
    driver = harness.load_driver(cell)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        state = driver.setup(cell, seed, system=control_for(cell))
        win = driver.window(state, args.seconds, harness.Spans(), None)
        driver.release(state)
        checks = driver.check(state, win)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "control": "half_pivots",
            "attempted": win.attempted, "failed": win.failed,
            "rejected": not all(c.ok for c in checks),
            "checks": {c.name: c.value for c in checks},
            "wall_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
