"""The controls: the plain reference in the program's place, computed one
precision below the configuration's float32 (bfloat16).  Each cell's
comparison has to reject its control; its readings set the upper end of
each limit (PERF.md).

    python3 bench/controls.py --workload dense16k.closure --seeds 1,2,3

runs, for each seed, the cell's set-up, a short window and its check with
the control standing in for the program, at the cell's own size and
load, and prints the numbers compared as one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402

LOW = "bfloat16"


def control_for(cell: harness.Cell):
    """``w -> closure``: textbook Floyd-Warshall in bfloat16, with next-hop
    tables where the cell's traffic asks for them."""
    import jax.numpy as jnp

    from bench import reference

    if cell.traffic["kind"] != "closure":
        raise KeyError(f"no control for traffic kind {cell.traffic['kind']!r}")
    if not cell.traffic.get("successors", False):
        return lambda w: reference.fw_closure(w, dtype=LOW).astype(jnp.float32)

    def control(w):
        d, s = reference.fw_closure(w, successors=True, dtype=LOW)
        return d.astype(jnp.float32), s
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
            "workload": cell.name, "seed": seed, "control": LOW,
            "attempted": win.attempted, "failed": win.failed,
            "rejected": not all(c.ok for c in checks),
            "checks": {c.name: c.value for c in checks},
            "wall_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
