"""Measure the VPU's float32 min-plus rate with a microkernel.

    python3 bench/vpu_probe.py

A Pallas kernel keeps three (rows, 128) float32 tiles in vector registers
and runs L steps of a = min(a, b + x); b = min(b, a + x): 4 operations per
element per step, no memory traffic inside the loop.  The best rate over
a few tile heights and unroll factors (steps per loop turn) is printed as JSON; it checks the
estimate in ``bench/peaks.json``.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402

STEPS = 1 << 20


def rate(rows: int, unroll: int, reps: int = 5) -> float:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        x = x_ref[...]

        def body(i, c):
            a, b = c
            for _ in range(unroll):  # Mosaic loops do not unroll
                a = jnp.minimum(a, b + x)
                b = jnp.minimum(b, a + x)
            return a, b

        a, b = jax.lax.fori_loop(0, STEPS // unroll, body, (x, x + 1.0))
        o_ref[...] = a + b

    f = jax.jit(lambda x: pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x))
    x = jnp.ones((rows, 128), jnp.float32)
    f(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        y = f(x)
    y.block_until_ready()
    return 4.0 * rows * 128 * STEPS * reps / (time.perf_counter() - t0)


def main() -> int:
    jax = harness.configure_jax()
    dev = harness.chip_devices(jax, 1)[0]
    rows_seen = {}
    for rows in (8, 16, 32, 64, 128):
        for unroll in (4, 8, 16):
            rows_seen[f"{rows}x{unroll}"] = rate(rows, unroll)
    best = max(rows_seen, key=rows_seen.get)
    print(json.dumps({"device_kind": dev.device_kind,
                      "best": best, "ops_per_s": rows_seen[best],
                      "all": rows_seen}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
