"""Closed-loop closures of one dense seeded graph.

Set-up makes the weight matrix on the device from the seed (uniform
[lo, hi) float32, zero diagonal) and compiles the engine's plan for its
shape ahead of time, without running a closure.  The window runs
``ApspEngine.solve`` back to back; it starts closures while fewer than
``seconds`` have passed and ends when the last one started has finished.
With ``"successors": true`` in the traffic each closure also returns its
next-hop table (``solve(w, successors=True)``).

After each closure the harness keeps ``check_rows`` of its rows (chosen
from the seed); with next hops it also walks the table from each of those
sources to every vertex and keeps the cost of each walk.  Once the window
has closed both are compared with a Bellman-Ford of the same source rows
(``reference.sssp_rows``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import numpy as np

from bench.harness import Check, Window, phase

# Largest relative gap between a checked closure entry and the reference
# (both float32, path sums in different orders).  Set between the readings
# on a TPU v5e at n=16384 (PERF.md): the program reads 0.0 on every seed,
# the control (Floyd-Warshall in bfloat16) at least 7.69e-3.
MAX_REL_ERR = 1e-4
# Largest relative gap between the cost of a walk along the next-hop table
# and the reference distance.  Set between the readings on a TPU v5e at
# n=16384 (PERF.md): the program reads 0.0, the control (Floyd-Warshall
# with next hops in bfloat16) at least 1.01e-2.
PATH_REL_ERR = 1e-4
LIMITS = {"max_rel_err": MAX_REL_ERR, "path_rel_err": PATH_REL_ERR}


@dataclasses.dataclass
class State:
    w: Any
    solve: Callable
    rows: np.ndarray      # (max closures, check_rows) source rows to keep
    take: Callable        # (w, closure output, rows) -> {check: rows}
    engine: Any = None
    kept: list = dataclasses.field(default_factory=list)
    closures: list = dataclasses.field(default_factory=list)


def seed_key(seed: int):
    import jax

    # Any whole seed (the driver's exceed 32 bits) folds to one 32-bit word.
    word = int(np.random.SeedSequence(seed).generate_state(1)[0])
    return jax.random.key(word)


def make_weights(seed: int, n: int, lo: float, hi: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        w = jax.random.uniform(key, (n, n), jnp.float32, lo, hi)
        return jnp.where(jnp.eye(n, dtype=bool), jnp.float32(0), w)

    return make(seed_key(seed))


def walk_costs(w, succ, rows):
    """Cost of following ``succ`` from each source in ``rows`` to every
    vertex: (len(rows), n), +inf where a walk leaves the table or has not
    arrived after n hops."""
    import jax
    import jax.numpy as jnp

    n = succ.shape[-1]
    cols = jnp.arange(n, dtype=jnp.int32)[None, :]
    cur = jnp.broadcast_to(rows.astype(jnp.int32)[:, None], (len(rows), n))
    cost = jnp.zeros(cur.shape, w.dtype)

    def cond(c):
        cur, _, hops = c
        return (hops < n) & jnp.any(cur != cols)

    def step(c):
        cur, cost, hops = c
        moving = cur != cols
        nxt = succ[cur, cols]
        ok = (nxt >= 0) & (nxt < n)
        nxt = jnp.clip(nxt, 0, n - 1)
        hop = jnp.where(ok, w[cur, nxt], jnp.inf)
        cost = jnp.where(moving, cost + hop, cost)
        # A walk that leaves the table stops where it is, at +inf.
        cur = jnp.where(moving, jnp.where(ok, nxt, cols), cur)
        return cur, cost, hops + 1

    cur, cost, _ = jax.lax.while_loop(cond, step, (cur, cost, 0))
    return jnp.where(cur == cols, cost, jnp.inf)


def _take(w, out, rows):
    if isinstance(out, tuple):
        dist, succ = out
        return {"max_rel_err": dist[rows],
                "path_rel_err": walk_costs(w, succ, rows)}
    return {"max_rel_err": out[rows]}


def setup(cell, seed: int, system=None) -> State:
    import jax

    cfg, traffic = cell.config, cell.traffic
    n = int(cfg["n"])
    successors = bool(traffic.get("successors", False))
    with phase("weights"):
        w = make_weights(seed, n, float(cfg["w_lo"]), float(cfg["w_hi"]))
        w.block_until_ready()
    rng = np.random.default_rng(np.random.SeedSequence(seed))
    k = int(traffic["check_rows"])
    rows = np.stack([np.sort(rng.choice(n, k, replace=False))
                     for _ in range(int(traffic["max_closures"]))])
    rows = rows.astype(np.int32)
    take = jax.jit(_take)
    direct = None
    if successors:
        # Next hops straight to each target: every walk ends after one hop.
        direct = jax.jit(lambda: jax.numpy.broadcast_to(
            jax.numpy.arange(n, dtype=jax.numpy.int32), (n, n)))()
        jax.block_until_ready(take(w, (w, direct), rows[0]))
    else:
        jax.block_until_ready(take(w, w, rows[0]))
    engine = None
    if system is None:
        from repro.apsp import ApspEngine

        engine = ApspEngine(method=cfg["method"])
        with phase("warm-up"):
            warm(engine, w, direct)
        if successors:
            def solve(x):
                r = engine.solve(x, successors=True)
                return r.dist, r.succ
        else:
            solve = lambda x: engine.solve(x).dist  # noqa: E731
    else:
        solve = system
    jax.block_until_ready(w)
    return State(w=w, solve=solve, rows=rows, take=take, engine=engine)


def warm(engine, w, succ=None) -> None:
    """Compile the plan ``engine.solve(w, successors=succ is not None)``
    runs, without running it.

    The plan's runner is compiled ahead of time for w's shape; the small
    eager ops around it (batch axis, unpadding, the negative-cycle test)
    are run once on w itself, and on ``succ``, an int32 table of w's
    shape, for the next hops.  Where the runner cannot be compiled ahead
    of time, one closure runs instead.
    """
    import jax

    n = w.shape[-1]
    successors = succ is not None
    entry = engine.plan_for(n, 1, dtype=w.dtype, successors=successors)
    lower = getattr(entry.runner, "lower", None)
    if lower is None:
        jax.block_until_ready(engine.solve(w, successors=successors).dist)
        return
    lower(jax.ShapeDtypeStruct((1, n, n), w.dtype)).compile()
    from repro.apsp.api import negative_cycle_mask

    x = w[None][..., :n, :n][0]
    np.asarray(negative_cycle_mask(x))
    if successors:
        jax.block_until_ready(succ[None][..., :n, :n][0])


def window(state: State, seconds: float, spans, tracer) -> Window:
    import jax

    if tracer is not None:
        tracer.start()
    t0 = time.perf_counter()
    kept = []
    while True:
        i = len(state.closures)
        with spans.span("bench.closure"):
            a = time.perf_counter()
            out = state.solve(state.w)
            jax.block_until_ready(out)
            b = time.perf_counter()
        state.closures.append((a, b))
        if i < len(state.rows):
            kept.append(state.take(state.w, out, state.rows[i]))
        del out
        if b - t0 >= seconds or i + 1 >= len(state.rows):
            break
    if tracer is not None:
        tracer.stop()
    state.kept = kept
    return Window(attempted=len(state.closures), failed=0,
                  data={"t0": t0, "t1": state.closures[-1][1]})


def end_to_end(state: State, win: Window) -> dict:
    return {"closure_s": (win.data["t1"] - win.data["t0"])
            / len(state.closures)}


def release(state: State) -> None:
    state.engine = None
    state.solve = None


def max_rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest |got - want| / want over the entries (0 where both are 0)."""
    got = got.astype(np.float64)
    want = want.astype(np.float64)
    with np.errstate(invalid="ignore"):
        gap = np.abs(got - want)
        rel = np.where(got == want, 0.0,
                       gap / np.maximum(np.abs(want), 1e-30))
    rel = np.where(np.isnan(rel), np.inf, rel)
    return float(rel.max()) if rel.size else 0.0


def check(state: State, win: Window) -> list[Check]:
    from bench import reference

    worst: dict[str, float] = {}
    for got, rows in zip(state.kept, state.rows):
        want = np.asarray(reference.sssp_rows(state.w, rows))
        for name, rows_got in got.items():
            worst[name] = max(worst.get(name, 0.0),
                              max_rel_err(np.asarray(rows_got), want))
    return [Check(name, value, LIMITS[name]) for name, value in worst.items()]
