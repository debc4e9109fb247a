"""Closed-loop bulk reachability: many directed graphs closed together.

Set-up draws ``graphs`` Graph500 Kronecker digraphs of n = 2**SCALE
vertices on the device from the seed, ``edgefactor * n`` directed edge
tuples each (``kronecker_edges``), and compiles the packer and the packed
solve ahead of time.  Each closure of the window is the program's whole
path from edge lists: ``ApspEngine.pack_edges`` (one word table, 32 graphs
a word) then ``ApspEngine.solve`` on the words.  The window, its
``bench.closure`` spans and ``closure_s`` are the dense driver's
(``bench.drivers.closure``).

After each closure the harness keeps the word rows of ``check_rows``
sources (chosen from the seed).  Once the window has closed, every lane of
those rows is compared with a breadth-first search over that graph's edge
list (``bench.reach_reference``).  ``reach_mismatch`` counts the (graph,
source, target) bits that differ; reachability is exact, so its limit is 0.

``system``, where given, replaces the program: ``(src, dst) -> words``,
called with the edge lists as one tuple.
"""
from __future__ import annotations

import numpy as np

from bench.drivers.closure import (  # noqa: F401  (the driver's interface)
    State,
    end_to_end,
    release,
    seed_key,
    window,
)
from bench.harness import Check, Window, phase

# Bits of the checked rows that may differ from the reference.
REACH_MISMATCH = 0


def kronecker_edges(key, scale: int, edges: int, initiator):
    """One Graph500 Kronecker edge list: (src, dst), ``edges`` int32 each.

    The specification's generator (section 3): for each of ``scale`` bits
    the edge picks a quadrant of the initiator [[A, B], [C, D]], then the
    vertex labels are permuted at random, then the edge list.  Self-loops
    and repeated edges stay, as the generator makes them.
    """
    import jax
    import jax.numpy as jnp

    a, b, c, _ = (float(x) for x in initiator)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    k_bits, k_label, k_order = jax.random.split(key, 3)

    def bit(i, ij):
        ki, kj = jax.random.split(jax.random.fold_in(k_bits, i))
        ii = jax.random.uniform(ki, (edges,)) > ab
        jj = jax.random.uniform(kj, (edges,)) > jnp.where(ii, c_norm, a_norm)
        return (ij[0] | (ii.astype(jnp.int32) << i),
                ij[1] | (jj.astype(jnp.int32) << i))

    zero = jnp.zeros((edges,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, bit, (zero, zero))
    label = jax.random.permutation(k_label, 1 << scale).astype(jnp.int32)
    order = jax.random.permutation(k_order, edges)
    return label[src][order], label[dst][order]


def make_edges(seed: int, n: int, graphs: int, edgefactor: int, initiator):
    """(src, dst): (graphs, edgefactor * n) int32 each, on the device."""
    import jax

    scale = n.bit_length() - 1
    if n != 1 << scale:
        raise ValueError(f"a Kronecker graph has 2**SCALE vertices, not {n}")
    keys = jax.random.split(seed_key(seed), graphs)
    make = jax.jit(jax.vmap(
        lambda k: kronecker_edges(k, scale, edgefactor * n, initiator)))
    return make(keys)


def _take(edges, words, rows):
    return words[:, rows]


def setup(cell, seed: int, system=None) -> State:
    import jax
    import jax.numpy as jnp

    cfg, traffic = cell.config, cell.traffic
    n, graphs = int(cfg["n"]), int(cfg["graphs"])
    with phase("edges"):
        edges = make_edges(seed, n, graphs, int(cfg["edgefactor"]),
                           cfg["initiator"])
        jax.block_until_ready(edges)
    rng = np.random.default_rng(np.random.SeedSequence(seed))
    k = int(traffic["check_rows"])
    rows = np.stack([np.sort(rng.choice(n, k, replace=False))
                     for _ in range(int(traffic["max_closures"]))])
    rows = rows.astype(np.int32)
    take = jax.jit(_take)
    engine = None
    if system is None:
        from repro.apsp import ApspEngine

        engine = ApspEngine(method=cfg["method"], semiring=cfg["semiring"],
                            packed=bool(cfg["packed"]))
        with phase("warm-up"):
            # The packer runs once; the solve is compiled without running,
            # and the engine's unpadding slice runs on the packed words.
            words = engine.pack_edges(*edges, n)
            entry = engine.plan_for(n, words.shape[0], dtype=words.dtype)
            spec = jax.ShapeDtypeStruct(words.shape, words.dtype)
            entry.runner.lower(spec).compile()
            jax.block_until_ready(words[..., :n, :n])

        def solve(e):
            return engine.solve(engine.pack_edges(*e, n)).dist
    else:
        words = jnp.zeros((-(-graphs // 32), n, n), jnp.int32)
        solve = system
    jax.block_until_ready(take(edges, words, rows[0]))
    del words
    return State(w=edges, solve=solve, rows=rows, take=take, engine=engine)


def check(state: State, win: Window) -> list[Check]:
    import jax.numpy as jnp

    from bench import reach_reference

    src, dst = state.w
    bad = 0
    for got, rows in zip(state.kept, state.rows):
        n = got.shape[-1]
        want = reach_reference.reach_rows(src, dst, jnp.asarray(rows), n)
        bits = reach_reference.unpack_rows(got, src.shape[0])
        bad += int(jnp.sum(bits != want))
    return [Check("reach_mismatch", float(bad), REACH_MISMATCH)]
