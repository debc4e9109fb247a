"""Plain references for the reachability cells' comparison.

Nothing here imports the program under test.  A packed table holds 32
graphs per int32 word: bit g % 32 of word g // 32 at [i, j] says "j is
reachable from i in graph g" (every vertex reaches itself).

    reach_rows    breadth-first search over each graph's edge list from a
                  few sources, one graph after another, on the device
    unpack_rows   the bits of some rows of a packed table, one per graph
    pack_lanes    a packed table from edge lists, one graph at a time
    fw_words      textbook Floyd-Warshall over a packed table, OR of ANDs,
                  over its first ``pivots`` pivots
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LANES = 32  # graphs per int32 word


def _reach_one(src, dst, sources, n: int):
    """(R, n) bool: which vertices each of ``sources`` reaches in one graph.

    Level by level: a vertex joins a source's set when one of its in-edges
    leaves the set.  The edges are sorted by head once, so the in-edges of
    v are one run [start[v], start[v + 1]) and "any in-edge from the set"
    is a difference of running counts, with no scatter."""
    order = jnp.argsort(dst)
    tail, head = src[order], dst[order]
    start = jnp.searchsorted(head, jnp.arange(n + 1), side="left")
    r = sources.shape[0]
    seen = jnp.zeros((n, r), bool).at[sources, jnp.arange(r)].set(True)

    def step(c):
        seen, _, level = c
        runs = jnp.cumsum(seen[tail].astype(jnp.int32), axis=0)
        runs = jnp.concatenate([jnp.zeros((1, r), jnp.int32), runs])
        fresh = seen | (runs[start[1:]] > runs[start[:-1]])
        return fresh, jnp.any(fresh != seen), level + 1

    seen, _, _ = jax.lax.while_loop(
        lambda c: c[1] & (c[2] < n), step, (seen, True, 0))
    return seen.T


@functools.partial(jax.jit, static_argnames="n")
def reach_rows(src, dst, sources, n: int):
    """(B, R, n) bool: reachability from ``sources`` in each of the B
    graphs whose edges are ``src[g] → dst[g]`` ((B, E) int32)."""
    return jax.lax.map(lambda e: _reach_one(e[0], e[1], sources, n),
                       (src, dst))


@functools.partial(jax.jit, static_argnames="graphs")
def unpack_rows(words, graphs: int):
    """(G, R, n) int32 rows of a packed table -> (graphs, R, n) bool."""
    g = jnp.arange(graphs)
    return ((words[g // LANES] >> (g % LANES)[:, None, None]) & 1) == 1


@functools.partial(jax.jit, static_argnames="n")
def pack_lanes(src, dst, n: int):
    """(ceil(B/32), n, n) int32 packed table of B edge lists, each graph's
    diagonal set, made one graph at a time from an (n, n) bool plane."""
    b = src.shape[0]
    idx = jnp.arange(n)

    def lane(g, words):
        plane = jnp.zeros((n, n), bool).at[src[g], dst[g]].set(True)
        plane = plane.at[idx, idx].set(True)
        bit = plane.astype(jnp.uint32) << (g % LANES).astype(jnp.uint32)
        return words.at[g // LANES].set(words[g // LANES] | bit)

    words = jax.lax.fori_loop(
        0, b, lane, jnp.zeros((-(-b // LANES), n, n), jnp.uint32))
    return jax.lax.bitcast_convert_type(words, jnp.int32)


@functools.partial(jax.jit, static_argnames="pivots")
def fw_words(words, pivots: int):
    """Floyd-Warshall over (G, n, n) packed words, pivots 0 .. pivots-1:
    r[i, j] |= r[i, k] & r[k, j] in all 32 lanes at once."""
    def body(k, r):
        col = jax.lax.dynamic_slice_in_dim(r, k, 1, axis=2)
        row = jax.lax.dynamic_slice_in_dim(r, k, 1, axis=1)
        return r | (col & row)

    return jax.lax.fori_loop(0, pivots, body, words)
