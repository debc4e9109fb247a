"""Plain references for the comparison that decides ``correct``.

Nothing here imports the program under test.  Shortest paths are min-plus
closures with +inf for a missing edge:

    fw_closure    textbook Floyd-Warshall, one full-matrix relaxation per
                  pivot k, optionally with next-hop (successor) tables;
                  ``dtype`` sets the precision it computes in
    sssp_rows     Bellman-Ford from a few sources at once, on the device
    dijkstra      dense Dijkstra on the host in float64 (the tests' witness)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("successors", "dtype"))
def fw_closure(w, *, successors: bool = False, dtype="float32"):
    """Floyd-Warshall of an (n, n) weight matrix, computed in ``dtype``.

    succ[i, j] is the first hop of a shortest i->j path: j for an edge,
    i on the diagonal, -1 with no path; it moves to succ[i, k] only on a
    strict improvement through pivot k.  Returns dist, or (dist, succ).
    """
    d = w.astype(dtype)
    n = d.shape[0]
    if not successors:
        def body(k, d):
            return jnp.minimum(d, d[:, k, None] + d[None, k, :])

        return jax.lax.fori_loop(0, n, body, d)
    idx = jnp.arange(n, dtype=jnp.int32)
    eye = idx[:, None] == idx[None, :]
    succ = jnp.where(jnp.isfinite(d) & ~eye,
                     jnp.broadcast_to(idx[None, :], (n, n)), -1)
    succ = jnp.where(eye, idx[:, None], succ)

    def body_s(k, c):
        d, s = c
        cand = d[:, k, None] + d[None, k, :]
        better = cand < d
        return jnp.where(better, cand, d), jnp.where(better, s[:, k, None], s)

    return jax.lax.fori_loop(0, n, body_s, (d, succ))


@functools.partial(jax.jit, static_argnames=("chunk",))
def sssp_rows(w, sources, *, chunk: int = 128):
    """Shortest-path distances from each of ``sources`` (k rows of n), by
    Bellman-Ford relaxation d[r, j] <- min_i d[r, i] + w[i, j] until no
    entry changes.  w: (n, n) with a zero diagonal and no negative cycle;
    n a multiple of ``chunk`` (the rows of w read per step)."""
    n = w.shape[0]
    chunk = min(chunk, n)
    steps = n // chunk

    def relax(d):
        def body(c, acc):
            wi = jax.lax.dynamic_slice_in_dim(w, c * chunk, chunk, 0)
            di = jax.lax.dynamic_slice_in_dim(d, c * chunk, chunk, 1)
            return jnp.minimum(acc, jnp.min(di[:, :, None] + wi[None], axis=1))

        return jax.lax.fori_loop(0, steps, body, d)

    def cond(c):
        d, prev, it = c
        return (it < n) & jnp.any(d != prev)

    def step(c):
        d, _, it = c
        return relax(d), d, it + 1

    d0 = w[sources]
    d, _, _ = jax.lax.while_loop(cond, step, (relax(d0), d0, 1))
    return d


def dijkstra(w: np.ndarray, src: int) -> np.ndarray:
    """Distances from src over a dense (n, n) matrix, in float64."""
    n = w.shape[0]
    dist = np.full(n, np.inf)
    dist[src] = 0.0
    open_ = np.full(n, np.inf)
    open_[src] = 0.0
    done = np.zeros(n, bool)
    for _ in range(n):
        u = int(np.argmin(open_))
        du = open_[u]
        if not np.isfinite(du):
            break
        done[u] = True
        open_[u] = np.inf
        cand = du + w[u].astype(np.float64)
        better = (cand < dist) & ~done
        dist[better] = cand[better]
        open_[better] = cand[better]
    return dist
