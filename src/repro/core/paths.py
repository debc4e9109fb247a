"""Shortest-path reconstruction (successor matrix) for APSP.

The paper computes distances only; real deployments (routing tables — one of
the paper's motivating applications) need next-hops.  We track a successor
matrix alongside the distance matrix: succ[i,j] = next vertex after i on the
shortest i→j path.  The FW relaxation updates it wherever the distance
*strictly* improves.

Two implementations:

  * ``fw_with_successors`` — the naive oracle: one relaxation sweep per k
    (n full-matrix passes, the memory-bound regime).
  * ``fw_blocked_with_successors`` — the blocked 3-phase algorithm carrying
    the successor matrix through every phase.  succ[i,j] ← succ[i,k] when
    pivot k improves (i,j), and k always lives in the pivot block, so the
    successor operand of each phase is exactly the phase's "A-side" block:
    the diag succ tile (phases 1/2-row), the panel's own succ columns
    (phase 2-col), or the succ column band (phase 3).  Same fori-loop round
    structure as ``fw_blocked`` — O(1) trace size in n.

Successor tracking doubles HBM traffic, which is why it is a separate entry
point rather than a flag on the hot kernel.  ``repro.apsp.solve(...,
successors=True)`` routes to the blocked version.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _init_successors(w: jax.Array) -> jax.Array:
    """succ[...,i,j] = j where an edge exists, i on the diagonal, else -1.

    Batch-rank-agnostic: (n,n) and (B,n,n) inputs get elementwise-identical
    initialization (broadcast over the leading dims).
    """
    n = w.shape[-1]
    eye = jnp.eye(n, dtype=bool)
    idx = jnp.arange(n, dtype=jnp.int32)
    has_edge = jnp.isfinite(w) & ~eye
    succ = jnp.where(has_edge, jnp.broadcast_to(idx[None, :], w.shape), -1)
    return jnp.where(eye, idx[:, None], succ)


@jax.jit
def fw_with_successors(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """FW returning (dist, succ).  succ[i,j] = -1 where no path exists."""
    n = w.shape[0]
    succ = _init_successors(w)

    def body(k, carry):
        w, succ = carry
        cand = w[:, k, None] + w[k, None, :]
        better = cand < w
        w = jnp.where(better, cand, w)
        succ = jnp.where(better, succ[:, k, None], succ)
        return w, succ

    return jax.lax.fori_loop(0, n, body, (w, succ))


def _relax_with_succ(k, w, succ, a, a_succ, b):
    """(w, succ) ⊕= step k: cand = a[:,k] + b[k,:]; succ ← a_succ[:,k]."""
    cand = a[:, k, None] + b[k, None, :]
    better = cand < w
    return jnp.where(better, cand, w), jnp.where(better, a_succ[:, k, None], succ)


@functools.partial(jax.jit, static_argnames=("block_size",))
def fw_blocked_with_successors(
    w: jax.Array, *, block_size: int = 128
) -> tuple[jax.Array, jax.Array]:
    """Blocked 3-phase FW carrying a successor matrix (min-plus only).

    n must be a multiple of block_size (``repro.apsp.solve`` pads).  Updates
    use strict improvement (<), matching ``fw_with_successors``; on graphs
    without ties the two produce identical successor matrices.
    """
    n = w.shape[0]
    s = block_size
    if n % s:
        raise ValueError(f"n={n} not a multiple of block_size={s}")
    succ = _init_successors(w)
    return jax.lax.fori_loop(
        0, n // s,
        lambda b, c: blocked_round_with_successors(*c, b, block_size=s),
        (w, succ),
    )


def blocked_round_with_successors(w, succ, b, *, block_size: int):
    """Round b of the blocked 3-phase FW on an (n, n) (dist, succ) pair —
    the phase-by-phase round the fused successor kernel bit-matches."""
    n = w.shape[0]
    s = block_size
    o = b * s

    # Phase 1 — diagonal tile; i, j, k all in the pivot block.
    diag = jax.lax.dynamic_slice(w, (o, o), (s, s))
    dsucc = jax.lax.dynamic_slice(succ, (o, o), (s, s))

    def p1(k, c):
        t, ts = c
        t, ts = _relax_with_succ(k, t, ts, t, ts, t)
        return t, ts

    diag, dsucc = jax.lax.fori_loop(0, s, p1, (diag, dsucc))
    w = jax.lax.dynamic_update_slice(w, diag, (o, o))
    succ = jax.lax.dynamic_update_slice(succ, dsucc, (o, o))

    # Phase 2 — row band (s, n): rows i live in the pivot block, so
    # succ[i,k] is the closed diag succ tile.  Row k of the band feeds
    # later iterations → k sequential.
    rband = jax.lax.dynamic_slice(w, (o, 0), (s, n))
    rsucc = jax.lax.dynamic_slice(succ, (o, 0), (s, n))

    def p2r(k, c):
        p, ps = c
        p, ps = _relax_with_succ(k, p, ps, diag, dsucc, p)
        return p, ps

    rband, rsucc = jax.lax.fori_loop(0, s, p2r, (rband, rsucc))
    rband = jax.lax.dynamic_update_slice(rband, diag, (0, o))
    rsucc = jax.lax.dynamic_update_slice(rsucc, dsucc, (0, o))

    # Phase 2 — column band (n, s): columns k live in the pivot block,
    # so succ[i,k] is the band's own (evolving) succ column k.
    cband = jax.lax.dynamic_slice(w, (0, o), (n, s))
    csucc = jax.lax.dynamic_slice(succ, (0, o), (n, s))

    def p2c(k, c):
        p, ps = c
        p, ps = _relax_with_succ(k, p, ps, p, ps, diag)
        return p, ps

    cband, csucc = jax.lax.fori_loop(0, s, p2c, (cband, csucc))
    cband = jax.lax.dynamic_update_slice(cband, diag, (o, 0))
    csucc = jax.lax.dynamic_update_slice(csucc, dsucc, (o, 0))

    w = jax.lax.dynamic_update_slice(w, rband, (o, 0))
    succ = jax.lax.dynamic_update_slice(succ, rsucc, (o, 0))
    w = jax.lax.dynamic_update_slice(w, cband, (0, o))
    succ = jax.lax.dynamic_update_slice(succ, csucc, (0, o))

    # Phase 3 — whole matrix vs the closed bands; succ[i,k] is the succ
    # column band.  Re-relaxing the pivot bands is a no-op under strict
    # improvement (they are already closed under k ∈ block).
    def p3(k, c):
        wm, sm = c
        return _relax_with_succ(k, wm, sm, cband, csucc, rband)

    w, succ = jax.lax.fori_loop(0, s, p3, (w, succ))
    return w, succ


def extract_path(succ: np.ndarray, src: int, dst: int, max_len: int | None = None) -> list[int]:
    """Walk the successor matrix from src to dst (host-side)."""
    succ = np.asarray(succ)
    if succ[src, dst] < 0:
        return []
    path = [src]
    cur = src
    limit = max_len or succ.shape[0] + 1
    while cur != dst and len(path) <= limit:
        cur = int(succ[cur, dst])
        if cur < 0:
            return []
        path.append(cur)
    return path


def _lift_distances(a: np.ndarray) -> np.ndarray:
    """Lowered-storage tables → float64 with real ±inf sentinels.

    The serving layer may cache distance tables in their storage lowering
    (min_plus_i16 saturating int16 with ±32767 sentinels, bf16 weights);
    the host-side walk below needs IEEE semantics — int16 "infinity" is
    finite to numpy and wraps under +, and bf16 is an ml_dtypes extension
    type some numpy builds can't reduce over.  Map sentinels to ±inf and
    compute in float64.
    """
    a = np.asarray(a)
    if a.dtype.kind in "iu":
        from repro.core.semiring import I16_INF, I16_NINF

        out = a.astype(np.float64)
        out[a == I16_INF] = np.inf
        out[a == I16_NINF] = -np.inf
        return out
    if a.dtype.kind == "f" and a.dtype.itemsize >= 4:
        return a
    return a.astype(np.float64)  # bf16 / f16


def extract_path_from_dist(
    w: np.ndarray, dist: np.ndarray, src: int, dst: int,
    *, max_len: int | None = None,
) -> list[int]:
    """Reconstruct a shortest path from the distance matrix alone (host).

    For serving paths when no successor table exists (the distributed
    refresh returns distances only): from u, the next hop is the neighbor v
    minimizing w[u, v] + dist[v, dst] — by Bellman optimality that sum
    equals dist[u, dst] on a shortest path.  O(path length · n) numpy; the
    argmin (rather than an exact-equality test) tolerates the float
    re-association between the closure's reduction order and this sum.
    Accepts lowered-storage tables (int16 saturating sentinels, bf16) —
    they lift to float for the walk.  Returns [] when dst is unreachable
    or no path materializes within ``max_len`` hops.
    """
    w = _lift_distances(w)
    dist = _lift_distances(dist)
    if not np.isfinite(dist[src, dst]):
        return []
    path = [src]
    cur = src
    visited = np.zeros(dist.shape[0], dtype=bool)
    visited[src] = True
    limit = max_len or dist.shape[0] + 1
    while cur != dst and len(path) <= limit:
        cand = w[cur, :] + dist[:, dst]
        # A shortest path never needs to revisit a vertex; masking visited
        # ones keeps zero-weight cycles (and self-loops) from trapping the
        # greedy walk in an A↔B oscillation.
        cand[visited] = np.inf
        nxt = int(np.argmin(cand))
        if not np.isfinite(cand[nxt]):
            return []
        path.append(nxt)
        visited[nxt] = True
        cur = nxt
    return path if cur == dst else []


def path_cost(w: np.ndarray, path: list[int]) -> float:
    """Sum of edge weights along ``path`` in the original adjacency matrix."""
    w = _lift_distances(w)
    if not path:
        return float("inf")
    return float(sum(w[a, b] for a, b in zip(path, path[1:])))
