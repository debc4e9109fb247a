"""Bulk reachability from edge lists: the edge-list packer, the lane-by-lane
``pack_reachability``, and the packed engine path they feed
(``ApspEngine(semiring="or_and", packed=True)``)."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apsp import (
    ApspEngine,
    pack_edges,
    pack_reachability,
    unpack_reachability,
)
from repro.apsp import engine as engine_mod
from repro.core.floyd_warshall import fw_naive
from repro.core.semiring import OR_AND, PACK_LANES


def _edges(rng, B, n, E):
    """(B, E) random edge lists, each with repeats and a self-loop."""
    src = rng.integers(0, n, (B, E)).astype(np.int32)
    dst = rng.integers(0, n, (B, E)).astype(np.int32)
    src[:, : E // 8], dst[:, : E // 8] = src[:, -(E // 8):], dst[:, -(E // 8):]
    dst[:, E // 8] = src[:, E // 8]
    return src, dst


def _dense(src, dst, n):
    """(B, n, n) float32 0/1 adjacency with every diagonal set."""
    out = np.zeros((src.shape[0], n, n), np.float32)
    for g in range(src.shape[0]):
        out[g, src[g], dst[g]] = 1.0
        np.fill_diagonal(out[g], 1.0)
    return out


@pytest.mark.parametrize("B", [1, 5, 32, 33])
def test_pack_edges_matches_pack_reachability(B):
    rng = np.random.default_rng(B)
    n, E = 40, 64
    src, dst = _edges(rng, B, n, E)
    want = np.asarray(pack_reachability(_dense(src, dst, n)))
    got = np.asarray(jax.jit(pack_edges, static_argnames="n")(src, dst, n=n))
    assert got.dtype == np.int32 and got.shape == (-(-B // PACK_LANES), n, n)
    assert np.array_equal(got, want)
    # An endpoint outside [0, n) drops its edge, whichever end it is.
    bad_src, bad_dst = src.copy(), dst.copy()
    bad_src[:, 0], bad_dst[:, 1] = -1, n
    assert np.array_equal(
        np.asarray(pack_edges(bad_src, bad_dst, n)),
        np.asarray(pack_reachability(_dense(src[:, 2:], dst[:, 2:], n))))


def _pack_reachability_whole(w):
    """``pack_reachability`` as it was: all 32 shifted lanes of a word made
    at once, then OR-reduced."""
    arr = jnp.asarray(w)
    if arr.ndim == 2:
        arr = arr[None]
    B, n, _ = arr.shape
    G = -(-B // PACK_LANES)
    bits = (arr != 0).astype(jnp.uint32)
    if G * PACK_LANES != B:
        bits = jnp.pad(bits, ((0, G * PACK_LANES - B), (0, 0), (0, 0)))
    shifts = jnp.arange(PACK_LANES, dtype=jnp.uint32)[None, :, None, None]
    words = jnp.bitwise_or.reduce(
        bits.reshape(G, PACK_LANES, n, n) << shifts, axis=1)
    return jax.lax.bitcast_convert_type(words, jnp.int32)


@pytest.mark.parametrize("B", [1, 7, 32, 33, 70])
def test_pack_reachability_lane_by_lane_is_unchanged(B):
    rng = np.random.default_rng(100 + B)
    w = rng.uniform(size=(B, 12, 12)).astype(np.float32)
    w[w < 0.6] = 0.0  # nonzero entries are edges, whatever their value
    for x in (w, w[0]):
        assert np.array_equal(np.asarray(pack_reachability(x)),
                              np.asarray(_pack_reachability_whole(x)))


def _bfs_reference():
    path = Path(__file__).resolve().parents[1] / "bench" / "reach_reference.py"
    spec = importlib.util.spec_from_file_location("reach_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("method,interpret", [("auto", None), ("fused", True)],
                         ids=["default", "fused_interpret"])
def test_packed_engine_closes_every_lane(method, interpret):
    """32 graphs of n=256 packed from edge lists and closed in one word
    table equal, lane by lane, the unpacked or_and closure of each graph
    and a breadth-first search over its edge list."""
    rng = np.random.default_rng(11)
    B, n, E = PACK_LANES, 256, 320
    src, dst = _edges(rng, B, n, E)
    eng = ApspEngine(semiring="or_and", packed=True, method=method,
                     interpret=interpret)
    res = eng.solve(eng.pack_edges(src, dst, n))
    assert res.method == ("fused" if interpret else "blocked")
    got = np.asarray(unpack_reachability(res.dist, count=B)) == 1
    want = np.asarray(fw_naive(jnp.asarray(_dense(src, dst, n)),
                               semiring=OR_AND)) == 1
    assert np.array_equal(got, want)
    assert not want.all() and want[:, ~np.eye(n, dtype=bool)].any()
    rows = np.arange(0, n, 9, dtype=np.int32)
    bfs = np.asarray(_bfs_reference().reach_rows(src, dst, rows, n))
    assert np.array_equal(got[:, rows], bfs)


def test_pack_counters_and_span(monkeypatch):
    names = []
    real = jax.profiler.TraceAnnotation

    def record(name, **kw):
        names.append(name)
        return real(name, **kw)

    monkeypatch.setattr(engine_mod.jax.profiler, "TraceAnnotation", record)
    rng = np.random.default_rng(3)
    eng = ApspEngine(semiring="or_and", packed=True)
    s, d = _edges(rng, 5, 64, 48)
    words = eng.pack_edges(s, d, 64)
    eng.pack_edges(s[0], d[0], 64)
    assert names == ["apsp.pack", "apsp.pack"]
    st = eng.stats
    assert (st.packs, st.graphs_packed, st.edges_packed) == (2, 6, 6 * 48)
    eng.solve(words)
    assert names[-1] == "apsp.solve"
    assert st.graphs_solved == PACK_LANES  # one word: 32 lanes
    with pytest.raises(ValueError, match="packed"):
        ApspEngine(semiring="or_and").pack_edges(s, d, 64)
