"""The plain references agree with each other and catch what they must."""
import _paths  # noqa: F401
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.drivers import closure


def _graph(n=48, seed=0):
    """A sparse digraph with whole weights 1..999 and unreachable pairs."""
    rng = np.random.default_rng(seed)
    w = rng.integers(1, 1000, (n, n)).astype(np.float32)
    w[rng.random((n, n)) > 0.08] = np.inf
    w[:, 0] = np.inf  # no edge into vertex 0
    np.fill_diagonal(w, 0.0)
    return w


def test_fw_closure_matches_dijkstra():
    w = _graph()
    d = np.asarray(reference.fw_closure(jnp.asarray(w)))
    for s in range(w.shape[0]):
        assert np.array_equal(d[s], reference.dijkstra(w, s))


def test_fw_successors_walk_shortest_paths():
    w = _graph(seed=1)
    d, s = reference.fw_closure(jnp.asarray(w), successors=True)
    d = np.asarray(d)
    assert np.isinf(d).any(), "the graph has unreachable pairs"
    rows = jnp.arange(w.shape[0], dtype=jnp.int32)
    got = np.asarray(closure.walk_costs(jnp.asarray(w), s, rows))
    assert np.array_equal(got, d)


def test_walk_costs_catch_a_bad_hop():
    w = _graph(seed=2)
    d, s = reference.fw_closure(jnp.asarray(w), successors=True)
    d = np.asarray(d)
    i, j = np.argwhere(np.isfinite(d) & ~np.eye(len(d), dtype=bool))[0]
    rows = jnp.asarray([i], jnp.int32)
    for bad in (i, -1):  # a hop to itself, a hop out of the table
        got = np.asarray(closure.walk_costs(jnp.asarray(w),
                                            s.at[i, j].set(bad), rows))
        assert np.isinf(got[0, j])
        assert closure.max_rel_err(got, d[[i]]) == np.inf


def test_sssp_rows_matches_dijkstra_on_dense_weights():
    w = np.asarray(closure.make_weights(3, 256, 1.0, 10.0))
    rows = np.array([0, 17, 255], np.int32)
    got = np.asarray(reference.sssp_rows(jnp.asarray(w), rows, chunk=64))
    for r, s in zip(got, rows):
        want = reference.dijkstra(w, int(s))
        assert np.allclose(r, want, rtol=1e-6, atol=0)


def test_max_rel_err():
    a = np.array([[0.0, 2.0, np.inf]])
    assert closure.max_rel_err(a, a) == 0.0
    b = np.array([[0.0, 2.0 * (1 + 1e-3), np.inf]])
    assert closure.max_rel_err(b, a) == pytest.approx(1e-3)
    assert closure.max_rel_err(np.array([[0.0, 2.0, 5.0]]), a) == np.inf


def test_bfloat16_closure_misses_the_limit():
    """The dense control's precision reads far above MAX_REL_ERR."""
    w = closure.make_weights(4, 256, 1.0, 10.0)
    rows = np.arange(0, 256, 16, dtype=np.int32)
    want = np.asarray(reference.sssp_rows(w, rows, chunk=64))
    low = np.asarray(reference.fw_closure(w, dtype="bfloat16")
                     .astype(jnp.float32))[rows]
    prog = np.asarray(reference.fw_closure(w))[rows]
    assert closure.max_rel_err(prog, want) <= closure.MAX_REL_ERR
    assert closure.max_rel_err(low, want) > 10 * closure.MAX_REL_ERR


def test_weights_are_seeded():
    a = np.asarray(closure.make_weights(2**40 + 1, 64, 1.0, 10.0))
    b = np.asarray(closure.make_weights(2**40 + 1, 64, 1.0, 10.0))
    c = np.asarray(closure.make_weights(2**40 + 2, 64, 1.0, 10.0))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.all(np.diag(a) == 0)
    off = a[~np.eye(64, dtype=bool)]
    assert off.min() >= 1.0 and off.max() < 10.0
