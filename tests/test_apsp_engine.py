"""ApspEngine acceptance surface: bucketing, caching, serving.

  * ``solve_many`` over ragged graph sizes matches per-graph ``solve``
    bitwise on all 5 semirings (property-tested via hypothesis when
    installed) and across dtypes;
  * the plan/executable cache: a repeated (n, B, dtype) key re-plans
    nothing and — the real guarantee — re-traces nothing;
  * bucketing groups by padded shape and preserves input order;
  * the serving layer (``serve.engine.RoutingEngine``) refreshes many
    graphs in one bucketed batched solve and answers path queries from the
    cached successor tables.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, st

from repro.apsp import ApspEngine, NegativeCycleError, solve
from repro.core.graph import grid_graph, random_digraph
from repro.core.paths import path_cost
from repro.core.semiring import SEMIRINGS


def _graph_for(semiring_name: str, n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if semiring_name == "or_and":
        w = (rng.uniform(size=(n, n)) < 0.1).astype(np.float32)
        np.fill_diagonal(w, 1.0)
        return w
    if semiring_name == "plus_mul":
        return rng.uniform(0.0, 0.01, size=(n, n)).astype(np.float32)
    w = rng.uniform(1.0, 10.0, size=(n, n)).astype(np.float32)
    np.fill_diagonal(w, 0.0)
    return w


# --------------------------------------------------- ragged == per-graph
@pytest.mark.parametrize("name", sorted(SEMIRINGS))
def test_solve_many_ragged_matches_per_graph_all_semirings(name):
    """The tentpole acceptance: bucketed batched == per-graph, bitwise."""
    eng = ApspEngine(semiring=name, validate=False)
    sizes = (12, 40, 70, 40, 90)  # two buckets share a padded shape
    graphs = [_graph_for(name, n, seed=n + i) for i, n in enumerate(sizes)]
    results = eng.solve_many(graphs)
    assert [r.n for r in results] == list(sizes)
    for g, r in zip(graphs, results):
        single = solve(g, semiring=name, validate=False)
        assert r.method == single.method
        assert np.array_equal(np.asarray(r.dist), np.asarray(single.dist)), (
            f"{name}: solve_many diverged from per-graph solve at n={r.n}"
        )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_solve_many_fused_dtypes_bitwise(dtype):
    eng = ApspEngine(method="fused", block_size=32, validate=False)
    graphs = [jnp.asarray(random_digraph(n, density=0.6, seed=n), dtype)
              for n in (40, 70, 40)]
    results = eng.solve_many(graphs)
    for g, r in zip(graphs, results):
        single = solve(g, method="fused", block_size=32, validate=False)
        assert r.dist.dtype == dtype
        assert np.array_equal(
            np.asarray(r.dist, np.float32), np.asarray(single.dist, np.float32)
        )


@settings(max_examples=5, deadline=None)
@given(st.lists(st.sampled_from([4, 9, 17, 33, 40, 66]), min_size=1, max_size=5))
def test_solve_many_property_ragged_sizes(sizes):
    """Property: ANY ragged size mix buckets to per-graph-identical output."""
    eng = ApspEngine(validate=False)
    graphs = [random_digraph(n, density=0.5, seed=n) for n in sizes]
    results = eng.solve_many(graphs)
    assert [r.n for r in results] == list(sizes)
    for g, r in zip(graphs, results):
        single = solve(g, validate=False)
        assert np.array_equal(np.asarray(r.dist), np.asarray(single.dist))


def test_solve_many_successors_match_blocked():
    eng = ApspEngine(method="fused", block_size=16, validate=False)
    graphs = [random_digraph(n, density=0.5, seed=n) for n in (30, 50, 30)]
    results = eng.solve_many(graphs, successors=True)
    for g, r in zip(graphs, results):
        ref = solve(g, method="blocked", block_size=16, successors=True,
                    validate=False)
        assert np.array_equal(np.asarray(r.dist), np.asarray(ref.dist))
        assert np.array_equal(np.asarray(r.succ), np.asarray(ref.succ))


# ----------------------------------------------------------- cache behavior
def test_cache_hit_no_recompile_on_repeated_key():
    """The no-recompile guarantee: a repeated (n, B, dtype) key must not
    re-plan (stats.misses flat) and must not re-trace (traces flat)."""
    eng = ApspEngine(method="fused", block_size=32, validate=False)
    wb = np.stack([random_digraph(70, density=0.5, seed=i) for i in range(4)])
    eng.solve(wb)
    assert eng.stats.misses == 1 and eng.cache_size == 1
    entry = next(iter(eng._cache.values()))
    assert entry.traces == 1  # compiled exactly once
    for _ in range(3):
        eng.solve(wb)
    assert eng.stats.misses == 1, "repeated key re-planned"
    assert entry.traces == 1, "repeated key re-traced/re-compiled"
    assert eng.stats.hits == 3

    # A different batch size is a different executable → one more miss.
    eng.solve(wb[:2])
    assert eng.stats.misses == 2 and eng.cache_size == 2


def test_cache_key_separates_successors_and_dtype():
    eng = ApspEngine(method="fused", block_size=32, validate=False)
    w = random_digraph(40, density=0.5, seed=1)
    eng.solve(w)
    eng.solve(w, successors=True)
    eng.solve(jnp.asarray(w, jnp.bfloat16))
    assert eng.cache_size == 3


def test_plan_for_models_fused_round():
    eng = ApspEngine(method="fused", block_size=32, validate=False)
    entry = eng.plan_for(100, batch=16)
    assert entry.key.n_padded == 128 and entry.key.batch == 16
    assert entry.key.batch_block and 16 % entry.key.batch_block == 0
    assert entry.vmem_bytes and entry.hbm_bytes_per_round
    # plan_for is itself cached
    assert eng.plan_for(100, batch=16) is entry


@pytest.mark.parametrize(
    "backend,variant,want_bk",
    [("tpu", "fori", 32), ("tpu", "unroll", 16), ("ref", "fori", 16)],
)
def test_plan_key_carries_the_depth_the_kernel_runs(backend, variant, want_bk):
    # The TPU "fori" round does not read bk, so its key pins bk = s.
    eng = ApspEngine(method="fused", block_size=32, bk=16, backend=backend,
                     variant=variant, validate=False)
    assert eng.plan_for(100, batch=2).key.bk == want_bk


def test_bucketing_counts_and_order():
    eng = ApspEngine(method="fused", block_size=32, validate=False)
    sizes = (90, 40, 96, 40, 20)
    graphs = [random_digraph(n, density=0.6, seed=n + 7) for n in sizes]
    results = eng.solve_many(graphs)
    # 90 and 96 pad to 96 → one bucket; two n=40 → one; n=20 → one.
    assert eng.stats.solves == 3
    assert eng.stats.graphs_solved == 5
    assert [r.n for r in results] == list(sizes)
    assert results[0].padded_n == results[2].padded_n == 96


def test_engine_validates_negative_cycles():
    w = np.full((70, 70), np.inf, np.float32)
    np.fill_diagonal(w, 0.0)
    w[0, 1], w[1, 2], w[2, 0] = 1.0, -3.0, 1.0
    eng = ApspEngine(method="fused", block_size=32)
    with pytest.raises(NegativeCycleError):
        eng.solve(w)
    ok = random_digraph(70, density=0.5, seed=0)
    with pytest.raises(NegativeCycleError) as ei:
        eng.solve_many([ok, w])
    assert "1" in str(ei.value)  # names the offending input index


def test_engine_rejects_distributed():
    with pytest.raises(ValueError):
        ApspEngine(method="distributed")


# ------------------------------------------------------------ serving layer
def test_routing_engine_serves_from_cached_tables():
    from repro.serve.engine import RoutingEngine

    side = 4
    w = grid_graph(side)
    w_failed = w.copy()
    w_failed[5, 6] = np.inf
    w_failed[6, 5] = np.inf

    router = RoutingEngine()
    router.add_graph("healthy", w)
    router.add_graph("failed", w_failed)
    router.add_graph("big", random_digraph(70, density=0.5, seed=3))
    assert router.dirty_count == 3
    assert router.refresh() == 3
    assert router.dirty_count == 0

    r = router.query("healthy", 0, 15)
    assert r.reachable and r.path[0] == 0 and r.path[-1] == 15
    assert abs(path_cost(w, r.path) - r.cost) < 1e-5

    r2 = router.query("failed", 5, 6)
    assert r2.reachable and len(r2.path) > 2  # rerouted around the cut link
    assert abs(path_cost(w_failed, r2.path) - r2.cost) < 1e-5

    # refresh() with nothing dirty is free
    assert router.refresh() == 0


def test_routing_engine_mutation_marks_dirty_and_requeries():
    from repro.serve.engine import RoutingEngine

    router = RoutingEngine()
    w = grid_graph(4)
    router.add_graph("g", w)
    before = router.query("g", 0, 15)
    router.fail_link("g", before.path[0], before.path[1])
    assert router.dirty_count == 1
    after = router.query("g", 0, 15)  # auto_refresh resolves
    assert router.dirty_count == 0
    assert after.cost >= before.cost
    assert after.path[1] != before.path[1]

    strict = RoutingEngine(auto_refresh=False)
    strict.add_graph("g", w)
    with pytest.raises(RuntimeError):
        strict.query("g", 0, 1)


def test_routing_engine_batches_refresh_through_one_engine():
    from repro.serve.engine import RoutingEngine

    router = RoutingEngine()
    for i in range(4):
        router.add_graph(f"g{i}", random_digraph(40, density=0.6, seed=i))
    router.refresh()
    # 4 same-shape graphs → one bucket → one batched solve
    assert router.engine.stats.solves == 1
    assert router.engine.stats.graphs_solved == 4
    replies = router.query_many([("g0", 0, 5), ("g3", 2, 7)])
    assert len(replies) == 2 and all(r.cost >= 0 for r in replies)
