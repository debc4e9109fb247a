"""The unified APSP front-end (repro.apsp.solve) + the O(1)-trace round loop.

Covers the PR's acceptance surface:
  * non-multiple n round-trips through solve() without manual padding;
  * batched solve() matches per-graph results bit-for-bit;
  * fori-loop-driven fw_staged/fw_blocked match the unrolled (seed) round
    loop bit-for-bit on every semiring;
  * blocked-path successor matrices reproduce fw_with_successors;
  * the fw_staged jaxpr holds a number of pallas_calls independent of n;
  * solve() is one ApspEngine.solve, and api.py calls no solve
    implementation of its own.
"""
import ast
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apsp import METHODS, ApspEngine, NegativeCycleError, plan, solve
from repro.core import SEMIRINGS, fw_blocked, fw_naive, fw_staged
from repro.core.graph import random_digraph
from repro.core.paths import (
    extract_path,
    fw_blocked_with_successors,
    fw_with_successors,
    path_cost,
)
from repro.utils import compat


def _graph_for(semiring_name: str, n: int, seed: int) -> np.ndarray:
    """A test matrix in the right value domain for each semiring."""
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


# ------------------------------------------------------- solve() front-end
@pytest.mark.parametrize("n", [5, 30, 100, 300])
def test_solve_pads_non_multiple_n(n):
    w = random_digraph(n, density=0.4, seed=n)
    res = solve(w, method="blocked")
    assert res.dist.shape == (n, n)
    assert res.padded_n % res.block_size == 0
    want = np.asarray(fw_naive(jnp.asarray(w)))
    np.testing.assert_allclose(np.asarray(res.dist), want, rtol=1e-5, atol=1e-5)


def test_solve_staged_non_multiple_n():
    n = 90  # pads to 96 with s=32: exercises dynamic_slice on padded tiles
    w = random_digraph(n, density=0.4, seed=7)
    res = solve(w, method="staged", block_size=32)
    assert res.dist.shape == (n, n) and res.padded_n == 96
    want = np.asarray(fw_naive(jnp.asarray(w)))
    np.testing.assert_allclose(np.asarray(res.dist), want, rtol=1e-5, atol=1e-5)


def test_solve_promotes_int_input_when_padding():
    # Int matrices can't hold the +inf padding identity; without promotion
    # INT_MAX + w wraps negative and silently shortens paths through the
    # padding vertices.
    rng = np.random.default_rng(0)
    wi = rng.integers(1, 10, size=(100, 100))
    np.fill_diagonal(wi, 0)
    res = solve(wi, method="blocked", block_size=64)  # pads 100 → 128
    assert jnp.issubdtype(res.dist.dtype, jnp.floating)
    want = np.asarray(fw_naive(jnp.asarray(wi, jnp.float32)))
    assert np.array_equal(np.asarray(res.dist), want)


def test_solve_batched_matches_per_graph():
    wb = np.stack([random_digraph(70, density=0.4, seed=i) for i in range(4)])
    res = solve(wb, method="blocked", block_size=32)
    assert res.batched and res.dist.shape == (4, 70, 70)
    for i in range(4):
        single = solve(wb[i], method="blocked", block_size=32)
        assert np.array_equal(np.asarray(res.dist[i]), np.asarray(single.dist))


def test_solve_batched_successors_match_per_graph():
    wb = np.stack([random_digraph(40, density=0.5, seed=i) for i in range(3)])
    res = solve(wb, method="blocked", block_size=16, successors=True)
    assert res.succ.shape == (3, 40, 40)
    for i in range(3):
        single = solve(wb[i], method="blocked", block_size=16, successors=True)
        assert np.array_equal(np.asarray(res.succ[i]), np.asarray(single.succ))


def test_solve_auto_dispatch():
    assert solve(random_digraph(20, seed=0)).method == "naive"
    big = solve(random_digraph(200, density=0.5, seed=1))
    assert big.method == ("staged" if jax.default_backend() == "tpu" else "blocked")
    s = solve(random_digraph(200, density=0.5, seed=1), successors=True)
    assert s.method == "blocked" and s.succ is not None


def test_solve_semiring_by_name_and_padding_identity():
    # or_and: pad value is 0 (⊕-identity), pad diag 1 (⊗-identity) — the
    # 20 real vertices must be unaffected by the 108 padding vertices.
    rng = np.random.default_rng(3)
    adj = (rng.uniform(size=(20, 20)) < 0.15).astype(np.float32)
    np.fill_diagonal(adj, 1.0)
    res = solve(adj, method="staged", semiring="or_and", block_size=32)
    want = np.asarray(fw_naive(jnp.asarray(adj), semiring=SEMIRINGS["or_and"]))
    assert np.array_equal(np.asarray(res.dist), want)


def test_solve_negative_cycle_raises():
    w = np.full((6, 6), np.inf, np.float32)
    np.fill_diagonal(w, 0.0)
    w[0, 1], w[1, 2], w[2, 0] = 1.0, -3.0, 1.0
    with pytest.raises(NegativeCycleError):
        solve(w, method="naive")
    # validate=False returns the (negative-diagonal) fixed point instead.
    res = solve(w, method="naive", validate=False)
    assert np.asarray(res.dist)[0, 0] < 0


def test_solve_rejects_bad_arguments():
    w = random_digraph(16, seed=0)
    with pytest.raises(ValueError):
        solve(w, method="warp-drive")
    with pytest.raises(ValueError):
        solve(w[:8, :4])
    with pytest.raises(ValueError):
        solve(w, successors=True, semiring="max_plus")
    with pytest.raises(ValueError):
        # numpy has no successor tracking (staged/fused do, natively, now).
        solve(w, method="numpy", successors=True)
    with pytest.raises(ValueError):
        solve(w, method="distributed")  # no mesh


# ------------------------------------------------- solve() == ApspEngine
@pytest.mark.parametrize("method", ["auto", "fused"])
@pytest.mark.parametrize("case", ["plain", "successors", "batched"])
def test_solve_equals_engine_solve(method, case):
    """The stateless solve is one ApspEngine.solve: same closure, same
    next hops, same resolved fields."""
    if case == "batched":
        w = np.stack([random_digraph(70, density=0.4, seed=i) for i in range(3)])
    else:
        w = random_digraph(70, density=0.4, seed=5)
    successors = case == "successors"
    kw = dict(method=method, block_size=32)
    got = solve(w, successors=successors, **kw)
    want = ApspEngine(**kw).solve(w, successors=successors)
    assert np.array_equal(np.asarray(got.dist), np.asarray(want.dist))
    if successors:
        assert np.array_equal(np.asarray(got.succ), np.asarray(want.succ))
    else:
        assert got.succ is None and want.succ is None
    fields = ("method", "semiring", "block_size", "n", "padded_n", "backend")
    assert [getattr(got, f) for f in fields] == [getattr(want, f) for f in fields]
    if method == "fused":
        assert got.backend == compat.resolve_pallas_backend("auto")


def test_api_reaches_no_solve_implementation():
    """api.solve's only path to a closure is ApspEngine.solve, and the
    engine never imports the front end: the arrows point one way."""
    import repro.apsp.api as api_mod
    import repro.apsp.engine as engine_mod

    def names(mod):
        tree = ast.parse(inspect.getsource(mod))
        out = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Name):
                out.add(node.id)
            elif isinstance(node, ast.Attribute):
                out.add(node.attr)
            elif isinstance(node, ast.alias):
                out.add(node.name)
            elif isinstance(node, ast.ImportFrom):
                out.add(node.module)
        return out

    solvers = {"fw_staged", "fw_staged_with_successors", "fw_kleene",
               "fw_distributed", "fw_blocked", "fw_naive", "fw_numpy"}
    api_names = names(api_mod)
    assert not solvers & api_names
    assert "ApspEngine" in api_names
    assert not {"repro.apsp.api", "api"} & names(engine_mod)


# ------------------------------------------- fori round loop == seed unroll
@pytest.mark.parametrize("name", sorted(SEMIRINGS))
def test_blocked_fori_matches_unrolled_bitwise(name):
    sr = SEMIRINGS[name]
    w = jnp.asarray(_graph_for(name, 96, seed=11))
    fori = fw_blocked(w, block_size=32, semiring=sr)
    unrolled = fw_blocked(w, block_size=32, semiring=sr, unroll_rounds=True)
    assert np.array_equal(np.asarray(fori), np.asarray(unrolled))


@pytest.mark.parametrize("name", sorted(SEMIRINGS))
def test_staged_fori_matches_unrolled_bitwise(name):
    sr = SEMIRINGS[name]
    w = jnp.asarray(_graph_for(name, 64, seed=13))
    kw = dict(block_size=32, bm=32, bn=32, bk=16, semiring=sr, interpret=True)
    fori = fw_staged(w, **kw)
    unrolled = fw_staged(w, unroll_rounds=True, lowering="phases", **kw)
    assert np.array_equal(np.asarray(fori), np.asarray(unrolled))


def _count_pallas_calls(jaxpr) -> int:
    """pallas_call *call sites*, recursing into sub-jaxprs per site."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)  # unwrap ClosedJaxpr
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            count += 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    count += _count_pallas_calls(sub)
    return count


def test_trace_size_constant_in_n():
    """The tentpole: pallas_call count in the jaxpr is independent of n."""

    def trace(n, **kw):
        w = jnp.zeros((n, n), jnp.float32)
        return jax.make_jaxpr(
            lambda x: fw_staged(x, block_size=128, interpret=True, **kw)
        )(w)

    n_small = _count_pallas_calls(trace(512))
    n_large = _count_pallas_calls(trace(2048))
    assert n_small == n_large > 0
    # The seed behavior (python round loop) scales with n — guard the guard:
    # phase 1 + 2×phase 2 + phase 3 per round, one round per 128 pivots.
    assert _count_pallas_calls(trace(512, unroll_rounds=True, lowering="phases")) == 4 * (512 // 128)
    assert _count_pallas_calls(trace(1024, unroll_rounds=True, lowering="phases")) == 4 * (1024 // 128)


# ------------------------------------------------------- blocked successors
@pytest.mark.parametrize("n,bs", [(32, 8), (64, 16), (96, 32)])
def test_blocked_successors_match_naive(n, bs):
    # Continuous random weights → ties have measure zero → the strict-<
    # update rule makes blocked and naive successor matrices identical.
    w = jnp.asarray(random_digraph(n, density=0.5, seed=n + bs))
    d_ref, s_ref = fw_with_successors(w)
    d_got, s_got = fw_blocked_with_successors(w, block_size=bs)
    np.testing.assert_allclose(np.asarray(d_got), np.asarray(d_ref), rtol=1e-6)
    assert np.array_equal(np.asarray(s_got), np.asarray(s_ref))


def test_blocked_successor_paths_have_correct_cost():
    n = 60
    w = random_digraph(n, density=0.3, seed=5)
    res = solve(w, successors=True, method="blocked", block_size=16)
    d, succ = np.asarray(res.dist), np.asarray(res.succ)
    rng = np.random.default_rng(0)
    for src, dst in rng.integers(0, n, size=(20, 2)):
        path = extract_path(succ, int(src), int(dst))
        if np.isfinite(d[src, dst]) and src != dst:
            assert path[0] == src and path[-1] == dst
            assert abs(path_cost(w, path) - d[src, dst]) < 1e-4
        elif not np.isfinite(d[src, dst]):
            assert path == []


# ------------------------------------------------------------ plan helpers
def test_plan_arithmetic():
    assert plan.padded_size(300, 128) == 384
    assert plan.round_count(300, 128) == 3
    assert plan.auto_block_size(1024) == 128
    assert 16 <= plan.auto_block_size(40) <= 40
    assert plan.mesh_factorization(8) == (4, 2)
    assert plan.mesh_factorization(8, pods=2) == (4, 2)
    assert plan.distributed_multiple(32, 4, 2) == 128
    # VMEM formula matches the documented reference points (EXPERIMENTS.md).
    assert plan.phase3_vmem_bytes(128, 128, 8) == 80 * 1024
    assert plan.phase3_vmem_bytes(128, 128, 32) == 128 * 1024
    assert "auto" in METHODS
