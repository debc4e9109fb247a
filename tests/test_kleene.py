"""Out-of-core recursive (R-Kleene) Floyd-Warshall acceptance surface.

ISSUE 8 contracts:

  * **bitwise, not allclose**: ``solve(method="recursive")`` equals
    ``method="fused"`` at the same block size on all 5 semirings × storage
    lowerings {f32, int16, bf16, packed or_and}, odd/padded n, batched
    inputs, and leaf sizes forcing ≥ 2 recursion levels.  The leaves replay
    the fused round's op chains and the deferred sweep is the same
    ascending-k left fold, so equality holds by construction — these tests
    pin the construction.
  * **out of core is the same computation**: a ``HostPanelStore`` run
    (host-resident matrix, streamed panels) is bitwise equal to the
    ``DevicePanelStore`` run and to the fused solve, and its measured
    h2d/d2h byte counters match ``plan.recursive_transfer_bytes`` within
    the 15% acceptance band (exact on the panel schedule).
  * **planning**: ``plan.kleene_ranges`` tiles the round axis exactly;
    ``recursive_plan`` flips out_of_core on the budget and picks a leaf
    whose residency fits; a capped ``hbm_budget`` promotes in-core methods
    to recursive in both ``solve`` and the engine; ``autotune_fw`` ranks
    streaming candidates when the matrix cannot fit.
  * **engine**: warm plan-cache solves retrace nothing (the executor's jit
    caches persist per key); plan keys carry (leaf, oocore).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apsp import (
    ApspEngine,
    DevicePanelStore,
    HostPanelStore,
    KleeneExecutor,
    plan,
    solve,
)
from repro.apsp.kleene import fw_kleene
from repro.core.semiring import LOWERED_SEMIRINGS, MIN_PLUS, SEMIRINGS
from repro.core.staged import fw_staged

SR_NAMES = ("min_plus", "max_plus", "max_min", "or_and", "plus_mul")


def _graph(n, seed, sr=MIN_PLUS, batch=None):
    """Random weights in each semiring's useful range (plus_mul needs small
    positive weights or the product closure overflows f32 — repo idiom)."""
    rng = np.random.default_rng(seed)
    shape = (n, n) if batch is None else (batch, n, n)
    if sr.name == "plus_mul":
        w = rng.uniform(0.0, 0.01, size=shape).astype(np.float32)
    elif sr.name == "max_plus":
        # Negative weights: positive cycles make the max_plus closure
        # diverge (doubling per relaxation overflows f32 past n ≈ 130).
        w = rng.uniform(-10.0, -1.0, size=shape).astype(np.float32)
    else:
        w = rng.uniform(1.0, 10.0, size=shape).astype(np.float32)
    w = np.where(rng.random(shape) < 0.4, np.float32(sr.zero), w)
    if sr.name != "plus_mul":
        # plus_mul keeps its small random diagonal (repo idiom): a ⊗-identity
        # self-loop feeds x → x + x² per pivot, overflowing f32 in ~7 rounds.
        idx = np.arange(n)
        w[..., idx, idx] = sr.one
    if sr.name == "or_and":
        w = (w != sr.zero).astype(np.float32)
    return w


def _bitwise(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ core schedule
@pytest.mark.parametrize("srname", SR_NAMES)
@pytest.mark.parametrize(
    "n,s,leaf",
    [
        (128, 32, 32),   # leaf == s: maximal recursion depth (3 levels)
        (160, 32, 64),   # ragged last panel (2.5 leaves)
        (96, 32, 96),    # degenerate: one panel == the fused schedule
    ],
)
def test_fw_kleene_bitwise_vs_fused(srname, n, s, leaf):
    sr = SEMIRINGS[srname]
    w = jnp.asarray(_graph(n, seed=7, sr=sr))
    ref = fw_staged(w, block_size=s, semiring=sr, lowering="ref")
    got = fw_kleene(w, semiring=sr, block_size=s, leaf=leaf)
    assert _bitwise(ref, got)


@pytest.mark.parametrize("srname", SR_NAMES)
def test_fw_kleene_batched_bitwise(srname):
    sr = SEMIRINGS[srname]
    w = jnp.asarray(_graph(96, seed=11, sr=sr, batch=3))
    ref = fw_staged(w, block_size=32, semiring=sr, lowering="ref")
    got = fw_kleene(w, semiring=sr, block_size=32, leaf=32)
    assert _bitwise(ref, got)


def test_solve_recursive_bitwise_all_semirings_odd_n():
    # Odd n exercises the shared padding policy: recursive pads exactly
    # like fused at the same block size, so results stay bitwise.
    for srname in SR_NAMES:
        sr = SEMIRINGS[srname]
        w = _graph(150, seed=13, sr=sr)
        rf = solve(w, method="fused", block_size=32, semiring=sr,
                   validate=False)
        rr = solve(w, method="recursive", block_size=32, leaf=64,
                   semiring=sr, validate=False)
        assert rr.method == "recursive"
        assert rr.padded_n == rf.padded_n
        assert _bitwise(rf.dist, rr.dist), srname


def test_solve_recursive_storage_lowerings_bitwise():
    # int16 saturating tropical
    w = _graph(100, seed=17)
    rf = solve(w, method="fused", block_size=32, dtype="int16",
               validate=False)
    rr = solve(w, method="recursive", block_size=32, leaf=32,
               dtype="int16", validate=False)
    assert rr.dist.dtype == np.int16 and _bitwise(rf.dist, rr.dist)
    # bf16 cast
    rf = solve(w, method="fused", block_size=32, dtype=jnp.bfloat16,
               validate=False)
    rr = solve(w, method="recursive", block_size=32, leaf=32,
               dtype=jnp.bfloat16, validate=False)
    assert rr.dist.dtype == jnp.bfloat16 and _bitwise(rf.dist, rr.dist)
    # packed or_and bit planes (40 graphs → 2 int32 words)
    rng = np.random.default_rng(19)
    wb = (rng.random((40, 96, 96)) < 0.05).astype(np.float32)
    rf = solve(wb, method="fused", block_size=32, semiring="or_and",
               packed=True)
    rr = solve(wb, method="recursive", block_size=32, leaf=32,
               semiring="or_and", packed=True)
    assert _bitwise(rf.dist, rr.dist)


def test_recursive_rejects_successors():
    with pytest.raises(ValueError, match="successors"):
        solve(_graph(64, seed=23), method="recursive", successors=True)


# ----------------------------------------------------------- out of core
def test_host_store_bitwise_and_transfer_model():
    n, s, leaf = 256, 32, 64
    w = _graph(n, seed=29)
    ref = fw_staged(jnp.asarray(w), block_size=s, semiring=MIN_PLUS,
                    lowering="ref")
    ex = KleeneExecutor(semiring=MIN_PLUS, block_size=s, leaf=leaf)
    store = HostPanelStore(w)
    ex.run(store)
    assert _bitwise(ref, store.result())
    # Measured stream bytes vs the plan model: the executor IS the model's
    # traversal (both walk plan.kleene_ranges), so this is exact, well
    # inside the 15% acceptance band.
    h2d, d2h = plan.recursive_transfer_bytes(n, s, leaf // s)
    assert abs(store.h2d_bytes - h2d) <= 0.15 * h2d
    assert abs(store.d2h_bytes - d2h) <= 0.15 * d2h
    assert store.h2d_bytes == h2d and store.d2h_bytes == d2h
    # In-core twin: same computation, zero transfer.
    dev = DevicePanelStore(jnp.asarray(w))
    KleeneExecutor(semiring=MIN_PLUS, block_size=s, leaf=leaf).run(dev)
    assert _bitwise(store.result(), dev.result())
    assert dev.h2d_bytes == 0 and dev.d2h_bytes == 0


def test_capped_budget_streams_and_matches_fused():
    # A budget far below the matrix footprint: solve must promote to
    # recursive + out-of-core, complete, and stay bitwise.  512² f32 = 1 MiB
    # against a 600 KiB budget — the full matrix cannot be resident, but one
    # s=64 pivot cross + factors (560 KiB) can.
    n, budget = 512, 600 << 10
    w = _graph(n, seed=31)
    assert n * n * 4 > budget
    rp = plan.recursive_plan(n, block_size=64, hbm_budget=budget)
    assert rp["out_of_core"]
    assert rp["hbm_resident_bytes"] <= budget < rp["matrix_bytes"]
    res = solve(w, method="fused", block_size=64, hbm_budget=budget)
    assert res.method == "recursive"
    ref = solve(w, method="fused", block_size=64)
    assert _bitwise(ref.dist, res.dist)


def test_batched_transfer_model_scales():
    n, s, leaf, B = 128, 32, 32, 3
    w = _graph(n, seed=37, batch=B)
    ex = KleeneExecutor(semiring=MIN_PLUS, block_size=s, leaf=leaf)
    store = HostPanelStore(w)
    ex.run(store)
    h2d, d2h = plan.recursive_transfer_bytes(n, s, leaf // s, batch=B)
    assert store.h2d_bytes == h2d and store.d2h_bytes == d2h
    ref = fw_staged(jnp.asarray(w), block_size=s, semiring=MIN_PLUS,
                    lowering="ref")
    assert _bitwise(ref, store.result())


# ------------------------------------------------------------------ plans
def test_kleene_ranges_tile_the_round_axis():
    for T in (1, 2, 3, 7, 8, 16, 33):
        for lr in (1, 2, 4):
            ranges, depth = plan.kleene_ranges(T, lr)
            # in-order, gap-free, leaf-bounded cover of [0, T)
            assert ranges[0][0] == 0 and ranges[-1][1] == T
            for (a, b), (c, _) in zip(ranges, ranges[1:]):
                assert b == c and 0 < b - a <= lr
            assert 0 < ranges[-1][1] - ranges[-1][0] <= lr
            assert depth >= 1


def test_recursive_plan_budget_flip_and_leaf_fit():
    rp_in = plan.recursive_plan(1000, block_size=128)
    assert not rp_in["out_of_core"] and rp_in["transfer_bytes"] == 0
    rp_out = plan.recursive_plan(1000, block_size=128, hbm_budget=3 << 20)
    assert rp_out["out_of_core"]
    assert rp_out["hbm_resident_bytes"] <= 3 << 20
    assert rp_out["transfer_bytes"] > 0
    assert rp_out["leaf"] % rp_out["block_size"] == 0
    # steps model matches an actual run (zeros input: we count dispatches)
    ex = KleeneExecutor(
        semiring=MIN_PLUS, block_size=128, leaf=rp_out["leaf"]
    )
    store = HostPanelStore(
        np.zeros((rp_out["n_padded"], rp_out["n_padded"]), np.float32)
    )
    ex.run(store)
    assert ex.leaf_calls == rp_out["leaf_calls"]
    assert ex.sweep_calls == rp_out["sweep_calls"]
    assert ex.depth == rp_out["depth"]


def test_autotune_ranks_streaming_candidates_under_budget():
    budget = 2 << 20
    ranked = plan.autotune_fw(1024, hbm_budget=budget)
    assert ranked and ranked[0]["impl"] == "recursive"
    for c in ranked:
        if c["impl"] == "recursive":
            assert c["hbm_bytes_total"] + c["pcie_bytes_total"] == pytest.approx(
                c["total_bytes"]
            )
        else:  # resident candidates must actually fit
            assert 1024 * 1024 * c["word"] * c["batch"] <= budget
    # without a budget the ranking is unchanged from the resident models
    base = plan.autotune_fw(256)
    assert base[0]["impl"] in ("fused", "staged")
    assert base[0]["total_bytes"] == base[0]["hbm_bytes_total"]


# ----------------------------------------------------------------- engine
def test_engine_recursive_warm_cache_no_retrace():
    eng = ApspEngine(method="recursive", block_size=32, leaf=64)
    w = _graph(200, seed=43)
    r1 = eng.solve(w)
    entry = next(iter(eng._cache.values()))
    assert entry.key.method == "recursive"
    assert entry.key.leaf == 64 and entry.key.oocore is False
    warm = entry.traces
    assert warm > 0
    r2 = eng.solve(w)
    assert entry.traces == warm  # the no-recompile guarantee
    assert eng.stats.hits == 1
    rf = solve(w, method="fused", block_size=32)
    assert _bitwise(r1.dist, rf.dist) and _bitwise(r2.dist, rf.dist)


def test_engine_budget_promotes_to_streaming():
    eng = ApspEngine(method="fused", block_size=32, hbm_budget=100_000)
    w = _graph(200, seed=47)
    res = eng.solve(w)
    key = next(iter(eng._cache))
    assert key.method == "recursive" and key.oocore is True
    assert res.method == "recursive"
    assert _bitwise(res.dist, solve(w, method="fused", block_size=32).dist)
    # the cached executor streamed for real
    entry = eng._cache[key]
    assert entry.executor.sweep_calls > 0
