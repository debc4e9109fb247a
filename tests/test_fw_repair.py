"""Rank-1 incremental repair: kernel vs twin, repair vs re-solve, policy.

Three layers of guarantee (ISSUE 7 acceptance):

  * ``kernels.fw_repair`` == its XLA twin ``kernels.ref.fw_repair_ref``
    BITWISE on every storage lowering — the kernel's staged two-phase grid
    (evolve pivot rows into scratch, then fold all E updates per band) is
    pure scheduling around the same ⊕/⊗ chain as the direct per-edge loop.
  * ``ApspEngine.repair`` == a full re-solve of the updated graph, bitwise,
    on all 5 semirings × {f32, int16, packed or_and} — distances AND
    successor tables (tie-free weights make successor comparison exact).
    The per-semiring input constructions live in
    ``launch.fw_serve.repair_scenario`` (shared with the CI smoke) and
    satisfy the kernel's documented exactness conditions.
  * the 8-virtual-device mesh path (``core.distributed
    .build_repair_shard_fn``) bit-matches both, via fw_dist_check --repair
    subprocesses (host-device count locks at first jax init).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.semiring import (
    I16_INF,
    LOWERED_SEMIRINGS,
    MIN_PLUS,
    SEMIRINGS,
)
from repro.kernels.fw_repair import fw_repair, fw_repair_with_successors
from repro.kernels.ref import fw_repair_ref, fw_repair_with_successors_ref
from repro.launch.fw_serve import _apply_updates, repair_scenario

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SR_NAMES = ("min_plus", "max_plus", "max_min", "or_and", "plus_mul")


def _random_closure_like(sr, n, seed):
    """Any square matrix in the lowering's dtype — kernel-vs-twin needs no
    closure structure, just identical inputs on both sides."""
    rng = np.random.default_rng(seed)
    if sr.packed:
        return rng.integers(-(2**31), 2**31, (n, n), dtype=np.int64).astype(
            np.int32
        )
    if sr.dtype == "int16":
        return rng.integers(-300, 300, (n, n)).astype(np.int16)
    d = rng.uniform(-10, 10, (n, n)).astype(np.float32)
    return d.astype(jnp.bfloat16) if sr.dtype == "bfloat16" else d


def _random_edges(sr, n, E, seed):
    rng = np.random.default_rng(seed + 1)
    u = rng.integers(0, n, E).astype(np.int32)
    v = rng.integers(0, n, E).astype(np.int32)
    if sr.packed:
        w = rng.integers(-(2**31), 2**31, E, dtype=np.int64).astype(np.int32)
    elif sr.dtype == "int16":
        w = rng.integers(-300, 300, E).astype(np.int16)
    else:
        w = rng.uniform(-10, 10, E).astype(np.float32)
        if sr.dtype == "bfloat16":
            w = w.astype(jnp.bfloat16)
    return u, v, w


@pytest.mark.parametrize(
    "srname",
    list(SR_NAMES) + sorted(LOWERED_SEMIRINGS),
)
def test_repair_kernel_bitwise_vs_twin(srname):
    """Pallas repair kernel == direct per-edge XLA loop, bit for bit."""
    sr = SEMIRINGS.get(srname) or LOWERED_SEMIRINGS[srname]
    n, E = 16, 5
    d = _random_closure_like(sr, n, 0)
    u, v, w = _random_edges(sr, n, E, 0)
    got = fw_repair(d, u, v, w, block_size=8, semiring=sr, interpret=True)
    want = fw_repair_ref(jnp.asarray(d), u, v, jnp.asarray(w), semiring=sr)
    assert np.array_equal(np.asarray(got), np.asarray(want), equal_nan=True)


def test_repair_succ_kernel_bitwise_vs_twin():
    """Successor-patching variant vs its twin (strict-< relaxation)."""
    n, E = 16, 5
    rng = np.random.default_rng(3)
    d = rng.integers(1, 10**6, (n, n)).astype(np.float32)
    np.fill_diagonal(d, 0.0)
    succ = rng.integers(-1, n, (n, n)).astype(np.int32)
    u = rng.integers(0, n, E).astype(np.int32)
    v = rng.integers(0, n, E).astype(np.int32)
    w = rng.integers(1, 100, E).astype(np.float32)
    gd, gs = fw_repair_with_successors(d, succ, u, v, w, block_size=8,
                                       interpret=True)
    wd, ws = fw_repair_with_successors_ref(jnp.asarray(d), jnp.asarray(succ),
                                           u, v, jnp.asarray(w))
    assert np.array_equal(np.asarray(gd), np.asarray(wd))
    assert np.array_equal(np.asarray(gs), np.asarray(ws))


# ------------------------------------------------- engine: repair == resolve
@pytest.mark.parametrize("srname", SR_NAMES)
def test_engine_repair_equals_resolve(srname):
    """One repair() call == full re-solve of the updated graph, bitwise.

    plus_mul compares against method="naive": the blocked/fused pivot-block
    re-relaxation over-counts under a non-idempotent ⊕, so only plain FW
    equals the true path-sum closure (and the repair recurrence targets
    that closure; the engine lifts/restores the ⊗-identity diagonal).
    """
    from repro.apsp import ApspEngine

    w, upd, baseline = repair_scenario(srname, 48)
    eng = ApspEngine(method=baseline, semiring=srname, validate=False)
    r0 = eng.solve(w)
    rep = eng.repair(r0.dist, upd)
    r1 = eng.solve(_apply_updates(w, upd, srname))
    assert np.array_equal(np.asarray(rep.dist), np.asarray(r1.dist),
                          equal_nan=True)


def test_engine_repair_int16_and_packed():
    from repro.apsp import ApspEngine, pack_reachability

    n = 48
    rng = np.random.default_rng(1)
    wi = rng.integers(1, 997, (n, n)).astype(np.int16)
    wi[rng.uniform(size=(n, n)) > 0.4] = I16_INF
    np.fill_diagonal(wi, 0)
    eng = ApspEngine(method="fused", semiring="min_plus", dtype=jnp.int16,
                     validate=False)
    r0 = eng.solve(wi)
    upd = [(3, 7, 1), (10, 2, 2)]
    rep = eng.repair(r0.dist, upd)
    w1 = wi.copy()
    for u, v, d in upd:
        w1[u, v] = min(int(w1[u, v]), d)
    assert np.array_equal(np.asarray(rep.dist), np.asarray(eng.solve(w1).dist))

    # packed: updates are (u, v, int32-lane-mask); graph lives in a word
    # plane (1, n, n) — repair squeezes/restores the unit word axis.
    Bs = rng.uniform(size=(2, n, n)) < 0.05
    Bs[:, np.arange(n), np.arange(n)] = True
    peng = ApspEngine(method="fused", semiring="or_and", packed=True,
                      validate=False)
    p0 = peng.solve(np.asarray(pack_reachability(Bs.astype(np.float32))))
    rep = peng.repair(p0.dist, [(3, 7, 1 << 0), (40, 9, 0b11)])
    B1 = Bs.copy()
    B1[0, 3, 7] = True
    B1[:, 40, 9] = True
    p1 = peng.solve(np.asarray(pack_reachability(B1.astype(np.float32))))
    assert np.asarray(rep.dist).shape == np.asarray(p1.dist).shape
    assert np.array_equal(np.asarray(rep.dist), np.asarray(p1.dist))


def test_engine_repair_successors_tie_free():
    """dist AND succ bitwise — repair_scenario's min_plus weights are large
    random integers, so shortest paths are unique and the strict-<
    tie-break cannot diverge between repair and re-solve."""
    from repro.apsp import ApspEngine

    w, upd, _ = repair_scenario("min_plus", 70, seed=2)
    eng = ApspEngine(method="fused", validate=False)
    r0 = eng.solve(w, successors=True)
    rep = eng.repair(r0.dist, upd, succ=r0.succ)
    r1 = eng.solve(_apply_updates(w, upd, "min_plus"), successors=True)
    assert np.array_equal(np.asarray(rep.dist), np.asarray(r1.dist),
                          equal_nan=True)
    assert np.array_equal(np.asarray(rep.succ), np.asarray(r1.succ))


def test_engine_repair_plan_cache_and_stats():
    """Same (shape, edge-bucket) repairs share one executable (traces==1);
    edge batches pad to power-of-two buckets; stats count repairs."""
    from repro.apsp import ApspEngine

    w, upd, _ = repair_scenario("min_plus", 48)
    eng = ApspEngine(method="fused", validate=False)
    r0 = eng.solve(w)
    eng.repair(r0.dist, upd)           # 3 edges → bucket 4
    misses = eng.stats.misses
    eng.repair(r0.dist, upd[:2])       # 2 edges → same bucket 4: cache hit
    assert eng.stats.misses == misses
    repair_entries = [e for k, e in eng._cache.items() if k.method == "repair"]
    assert repair_entries and all(e.traces == 1 for e in repair_entries)
    assert eng.stats.repairs == 2 and eng.stats.edges_repaired == 5


def test_should_repair_crossover():
    """The cost policy: tiny backlogs repair, huge backlogs re-solve."""
    from repro.apsp import ApspEngine

    eng = ApspEngine(method="fused")
    assert eng.should_repair(1024, 1)
    assert not eng.should_repair(1024, 500)
    assert not eng.should_repair(1024, 0)


def test_should_repair_worsening_fast_reject():
    """Edge worsenings fast-reject regardless of cost: repair only absorbs
    ⊕-improvements, so even a 1-edge backlog with one worsening must take
    the re-solve fallback — and the reject is visible in stats."""
    from repro.apsp import ApspEngine

    eng = ApspEngine(method="fused")
    assert eng.should_repair(1024, 1)           # cheap AND sound → repair
    assert eng.stats.repair_rejects == 0
    assert not eng.should_repair(1024, 1, worsenings=1)
    assert not eng.should_repair(1024, 3, worsenings=2)
    assert eng.stats.repair_rejects == 2


def test_repair_rejects_bad_inputs():
    from repro.apsp import ApspEngine

    eng = ApspEngine(method="fused")
    w, upd, _ = repair_scenario("min_plus", 32)
    r0 = eng.solve(w, successors=True)
    with pytest.raises(ValueError):
        eng.repair(r0.dist, [])
    with pytest.raises(ValueError):
        eng.repair(np.zeros(5, np.float32), upd)
    ieng = ApspEngine(method="fused", dtype=jnp.int16)
    ri = ieng.solve(np.ones((8, 8), np.int16) - np.eye(8, dtype=np.int16))
    with pytest.raises(ValueError):  # int16 has no strict-< succ lowering
        ieng.repair(ri.dist, [(0, 1, 1)], succ=np.zeros((8, 8), np.int32))


# ------------------------------------------------------ 8-device mesh repair
def _run_dist_repair(*args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"  # virtual host devices
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.fw_dist_check",
         "--devices", "8", "--n", "64", "--repair", *args],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return res.stdout


@pytest.mark.parametrize("srname", SR_NAMES)
def test_distributed_repair_bitwise(srname):
    """Mesh repair == single-device repair == full re-solve, bitwise, and
    the warm repair cache must not retrace (subprocess: the XLA host-device
    count locks at first jax init)."""
    out = _run_dist_repair("--semiring", srname)
    assert "OK repair" in out


def test_distributed_repair_int16_and_packed_bitwise():
    assert "OK repair" in _run_dist_repair("--dtype", "int16")
    assert "OK repair" in _run_dist_repair("--packed")
