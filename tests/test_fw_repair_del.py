"""Decremental APSP repair: sweep kernel vs twin, repair_del vs re-solve,
edge cases, policy, and the 8-device mesh (ISSUE 10 acceptance).

Four layers of guarantee:

  * ``kernels.fw_repair_del.fw_repair_del_sweep`` (the Pallas restricted
    row sweep) == its XLA twin ``fw_repair_del_sweep_ref`` BITWISE — the
    kernel runs the fused round's own phase recurrences on identical
    operands, scheduling is the only difference.
  * ``ApspEngine.repair_del`` == a full re-solve of the deleted graph,
    bitwise, on all 5 semirings (f32) plus the int16/bf16/packed storage
    lowerings — distances AND successor tables (tie-free weights).
    plus_mul routes through its documented full-solve fallback
    (the one-witness marking is unsound for a non-idempotent ⊕).
  * the edge cases the marking stage must get right without dispatching
    anything: an empty deletion batch, a self-loop deletion, and an
    off-shortest-path deletion (affected set exactly empty ⇒ no sweep,
    warm traces stay flat).
  * the 8-virtual-device mesh path bit-matches single-device repair_del
    and a full re-solve, via fw_dist_check --repair-del subprocesses
    (host-device count locks at first jax init).
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.semiring import I16_INF, SEMIRINGS
from repro.kernels.fw_repair_del import (
    fw_repair_del_sweep,
    fw_repair_del_sweep_ref,
    mark_affected,
)
from repro.launch.fw_serve import pick_deletions, repair_scenario

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SR_NAMES = ("min_plus", "max_plus", "max_min", "or_and", "plus_mul")
IDEMPOTENT = ("min_plus", "max_plus", "max_min", "or_and")


def _pad_rows(rows, m, floor=4):
    """Engine-style row bucket: power-of-two capacity, padded with m."""
    a_pad = min(max(floor, 1 << max(0, (len(rows) - 1)).bit_length()), m)
    out = np.full(a_pad, m, np.int32)
    out[: len(rows)] = np.sort(np.asarray(rows, np.int32))
    return out


# ------------------------------------------------ kernel: sweep vs XLA twin
@pytest.mark.parametrize("srname", IDEMPOTENT)
def test_sweep_kernel_bitwise_vs_ref(srname):
    """Pallas restricted sweep == XLA twin == full re-solve, bit for bit,
    starting from a real marked d_init (n=16, s=8 → 2 pivot blocks)."""
    from repro.apsp import solve as apsp_solve

    sr = SEMIRINGS[srname]
    n, s = 16, 8
    w, _, baseline = repair_scenario(srname, n)
    d0 = np.asarray(
        apsp_solve(w, method=baseline, block_size=s, semiring=srname,
                   validate=False).dist
    )
    dels, w1 = pick_deletions(w, d0, srname, count=2)
    assert dels, "scenario must contain on-path edges"
    u = jnp.asarray([e[0] for e in dels], jnp.int32)
    v = jnp.asarray([e[1] for e in dels], jnp.int32)
    wold = jnp.asarray(np.asarray([e[2] for e in dels], d0.dtype))
    d_init, row_mask, cnt = mark_affected(
        jnp.asarray(d0), jnp.asarray(np.asarray(w1, d0.dtype)),
        u, v, wold, len(dels), semiring=sr,
    )
    assert int(cnt) > 0
    rows = _pad_rows(np.flatnonzero(np.asarray(row_mask)), n)
    got = fw_repair_del_sweep(d_init, rows, block_size=s, semiring=sr,
                              interpret=True)
    want = fw_repair_del_sweep_ref(d_init, rows, block_size=s, semiring=sr)
    resolve = np.asarray(
        apsp_solve(w1, method=baseline, block_size=s, semiring=srname,
                   validate=False).dist
    )
    assert np.array_equal(np.asarray(got), np.asarray(want), equal_nan=True)
    assert np.array_equal(np.asarray(want), resolve, equal_nan=True)


# -------------------------------------------- engine: repair_del == resolve
@pytest.mark.parametrize("srname", SR_NAMES)
def test_engine_repair_del_equals_resolve(srname):
    """One repair_del() == full re-solve of the deleted graph, bitwise.

    threshold is forced high: at n=48 a deletion touches most rows and the
    byte model would (correctly) pick the re-solve arm; this test wants
    the sweep arm exercised.  plus_mul must instead take its documented
    full-solve fallback — and still be bitwise.
    """
    from repro.apsp import ApspEngine

    w, _, baseline = repair_scenario(srname, 48)
    eng = ApspEngine(method=baseline, semiring=srname, validate=False)
    r0 = eng.solve(w)
    dels, w1 = pick_deletions(w, r0.dist, srname)
    if not dels:  # plus_mul: no single edge equals the path-sum closure
        w0 = np.asarray(w)
        u, v = next((u, v) for u, v in np.argwhere(w0 != 0) if u != v)
        dels = [(int(u), int(v), float(w0[u, v]))]
        w1 = np.array(w0, copy=True)
        w1[u, v] = SEMIRINGS[srname].zero
    rep = eng.repair_del(r0.dist, w1, dels, threshold=100.0)
    r1 = eng.solve(w1)
    assert np.array_equal(np.asarray(rep.dist), np.asarray(r1.dist),
                          equal_nan=True)
    if srname == "plus_mul":
        assert eng.stats.repair_del_fallbacks == 1
        assert eng.stats.repair_dels == 0
    else:
        assert eng.stats.repair_dels == 1
        assert eng.stats.repair_del_fallbacks == 0


def test_engine_repair_del_int16_and_bf16():
    """The saturating int16 and bf16 storage lowerings: deletions of
    on-shortest-path edges (picked in the lowered value domain) repair
    to the exact re-solve, bitwise."""
    from repro.apsp import ApspEngine

    n = 48
    rng = np.random.default_rng(5)
    for dt in (jnp.int16, jnp.bfloat16):
        w = rng.integers(1, 120, (n, n)).astype(np.float32)
        w[rng.uniform(size=(n, n)) > 0.4] = np.inf
        np.fill_diagonal(w, 0.0)
        eng = ApspEngine(method="fused", semiring="min_plus", dtype=dt,
                         validate=False)
        r0 = eng.solve(w)
        df = np.asarray(r0.dist).astype(np.float64)
        dels, w1 = [], w.copy()
        for u, v in np.argwhere(np.isclose(w, df) & np.isfinite(w)):
            if u != v:
                dels.append((int(u), int(v), float(w[u, v])))
                w1[u, v] = np.inf
            if len(dels) == 3:
                break
        assert dels
        rep = eng.repair_del(r0.dist, w1, dels, threshold=100.0)
        r1 = eng.solve(w1)
        assert eng.stats.repair_dels == 1, jnp.dtype(dt).name
        assert np.array_equal(
            np.asarray(rep.dist).astype(np.float64),
            np.asarray(r1.dist).astype(np.float64),
        ), jnp.dtype(dt).name


def test_engine_repair_del_packed_word_plane():
    """Bit-packed or_and: deletions are (u, v, int32-lane-mask) — clearing
    edge 3→7 in lane 0 only and edge 40→9 in both lanes must reproduce
    the re-solve of the edited planes, word for word."""
    from repro.apsp import ApspEngine, pack_reachability

    n = 48
    rng = np.random.default_rng(9)
    Bs = rng.uniform(size=(2, n, n)) < 0.08
    Bs[:, np.arange(n), np.arange(n)] = True
    Bs[0, 3, 7] = True
    Bs[:, 40, 9] = True
    peng = ApspEngine(method="fused", semiring="or_and", packed=True,
                      validate=False)
    p0 = peng.solve(np.asarray(pack_reachability(Bs.astype(np.float32))))
    B1 = Bs.copy()
    B1[0, 3, 7] = False
    B1[:, 40, 9] = False
    words1 = np.asarray(pack_reachability(B1.astype(np.float32)))
    rep = peng.repair_del(p0.dist, words1,
                          [(3, 7, 1 << 0), (40, 9, 0b11)], threshold=100.0)
    p1 = peng.solve(words1)
    assert np.asarray(rep.dist).shape == np.asarray(p1.dist).shape
    assert np.array_equal(np.asarray(rep.dist), np.asarray(p1.dist))


def test_engine_repair_del_successors_both_arms():
    """dist AND succ bitwise on both policy arms: the restricted sweep
    (forced threshold) and the full-solve fallback (threshold=0)."""
    from repro.apsp import ApspEngine

    for thr, arm in ((100.0, "sweep"), (0.0, "fallback")):
        w, _, _ = repair_scenario("min_plus", 48, seed=4)
        eng = ApspEngine(method="fused", validate=False)
        r0 = eng.solve(w, successors=True)
        dels, w1 = pick_deletions(w, r0.dist, "min_plus")
        rep = eng.repair_del(r0.dist, w1, dels, succ=r0.succ, threshold=thr)
        r1 = eng.solve(w1, successors=True)
        assert np.array_equal(np.asarray(rep.dist), np.asarray(r1.dist),
                              equal_nan=True), arm
        assert np.array_equal(np.asarray(rep.succ), np.asarray(r1.succ)), arm
        assert (eng.stats.repair_dels == 1) == (arm == "sweep")


# --------------------------------------------------- edge cases (marking)
def test_repair_del_empty_batch_is_noop():
    """E=0: the result is the input closure, bitwise, and nothing runs —
    no solves, no sweeps, no fallbacks."""
    from repro.apsp import ApspEngine

    w, _, _ = repair_scenario("min_plus", 32)
    eng = ApspEngine(method="fused", validate=False)
    r0 = eng.solve(w)
    solves = eng.stats.solves
    rep = eng.repair_del(r0.dist, w, [])
    assert np.array_equal(np.asarray(rep.dist), np.asarray(r0.dist),
                          equal_nan=True)
    assert eng.stats.solves == solves
    assert eng.stats.repair_dels == 0 and eng.stats.repair_del_fallbacks == 0


def test_repair_del_self_loop_deletion():
    """Deleting a self-loop: the closure diagonal is the ⊗-identity, so
    the repaired result equals the re-solve (which re-lifts it) bitwise —
    whether or not the marking found any witnesses."""
    from repro.apsp import ApspEngine

    w, _, _ = repair_scenario("min_plus", 32, seed=1)
    w = np.asarray(w).copy()
    w[5, 5] = 0.0  # explicit unit self-loop
    eng = ApspEngine(method="fused", validate=False)
    r0 = eng.solve(w)
    w1 = w.copy()
    w1[5, 5] = np.inf
    rep = eng.repair_del(r0.dist, w1, [(5, 5, 0.0)], threshold=100.0)
    r1 = eng.solve(w1)
    assert np.array_equal(np.asarray(rep.dist), np.asarray(r1.dist),
                          equal_nan=True)


def test_repair_del_off_path_deletion_is_noop_and_traces_flat():
    """An off-shortest-path deletion (w[u,v] strictly worse than the
    closure) witnesses strictly ⊕-worse everywhere ⇒ the affected set is
    exactly empty: no sweep dispatch, a noop in stats, and repeating the
    call retraces nothing."""
    from repro.apsp import ApspEngine

    w, _, _ = repair_scenario("min_plus", 48, seed=2)
    eng = ApspEngine(method="fused", validate=False)
    r0 = eng.solve(w)
    w0, d0 = np.asarray(w), np.asarray(r0.dist)
    off = next(
        (u, v) for u, v in np.argwhere(np.isfinite(w0) & (w0 > d0))
        if u != v
    )
    u, v = int(off[0]), int(off[1])
    w1 = w0.copy()
    w1[u, v] = np.inf
    rep = eng.repair_del(r0.dist, w1, [(u, v, float(w0[u, v]))],
                         threshold=100.0)
    assert np.array_equal(np.asarray(rep.dist), d0, equal_nan=True)
    assert eng.stats.repair_del_noops == 1
    assert eng.stats.repair_dels == 0  # the sweep never dispatched
    sweep_keys = [k for k in eng._cache if k.method == "repair_del"]
    assert not sweep_keys  # only the mark stage compiled
    eng.repair_del(r0.dist, w1, [(u, v, float(w0[u, v]))], threshold=100.0)
    marks = [e for k, e in eng._cache.items()
             if k.method == "repair_del_mark"]
    assert marks and all(e.traces == 1 for e in marks)


def test_repair_del_plan_cache_and_stats():
    """Same (shape, edge-bucket, row-bucket) deletions share executables
    (traces==1 on warm repeat); stats count rows and edges."""
    from repro.apsp import ApspEngine

    w, _, _ = repair_scenario("min_plus", 48)
    eng = ApspEngine(method="fused", validate=False)
    r0 = eng.solve(w)
    dels, w1 = pick_deletions(w, r0.dist, "min_plus")
    eng.repair_del(r0.dist, w1, dels, threshold=100.0)
    eng.repair_del(r0.dist, w1, dels, threshold=100.0)  # warm
    entries = [e for k, e in eng._cache.items()
               if k.method.startswith("repair_del")]
    assert entries and all(e.traces == 1 for e in entries)
    assert eng.stats.repair_dels == 2
    assert eng.stats.edges_deleted == 2 * len(dels)
    assert eng.stats.repair_del_rows > 0


# --------------------------------------------------------------- the policy
def test_should_repair_del_crossover():
    """The byte model: few affected rows repair, many re-solve, zero is
    a noop the policy never needs to price."""
    from repro.apsp import plan

    assert plan.should_repair_del(1024, 8)
    assert not plan.should_repair_del(1024, 900)
    assert not plan.should_repair_del(1024, 0)
    # threshold scales the re-solve budget
    a = 300
    assert plan.should_repair_del(1024, a, threshold=2.0) or not \
        plan.should_repair_del(1024, a, threshold=0.1)


def test_repair_del_rejects_bad_inputs():
    from repro.apsp import ApspEngine

    eng = ApspEngine(method="fused")
    w, _, _ = repair_scenario("min_plus", 32)
    r0 = eng.solve(w, successors=True)
    with pytest.raises(ValueError):  # dist must be square
        eng.repair_del(np.zeros(5, np.float32), np.asarray(w), [(0, 1, 1.0)])
    with pytest.raises(ValueError):  # w must match dist's shape
        eng.repair_del(r0.dist, np.zeros((8, 8), np.float32), [(0, 1, 1.0)])
    ieng = ApspEngine(method="fused", dtype=jnp.int16)
    wi = np.ones((8, 8), np.int16) - np.eye(8, dtype=np.int16)
    ri = ieng.solve(wi)
    with pytest.raises(ValueError):  # int16 has no strict-< succ lowering
        ieng.repair_del(ri.dist, wi, [(0, 1, 1)],
                        succ=np.zeros((8, 8), np.int32))


# -------------------------------------------- 8-device mesh repair_del
def _run_dist_repair_del(*args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"  # virtual host devices
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.fw_dist_check",
         "--devices", "8", "--n", "64", "--repair-del", *args],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return res.stdout


@pytest.mark.parametrize("srname", SR_NAMES)
def test_distributed_repair_del_bitwise(srname):
    """Mesh repair_del == single-device repair_del == full re-solve,
    bitwise, warm cache flat (subprocess: XLA host-device count locks at
    first jax init)."""
    assert "OK repair_del" in _run_dist_repair_del("--semiring", srname)
