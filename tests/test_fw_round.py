"""Fused multi-stage round kernel (kernels.fw_round) acceptance surface.

  * bit-identity: the fused one-dispatch round is bitwise equal to the seed
    4-kernel lowering (``fw_staged(unroll_rounds=True, fused=False)``)
    across semirings, dtypes, and round counts — not merely allclose;
  * the batch grid: (B,n,n) inputs through fw_round / the phase kernels /
    fw_staged are bitwise equal to B per-graph runs, for any batch block;
  * successor tracking through the fused round
    (``fw_round_with_successors`` / ``fw_staged_with_successors``)
    bit-matches ``fw_blocked_with_successors``, single and batched, in both
    the Pallas and the execution-grade XLA ("ref") lowerings;
  * per-round pallas_call count drops from 4 to 1 in the jaxpr;
  * arbitrary (non-power-of-two) n round-trips through ``solve`` padding;
  * the phase-2 band kernels fit their tile to any n (regression for the
    ``n % bt`` crash at default bt=512);
  * the plan-layer VMEM/occupancy model (now batch-aware) and autotune
    sweep are coherent.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apsp import pack_reachability, plan, solve, unpack_reachability
from repro.core.floyd_warshall import fw_naive
from repro.core.graph import random_digraph
from repro.core.paths import _init_successors, fw_blocked_with_successors
from repro.core.semiring import (
    I16_INF,
    LOWERED_SEMIRINGS,
    MAX_MIN,
    MIN_PLUS,
    PACK_LANES,
    SEMIRINGS,
)
from repro.core.staged import fw_staged, fw_staged_with_successors
from repro.kernels.fw_phase1 import fw_phase1
from repro.kernels.fw_phase2 import fw_phase2_col, fw_phase2_row
from repro.kernels.fw_round import (
    _round_order,
    fw_round,
    fw_round_bordered,
    fw_round_with_successors,
)
from repro.kernels.minplus_matmul import semiring_matmul
from repro.kernels.ref import (
    fw_phase2_col_ref,
    fw_phase2_row_ref,
    fw_round_bordered_ref,
    fw_round_ref,
    fw_round_with_successors_ref,
)


def _graph(n, seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    w = rng.uniform(1.0, 10.0, size=(n, n)).astype(np.float32)
    np.fill_diagonal(w, 0.0)
    return jnp.asarray(w, dtype)


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


# ------------------------------------------------------------- bit-identity
@pytest.mark.parametrize("name", sorted(SEMIRINGS))
def test_fused_matches_seed_lowering_bitwise(name):
    """The tentpole: fused fori round == seed unrolled 4-kernel round,
    bit for bit, for every semiring (idempotent or not)."""
    sr = SEMIRINGS[name]
    rng = np.random.default_rng(17)
    if name == "or_and":
        w = (rng.uniform(size=(96, 96)) < 0.1).astype(np.float32)
        np.fill_diagonal(w, 1.0)
    elif name == "plus_mul":
        w = rng.uniform(0.0, 0.01, size=(96, 96)).astype(np.float32)
    else:
        w = rng.uniform(1.0, 10.0, size=(96, 96)).astype(np.float32)
        np.fill_diagonal(w, 0.0)
    w = jnp.asarray(w)
    kw = dict(block_size=32, bm=32, bn=32, bk=16, semiring=sr, interpret=True)
    fused = fw_staged(w, **kw)  # fused fori is the default lowering
    seed = fw_staged(w, unroll_rounds=True, fused=False, **kw)
    assert np.array_equal(np.asarray(fused), np.asarray(seed))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sr", [MIN_PLUS, MAX_MIN], ids=["min_plus", "max_min"])
def test_fused_bit_identity_dtypes(sr, dtype):
    w = _graph(128, seed=5, dtype=dtype)
    kw = dict(block_size=32, bk=32, semiring=sr, interpret=True)
    fused = fw_staged(w, **kw)
    seed = fw_staged(w, unroll_rounds=True, fused=False, **kw)
    assert fused.dtype == dtype
    assert np.array_equal(np.asarray(fused, np.float32),
                          np.asarray(seed, np.float32))


@pytest.mark.parametrize("n,s,bk", [(96, 32, 8), (64, 64, 64), (160, 32, 32)])
def test_fw_round_matches_legacy_round_sequence(n, s, bk):
    """Round-by-round: one fw_round call == the 4-dispatch phase sequence."""

    def legacy_round(w, b):
        o = b * s
        diag = fw_phase1(jax.lax.dynamic_slice(w, (o, o), (s, s)), interpret=True)
        rb = fw_phase2_row(diag, jax.lax.dynamic_slice(w, (o, 0), (s, n)),
                           interpret=True)
        rb = jax.lax.dynamic_update_slice(rb, diag, (0, o))
        cb = fw_phase2_col(diag, jax.lax.dynamic_slice(w, (0, o), (n, s)),
                           interpret=True)
        cb = jax.lax.dynamic_update_slice(cb, diag, (o, 0))
        w = jax.lax.dynamic_update_slice(w, rb, (o, 0))
        w = jax.lax.dynamic_update_slice(w, cb, (0, o))
        return semiring_matmul(cb, rb, w, bm=min(256, n), bn=min(256, n),
                               bk=min(bk, s), interpret=True)

    wl = wf = _graph(n, seed=n)
    for b in range(n // s):
        wl = legacy_round(wl, b)
        wf = fw_round(wf, b, block_size=s, bk=bk, interpret=True)
        assert np.array_equal(np.asarray(wl), np.asarray(wf)), f"round {b}"


# ------------------------------------------- phase 3's lane-broadcast cache
@pytest.mark.parametrize(
    "case", ["min_plus", "plus_mul", "or_and_packed", "batched"])
def test_fw_round_lane_cache_bitwise(case):
    """Phase 3 broadcasts a tile row's a-operand columns once, into a cache
    rebuilt at the row's first step, and every step of the row loads them.
    With T = 4 tile rows (four fills of the cache a round) and the pivot at
    the first, a middle and the last tile, each round bit-matches the XLA
    twin and the uncached bk-chunk lowering (variant="unroll")."""
    s, n = 32, 128
    sr, batch_block = MIN_PLUS, None
    if case == "plus_mul":
        sr = SEMIRINGS["plus_mul"]
        rng = np.random.default_rng(29)
        w = jnp.asarray(rng.uniform(0.0, 0.01, (n, n)).astype(np.float32))
    elif case == "or_and_packed":
        sr = LOWERED_SEMIRINGS["or_and_packed"]
        w = _lowered_data(sr, (n, n), seed=29)
    elif case == "batched":
        w, batch_block = _batch(4, n, seed0=29), 2
    else:
        w = _graph(n, seed=29)
    kw = dict(block_size=s, bk=16, semiring=sr)
    for b in (0, 2, n // s - 1):
        got = fw_round(w, b, batch_block=batch_block, interpret=True, **kw)
        uncached = fw_round(w, b, batch_block=batch_block, variant="unroll",
                            interpret=True, **kw)
        twin = fw_round_ref(w, b, **kw)
        assert got.dtype == w.dtype
        assert np.array_equal(np.asarray(got), np.asarray(twin)), f"round {b}"
        assert np.array_equal(np.asarray(got), np.asarray(uncached))
        w = got


# ------------------------------------------------------------- batch grid
def _batch(B, n, seed0=0):
    return jnp.asarray(np.stack(
        [random_digraph(n, density=0.6, seed=seed0 + i) for i in range(B)]
    ))


@pytest.mark.parametrize("batch_block", [None, 1, 2])
def test_fw_round_batched_bitwise_per_graph(batch_block):
    """(B,n,n) through the leading batch grid dim == B per-graph rounds."""
    B, n, s = 4, 64, 32
    wb = _batch(B, n)
    got = wb
    want = [wb[i] for i in range(B)]
    for b in range(n // s):
        got = fw_round(got, b, block_size=s, bk=16,
                       batch_block=batch_block, interpret=True)
        want = [fw_round(g, b, block_size=s, bk=16, interpret=True)
                for g in want]
    for i in range(B):
        assert np.array_equal(np.asarray(got[i]), np.asarray(want[i]))


def test_fw_round_batch_block_must_divide():
    wb = _batch(3, 32)
    with pytest.raises(ValueError):
        fw_round(wb, 0, block_size=32, batch_block=2, interpret=True)


@pytest.mark.parametrize("name", ["min_plus", "plus_mul"])
@pytest.mark.parametrize("fused", [True, False])
def test_fw_staged_batched_bitwise_per_graph(name, fused):
    """Both round lowerings run the batch natively, bitwise == per-graph
    (plus_mul included: non-idempotent ⊕ catches any chain reordering)."""
    sr = SEMIRINGS[name]
    rng = np.random.default_rng(3)
    if name == "plus_mul":
        wb = jnp.asarray(rng.uniform(0, 0.01, size=(3, 64, 64)).astype(np.float32))
    else:
        wb = _batch(3, 64, seed0=9)
    kw = dict(block_size=32, bm=32, bn=32, bk=16, semiring=sr, interpret=True)
    batched = fw_staged(wb, fused=fused, **kw)
    for i in range(3):
        single = fw_staged(wb[i], fused=fused, **kw)
        assert np.array_equal(np.asarray(batched[i]), np.asarray(single))


def test_phase_kernels_batched_bitwise():
    B, s, n = 3, 32, 96
    wb = _batch(B, n, seed0=4)
    diag = fw_phase1(wb[:, :s, :s], interpret=True)
    row = fw_phase2_row(diag, wb[:, :s, :], interpret=True)
    col = fw_phase2_col(diag, wb[:, :, :s], interpret=True)
    mm = semiring_matmul(wb, wb, wb, bm=32, bn=32, bk=16, interpret=True)
    for i in range(B):
        assert np.array_equal(
            np.asarray(diag[i]), np.asarray(fw_phase1(wb[i, :s, :s], interpret=True)))
        assert np.array_equal(
            np.asarray(row[i]),
            np.asarray(fw_phase2_row(diag[i], wb[i, :s, :], interpret=True)))
        assert np.array_equal(
            np.asarray(col[i]),
            np.asarray(fw_phase2_col(diag[i], wb[i, :, :s], interpret=True)))
        assert np.array_equal(
            np.asarray(mm[i]),
            np.asarray(semiring_matmul(wb[i], wb[i], wb[i], bm=32, bn=32,
                                       bk=16, interpret=True)))


# ----------------------------------------------- fused successor tracking
@pytest.mark.parametrize("lowering", ["pallas", "ref"])
def test_fused_successors_bit_match_blocked(lowering):
    """The satellite acceptance: the fused successor round == the blocked
    successor path, distances AND next hops, bit for bit."""
    n, s = 96, 32
    w = _batch(1, n, seed0=2)[0]
    d_ref, s_ref = fw_blocked_with_successors(w, block_size=s)
    d_got, s_got = fw_staged_with_successors(
        w, block_size=s, interpret=True, lowering=lowering)
    assert np.array_equal(np.asarray(d_got), np.asarray(d_ref))
    assert np.array_equal(np.asarray(s_got), np.asarray(s_ref))


@pytest.mark.parametrize("lowering", ["pallas", "ref"])
def test_fused_successors_batched(lowering):
    B, n, s = 3, 64, 32
    wb = _batch(B, n, seed0=6)
    d_got, s_got = fw_staged_with_successors(
        wb, block_size=s, interpret=True, lowering=lowering)
    for i in range(B):
        d_ref, s_ref = fw_blocked_with_successors(wb[i], block_size=s)
        assert np.array_equal(np.asarray(d_got[i]), np.asarray(d_ref))
        assert np.array_equal(np.asarray(s_got[i]), np.asarray(s_ref))


def _tie_graph(n, seed):
    """Integer weights from {1, 2, 3} with 30% missing edges: equal-length
    paths abound (ties), and a few vertices have no out-edges, so their
    rows stay unreachable (next hop -1)."""
    rng = np.random.default_rng(seed)
    w = rng.choice(np.float32([1.0, 2.0, 3.0]), size=(n, n))
    w[rng.random((n, n)) < 0.3] = np.inf
    w[rng.choice(n, size=3, replace=False)] = np.inf
    np.fill_diagonal(w, 0.0)
    return w


@pytest.mark.parametrize("batched", [False, True], ids=["single", "batched"])
@pytest.mark.parametrize("s", [32, 12], ids=["grouped", "ungrouped"])
def test_successor_round_ties_bitwise(s, batched):
    """Phase 3 picks its columns/rows a group at a time (a group per 8 steps
    when 8 divides the block, else per step); on tie-heavy weights every
    round still bit-matches the XLA twin, and the whole solve the blocked
    successor path, next hops included."""
    n = 96
    w = jnp.asarray(np.stack([_tie_graph(n, 40 + i) for i in range(2)])
                    if batched else _tie_graph(n, 40))
    d, sc = w, _init_successors(w)
    for b in range(n // s):
        d_want, s_want = fw_round_with_successors_ref(d, sc, b, block_size=s)
        d, sc = fw_round_with_successors(d, sc, b, block_size=s,
                                         interpret=True)
        assert np.array_equal(np.asarray(d), np.asarray(d_want))
        assert np.array_equal(np.asarray(sc), np.asarray(s_want))
    assert (np.asarray(sc) == -1).any()  # unreachable pairs were exercised
    ws = w if batched else w[None]
    d_got, s_got = fw_staged_with_successors(w, block_size=s, interpret=True)
    for wi, di, si in zip(ws, d_got.reshape(ws.shape), s_got.reshape(ws.shape)):
        d_ref, s_ref = fw_blocked_with_successors(wi, block_size=s)
        assert np.array_equal(np.asarray(di), np.asarray(d_ref))
        assert np.array_equal(np.asarray(si), np.asarray(s_ref))


def test_fw_round_with_successors_rejects_bad_shapes():
    w = jnp.zeros((32, 32))
    with pytest.raises(ValueError):
        fw_round_with_successors(w, jnp.zeros((32, 16), jnp.int32), 0,
                                 block_size=32, interpret=True)


def test_solve_fused_successors_native():
    """solve(method='fused', successors=True) no longer falls back to the
    blocked multi-dispatch path — and still reproduces its tables."""
    w = random_digraph(70, density=0.5, seed=11)
    res = solve(w, method="fused", block_size=32, successors=True)
    assert res.method == "fused"  # no silent fallback
    ref = solve(w, method="blocked", block_size=32, successors=True)
    assert np.array_equal(np.asarray(res.dist), np.asarray(ref.dist))
    assert np.array_equal(np.asarray(res.succ), np.asarray(ref.succ))


# ------------------------------------------------- ref (XLA) round lowering
@pytest.mark.parametrize("name", sorted(SEMIRINGS))
def test_ref_round_lowering_bitwise(name):
    """fused="ref" (what solve runs on CPU) == the Pallas interpreter,
    bit for bit, on every semiring."""
    sr = SEMIRINGS[name]
    rng = np.random.default_rng(17)
    if name == "or_and":
        w = (rng.uniform(size=(64, 64)) < 0.1).astype(np.float32)
        np.fill_diagonal(w, 1.0)
    elif name == "plus_mul":
        w = rng.uniform(0.0, 0.01, size=(64, 64)).astype(np.float32)
    else:
        w = rng.uniform(1.0, 10.0, size=(64, 64)).astype(np.float32)
        np.fill_diagonal(w, 0.0)
    w = jnp.asarray(w)
    kw = dict(block_size=32, bk=16, semiring=sr)
    pallas = fw_staged(w, interpret=True, **kw)
    ref = fw_staged(w, fused="ref", **kw)
    assert np.array_equal(np.asarray(pallas), np.asarray(ref))


# -------------------------------------------------------- solve() integration
@pytest.mark.parametrize("n", [90, 100])
def test_solve_fused_non_pow2_n(n):
    w = random_digraph(n, density=0.4, seed=n)
    res = solve(w, method="fused", block_size=32)
    assert res.method == "fused" and res.dist.shape == (n, n)
    assert res.padded_n % 32 == 0
    want = np.asarray(fw_naive(jnp.asarray(w)))
    np.testing.assert_allclose(np.asarray(res.dist), want, rtol=1e-5, atol=1e-5)


def test_solve_fused_batched_matches_per_graph():
    wb = np.stack([random_digraph(70, density=0.4, seed=i) for i in range(3)])
    res = solve(wb, method="fused", block_size=32)
    assert res.batched and res.dist.shape == (3, 70, 70)
    for i in range(3):
        single = solve(wb[i], method="fused", block_size=32)
        assert np.array_equal(np.asarray(res.dist[i]), np.asarray(single.dist))


def test_single_round_graph():
    # T=1: the whole matrix is the pivot tile; phase 1 + its self-relaxation.
    w = _graph(32, seed=2)
    fused = fw_staged(w, block_size=32, interpret=True)
    seed = fw_staged(w, block_size=32, unroll_rounds=True, fused=False,
                     interpret=True)
    assert np.array_equal(np.asarray(fused), np.asarray(seed))


def test_fw_round_rejects_bad_shapes():
    with pytest.raises(ValueError):
        fw_round(jnp.zeros((48, 48)), 0, block_size=32, interpret=True)
    with pytest.raises(ValueError):
        fw_round(jnp.zeros((32, 48)), 0, block_size=16, interpret=True)


# --------------------------------------------------------- trace/dispatch size
def test_per_round_dispatch_count_dropped():
    """The acceptance criterion: ≥4 pallas_calls per round → 1."""

    def trace(n, **kw):
        w = jnp.zeros((n, n), jnp.float32)
        return jax.make_jaxpr(
            lambda x: fw_staged(x, block_size=128, interpret=True, **kw)
        )(w)

    rounds = 512 // 128
    # unrolled traces expose the per-round count directly:
    assert _count_pallas_calls(trace(512, unroll_rounds=True, fused=True)) == rounds
    assert _count_pallas_calls(trace(512, unroll_rounds=True, fused=False)) == 4 * rounds
    # and the fori lowering holds exactly ONE pallas_call total:
    assert _count_pallas_calls(trace(512)) == 1
    assert _count_pallas_calls(trace(2048)) == 1


def test_round_order_covers_every_tile():
    for T, b in [(1, 0), (3, 0), (3, 2), (5, 3)]:
        oi, oj = _round_order(jnp.int32(b), T)
        oi, oj = np.asarray(oi), np.asarray(oj)
        assert oi.shape == (T * T + 2 * T - 1,)
        # step 0 is the pivot tile; band steps precede all phase-3 steps.
        assert (oi[0], oj[0]) == (b, b)
        assert (oi[1:T] == b).all() and (oj[T:2 * T - 1] == b).all()
        # phase 3 visits every tile exactly once.
        p3 = set(zip(oi[2 * T - 1:].tolist(), oj[2 * T - 1:].tolist()))
        assert p3 == {(i, j) for i in range(T) for j in range(T)}


# -------------------------------------------- phase-2 band fitting regression
def test_phase2_fits_block_to_any_n():
    # Default bt=512 used to raise for any n not divisible by it (n=640).
    s, n = 32, 640
    diag = fw_phase1(_graph(s, seed=1), interpret=True)
    band = jnp.asarray(np.random.default_rng(2).uniform(1, 10, (s, n)),
                       jnp.float32)
    got = fw_phase2_row(diag, band, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(fw_phase2_row_ref(diag, band)))
    got = fw_phase2_col(diag, band.T, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(fw_phase2_col_ref(diag, band.T)))


# ------------------------------------------------------------ plan-layer model
def test_plan_fused_model():
    # scratch bands: 2·s·n + 2·2·s² words, plus the body's live (s,s) tiles
    # on Mosaic's stack and the fori phase 3's lane cache of s³ words.
    live = plan.LIVE_TILES * 128 * 128
    assert plan.fused_round_vmem_bytes(1024, 128, 32) == (
        (2 * 128 * 1024 + 4 * 128 * 128 + live + 128 ** 3) * 4
    )
    # broadcast variant adds the (s, bk, s) product transient and no cache.
    assert plan.fused_round_vmem_bytes(1024, 128, 32, variant="broadcast") == (
        (2 * 128 * 1024 + 4 * 128 * 128 + live + 128 * 32 * 128) * 4
    )
    assert plan.fused_round_steps(1024, 128) == 8 * 8 + 2 * 8 - 1
    # one read + one write per grid step, (s,s) words each.
    assert plan.fused_round_hbm_bytes(1024, 128) == 2 * 79 * 128 * 128 * 4


def test_plan_counts_the_lane_cache():
    # The fori round's phase 3 keeps s³ 32-bit words per graph (16-bit
    # storage widens); the other variants keep no cache.
    s = 128
    for word in (4, 2):
        for n in (1024, 16384):
            fori = plan.fused_round_vmem_bytes(n, s, 32, word=word, batch=3)
            unroll = plan.fused_round_vmem_bytes(
                n, s, 32, word=word, variant="unroll", batch=3)
            assert fori - unroll == 3 * s ** 3 * 4
        fori = plan.bordered_round_vmem_bytes(384, 256, s, 32, word=word,
                                              batch=2)
        unroll = plan.bordered_round_vmem_bytes(
            384, 256, s, 32, word=word, variant="unroll", batch=2)
        assert fori - unroll == 2 * s ** 3 * 4
    assert plan.lane_cache_bytes(s, variant="broadcast") == 0
    # At n=16384 f32 one graph's round asks about 25 MiB: 17 + 8 of cache.
    assert plan.fused_round_vmem_bytes(16384, s, 32) == (
        (2 * s * 16384 + (4 + plan.LIVE_TILES) * s * s) * 4 + s ** 3 * 4)


@pytest.mark.parametrize("n,want", [(2048, 8), (4096, 4), (16384, 4)])
def test_plan_auto_batch_block_fits_budget(n, want):
    # The fattest divisor of B = 16 whose round, lane cache included,
    # fits the v5e's 100 MiB scoped-VMEM budget.
    budget = 100 << 20
    bb = plan.auto_batch_block(16, n, 128, vmem_budget=budget)
    assert bb == want
    assert plan.fused_round_vmem_bytes(n, 128, 32, batch=bb) <= budget
    fatter = min(d for d in (2, 4, 8, 16) if d > bb)
    assert plan.fused_round_vmem_bytes(n, 128, 32, batch=fatter) > budget


def test_plan_batch_models():
    # per-graph scratch bands: the footprint scales linearly in batch block.
    one = plan.fused_round_vmem_bytes(1024, 128, 32)
    assert plan.fused_round_vmem_bytes(1024, 128, 32, batch=4) == 4 * one
    assert plan.fused_round_hbm_bytes(1024, 128, batch=8) == (
        8 * plan.fused_round_hbm_bytes(1024, 128)
    )
    assert plan.fused_round_steps(1024, 128, batch=2) == (
        2 * plan.fused_round_steps(1024, 128)
    )
    # auto_batch_block: fattest divisor of B under the budget; 1 if nothing
    # fatter fits.  The successor round doubles bands, tiles and live
    # values but keeps no lane cache, so it plans with its own footprint.
    assert plan.auto_batch_block(16, 128, 32) == 16
    assert plan.auto_batch_block(16, 128, 32, vmem_budget=2 * one) >= 1
    succ = plan.fused_round_vmem_bytes(1024, 128, 32, successors=True)
    assert succ == 2 * plan.fused_round_vmem_bytes(
        1024, 128, 32, variant="unroll")
    tight = plan.auto_batch_block(
        16, 1024, 128, vmem_budget=4 * one, successors=False)
    tight_s = plan.auto_batch_block(
        16, 1024, 128, vmem_budget=4 * one, successors=True)
    assert tight == 4
    assert tight_s == max(d for d in (1, 2, 4, 8, 16) if d * succ <= 4 * one)
    assert 16 % plan.auto_batch_block(16, 1024, 128) == 0
    with pytest.raises(ValueError):
        plan.auto_batch_block(0, 128, 32)
    # batched candidates carry batch_block and scale totals by the batch.
    cands = plan.fw_candidates(1024, batch=8)
    fused = [c for c in cands if c["impl"] == "fused"]
    assert fused and all(8 % c["batch_block"] == 0 for c in fused)
    base = {(c["impl"], c["block_size"], c["bm"], c["bk"]): c
            for c in plan.fw_candidates(1024)}
    for c in cands:
        b = base[(c["impl"], c["block_size"], c["bm"], c["bk"])]
        assert c["hbm_bytes_per_round"] == 8 * b["hbm_bytes_per_round"]


def test_plan_staging_depth_follows_the_kernels():
    # The TPU "fori" kernels do not read bk: one candidate per tile, bk = s.
    assert plan.kernel_bk(128, 32) == 128
    assert plan.kernel_bk(128, 32, variant="unroll") == 32
    assert plan.kernel_bk(128, 256, variant="broadcast") == 128
    assert plan.kernel_bk(128, 32, backend="ref") == 32
    kw = dict(block_sizes=(64, 128), bks=(8, 16, 32, 64, 128))
    fori = plan.fw_candidates(512, **kw)
    assert {(c["impl"], c["block_size"], c["bk"]) for c in fori} == {
        (impl, s, s) for impl in ("fused", "staged") for s in (64, 128)
    }
    unroll = plan.fw_candidates(512, variant="unroll", **kw)
    assert {c["bk"] for c in unroll if c["block_size"] == 128} == {
        8, 16, 32, 64, 128
    }
    # Staged candidates count and charge the slices semiring_matmul streams.
    assert plan.grid_k_step(128, 16) == 128
    assert plan.grid_k_step(256, 256) == 256
    assert plan.grid_k_step(96, 32) == 96
    for c in unroll:
        if c["impl"] == "staged":
            kb = plan.grid_k_step(c["block_size"], c["bk"])
            assert c["steps_per_round"] == (512 // c["bm"]) ** 2 * (
                c["block_size"] // kb)
            assert c["vmem_bytes"] == plan.phase3_vmem_bytes(
                c["bm"], c["bm"], kb, fused=True)


def test_plan_candidates_and_autotune():
    cands = plan.fw_candidates(1024)
    assert cands and all(c["vmem_bytes"] <= 128 << 20 for c in cands)
    assert {c["impl"] for c in cands} == {"fused", "staged"}
    # a tiny budget filters the fat fused scratch but keeps small tiles.
    tight = plan.fw_candidates(1024, vmem_budget=300 * 1024)
    assert tight and all(c["vmem_bytes"] <= 300 * 1024 for c in tight)
    # model ranking: total-traffic ordering, fused preferred on ties.
    ranked = plan.autotune_fw(1024)
    totals = [c["hbm_bytes_total"] for c in ranked]
    assert totals == sorted(totals)
    assert ranked[0]["impl"] == "fused"
    # measured ranking consumes a callback and sorts by it.
    measured = plan.autotune_fw(
        256, measure=lambda c: c["block_size"] * 1e-6, top=3
    )
    assert [c["us"] for c in measured] == sorted(c["us"] for c in measured)
    assert all("us" in c for c in measured)


# ----------------------------------------- bandwidth-lean storage lowerings
def _lowered_data(sr, shape, seed):
    """Random input in a lowering's native storage: int32 words for the
    bit-packed closure, {0,1} int16 for or_and_i16, int16 with ⊕-identity
    sentinels sprinkled ("missing edges") for the tropical lowerings."""
    rng = np.random.default_rng(seed)
    if sr.packed:
        words = rng.integers(0, 1 << 32, size=shape, dtype=np.uint64)
        return jnp.asarray(words.astype(np.uint32).view(np.int32))
    if sr.name == "or_and_i16":
        return jnp.asarray((rng.uniform(size=shape) < 0.25).astype(np.int16))
    v = rng.integers(-40, 40, size=shape).astype(np.int16)
    v[rng.uniform(size=shape) < 0.15] = np.int16(sr.zero)
    return jnp.asarray(v)


@pytest.mark.parametrize("name", sorted(LOWERED_SEMIRINGS))
def test_lowered_round_bitwise(name):
    """Every storage lowering (bit-packed or_and, saturating int16 tropical)
    through the fused Pallas round == the seed 4-kernel lowering == the XLA
    "ref" twin, bit for bit — the kernels are dtype/operator generic."""
    sr = LOWERED_SEMIRINGS[name]
    w = _lowered_data(sr, (96, 96), seed=13)
    kw = dict(block_size=32, bk=16, semiring=sr)
    fused = fw_staged(w, interpret=True, **kw)
    unrolled = fw_staged(w, unroll_rounds=True, fused=False, interpret=True,
                         **kw)
    ref = fw_staged(w, fused="ref", **kw)
    assert fused.dtype == w.dtype
    assert np.array_equal(np.asarray(fused), np.asarray(unrolled))
    assert np.array_equal(np.asarray(fused), np.asarray(ref))


def test_bf16_ref_round_bitwise():
    # bf16 closes the dtype matrix: Pallas interpreter == execution-grade ref.
    w = _graph(96, seed=7, dtype=jnp.bfloat16)
    kw = dict(block_size=32, bk=16, semiring=MIN_PLUS)
    pallas = fw_staged(w, interpret=True, **kw)
    ref = fw_staged(w, fused="ref", **kw)
    assert pallas.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(pallas, np.float32),
                          np.asarray(ref, np.float32))


@pytest.mark.parametrize("owner", [(-1, -1), (1, 1), (2, 1)],
                         ids=["ghost", "owner", "owner_last_row"])
@pytest.mark.parametrize(
    "case", ["min_plus_i16", "max_plus_i16", "or_and_packed", "bf16", "f32"])
def test_bordered_round_lowerings_bitwise(case, owner):
    """The distributed bordered round stays bitwise-equal to its XLA twin
    through every bandwidth-lean lowering (acceptance criterion), with its
    three tile rows each relaxed against their own lane cache."""
    s, rows, cols = 32, 96, 64
    if case in ("bf16", "f32"):
        sr = MIN_PLUS
        rng = np.random.default_rng(21)
        w = jnp.asarray(rng.uniform(1, 10, (rows, cols)).astype(np.float32),
                        jnp.bfloat16 if case == "bf16" else jnp.float32)
    else:
        sr = LOWERED_SEMIRINGS[case]
        w = _lowered_data(sr, (rows, cols), seed=21)
    orow, ocol = owner
    kw = dict(block_size=s, bk=16, semiring=sr)
    try:
        got = fw_round_bordered(w, orow, ocol, interpret=True, **kw)
    except NotImplementedError:
        pytest.skip("pallas TPU lowering unavailable in this build")
    want = fw_round_bordered_ref(w, orow, ocol, **kw)
    assert got.dtype == w.dtype
    to_np = (lambda x: np.asarray(x, np.float32)) if case == "bf16" else np.asarray
    assert np.array_equal(to_np(got), to_np(want))


# ------------------------------------------------ packed closure via solve()
def test_pack_unpack_roundtrip_and_layout():
    rng = np.random.default_rng(9)
    for B in (1, 3, PACK_LANES, PACK_LANES + 7):
        bits = (rng.uniform(size=(B, 6, 6)) < 0.5).astype(np.float32)
        words = pack_reachability(bits)
        assert words.dtype == jnp.int32
        assert words.shape == (-(-B // PACK_LANES), 6, 6)
        back = unpack_reachability(words, count=B)
        assert np.array_equal(np.asarray(back), bits)
    # LSB-first layout: graph g lives at word g // 32, bit g % 32.
    bits = (rng.uniform(size=(3, 6, 6)) < 0.5).astype(np.float32)
    w0 = np.asarray(pack_reachability(bits))[0]
    for g in range(3):
        assert np.array_equal(((w0 >> g) & 1).astype(np.float32), bits[g])


def test_packed_solve_matches_unpacked_all_counts():
    """pack → solve(packed=True) → unpack == the unpacked or_and solve,
    bitwise, for every graph count B ∈ 1..32 (one word's worth of lanes)."""
    n = 24
    rng = np.random.default_rng(5)
    pool = (rng.uniform(size=(PACK_LANES, n, n)) < 0.12).astype(np.float32)
    for g in range(PACK_LANES):
        np.fill_diagonal(pool[g], 1.0)
    want = np.asarray(
        solve(jnp.asarray(pool), semiring="or_and", method="fused",
              block_size=8).dist)
    for B in range(1, PACK_LANES + 1):
        res = solve(pool[:B], semiring="or_and", packed=True, method="fused",
                    block_size=8)
        assert res.dist.shape == (B, n, n)
        assert np.array_equal(np.asarray(res.dist), want[:B]), f"B={B}"


def test_packed_solve_single_graph_2d():
    # A 2-D (n, n) input round-trips through the pack adapter unchanged.
    rng = np.random.default_rng(6)
    w = (rng.uniform(size=(40, 40)) < 0.15).astype(np.float32)
    np.fill_diagonal(w, 1.0)
    res = solve(w, semiring="or_and", packed=True, method="fused",
                block_size=32)
    ref = solve(w, semiring="or_and", method="fused", block_size=32)
    assert res.dist.shape == (40, 40)
    assert np.array_equal(np.asarray(res.dist), np.asarray(ref.dist))


def test_packed_solve_rejects_successors():
    w = (np.random.default_rng(1).uniform(size=(16, 16)) < 0.2)
    with pytest.raises(ValueError):
        solve(w.astype(np.float32), semiring="or_and", packed=True,
              successors=True)


def test_solve_int16_dtype_end_to_end():
    """dtype=int16 through solve(): inf edges coerce to the I16_INF
    sentinel, distances bit-match the f32 solve on integer weights."""
    rng = np.random.default_rng(8)
    w = rng.integers(1, 50, size=(60, 60)).astype(np.float32)
    w[rng.uniform(size=(60, 60)) < 0.5] = np.inf
    np.fill_diagonal(w, 0.0)
    res = solve(w, dtype=jnp.int16, method="fused", block_size=32)
    assert res.dist.dtype == jnp.int16
    want = np.asarray(solve(w, method="fused", block_size=32).dist)
    got = np.asarray(res.dist).astype(np.float32)
    got[got == I16_INF] = np.inf
    assert np.array_equal(got, want)
