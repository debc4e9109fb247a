"""GPU (Triton) fused-round lowering acceptance surface.

The Triton round (``kernels.fw_round_gpu``) must be bitwise equal — in
Pallas interpret mode, which is how this container (and CI) executes it —
to the XLA ref twins and the TPU fused kernel on every semiring × storage
lowering, batched, bordered, and with successor tracking.  On top of the
kernel itself:

  * backend resolution (``compat.resolve_pallas_backend`` /
    ``solve(backend=)``) dispatches the right lowering and preserves the
    historical auto policy;
  * ``ApspEngine(backend=)`` keys executables per backend with the
    warm-cache no-retrace guarantee intact;
  * ``plan.fw_candidates(backend=)`` emits per-backend candidate sets (no
    VMEM-model candidates leak into a non-TPU pool) and ``autotune_fw``
    stamps every result with the resolved backend.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apsp import ApspEngine, plan, solve
from repro.core.semiring import (
    LOWERED_SEMIRINGS,
    MIN_PLUS,
    SEMIRINGS,
)
from repro.core.staged import fw_staged, fw_staged_with_successors
from repro.kernels.fw_round import fw_round, fw_round_with_successors
from repro.kernels.fw_round_gpu import (
    fw_round_bordered_gpu,
    fw_round_gpu,
    fw_round_with_successors_gpu,
)
from repro.kernels.ref import (
    fw_round_bordered_ref,
    fw_round_ref,
    fw_round_with_successors_ref,
)
from repro.utils import compat


def _graph(n, seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    w = rng.uniform(1.0, 10.0, size=(n, n)).astype(np.float32)
    np.fill_diagonal(w, 0.0)
    return jnp.asarray(w, dtype)


def _lowered_data(sr, shape, seed):
    """Random input in a lowering's native storage (see test_fw_round)."""
    rng = np.random.default_rng(seed)
    if sr.packed:
        words = rng.integers(0, 1 << 32, size=shape, dtype=np.uint64)
        return jnp.asarray(words.astype(np.uint32).view(np.int32))
    if sr.name == "or_and_i16":
        return jnp.asarray((rng.uniform(size=shape) < 0.25).astype(np.int16))
    v = rng.integers(-40, 40, size=shape).astype(np.int16)
    v[rng.uniform(size=shape) < 0.15] = np.int16(sr.zero)
    return jnp.asarray(v)


def _eq(a, b):
    # bf16 compares via f32 view; everything else exact as-is.
    if a.dtype == jnp.bfloat16:
        return np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
    return np.array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------ kernel bit-identity
@pytest.mark.parametrize("name", sorted(SEMIRINGS))
def test_gpu_round_bitwise_all_semirings(name):
    """Triton round == XLA ref twin == TPU fused kernel, per round, f32."""
    sr = SEMIRINGS[name]
    w = _graph(96, seed=3)
    for b in (0, 2):
        got = fw_round_gpu(w, b, block_size=32, bk=16, semiring=sr,
                           interpret=True)
        ref = fw_round_ref(w, b, block_size=32, bk=16, semiring=sr)
        tpu = fw_round(w, b, block_size=32, bk=16, semiring=sr,
                       interpret=True)
        assert got.dtype == w.dtype
        assert _eq(got, ref)
        assert _eq(got, tpu)


@pytest.mark.parametrize("name", sorted(LOWERED_SEMIRINGS))
def test_gpu_round_bitwise_storage_lowerings(name):
    """Every storage lowering (bit-packed or_and, saturating int16) through
    the Triton round == the ref twin, bit for bit."""
    sr = LOWERED_SEMIRINGS[name]
    w = _lowered_data(sr, (96, 96), seed=13)
    got = fw_round_gpu(w, 1, block_size=32, bk=16, semiring=sr,
                       interpret=True)
    ref = fw_round_ref(w, 1, block_size=32, bk=16, semiring=sr)
    assert got.dtype == w.dtype
    assert _eq(got, ref)


def test_gpu_round_bitwise_bf16():
    w = _graph(96, seed=7, dtype=jnp.bfloat16)
    got = fw_round_gpu(w, 1, block_size=32, bk=16, semiring=MIN_PLUS,
                       interpret=True)
    ref = fw_round_ref(w, 1, block_size=32, bk=16, semiring=MIN_PLUS)
    assert got.dtype == jnp.bfloat16
    assert _eq(got, ref)


@pytest.mark.parametrize("batch_block", [None, 1, 3])
def test_gpu_round_batched_bitwise_per_graph(batch_block):
    """(B,n,n) through the batched Triton grid == B per-graph rounds."""
    B, n, s = 3, 64, 32
    wb = jnp.stack([_graph(n, seed=40 + k) for k in range(B)])
    got = fw_round_gpu(wb, 1, block_size=s, batch_block=batch_block,
                       interpret=True)
    for k in range(B):
        one = fw_round_gpu(wb[k], 1, block_size=s, interpret=True)
        assert _eq(got[k], one)


def test_gpu_round_batch_block_must_divide():
    wb = jnp.stack([_graph(64, seed=1) for _ in range(3)])
    with pytest.raises(ValueError, match="must divide"):
        fw_round_gpu(wb, 0, block_size=32, batch_block=2, interpret=True)


@pytest.mark.parametrize("owner", [(-1, -1), (1, 1)], ids=["ghost", "owner"])
@pytest.mark.parametrize(
    "case", ["min_plus", "plus_mul", "min_plus_i16", "or_and_packed", "bf16"])
def test_gpu_bordered_round_bitwise(case, owner):
    """The bordered (distributed per-device) Triton round == its XLA twin,
    including the owner-echo splice that non-idempotent ⊕ depends on."""
    s, rows, cols = 32, 96, 64
    if case in ("min_plus", "plus_mul"):
        sr = SEMIRINGS[case]
        rng = np.random.default_rng(21)
        w = jnp.asarray(rng.uniform(1, 10, (rows, cols)).astype(np.float32))
    elif case == "bf16":
        sr = MIN_PLUS
        rng = np.random.default_rng(21)
        w = jnp.asarray(rng.uniform(1, 10, (rows, cols)).astype(np.float32),
                        jnp.bfloat16)
    else:
        sr = LOWERED_SEMIRINGS[case]
        w = _lowered_data(sr, (rows, cols), seed=21)
    orow, ocol = owner
    kw = dict(block_size=s, bk=16, semiring=sr)
    got = fw_round_bordered_gpu(w, orow, ocol, interpret=True, **kw)
    want = fw_round_bordered_ref(w, orow, ocol, variant="fori", **kw)
    assert got.dtype == w.dtype
    assert _eq(got, want)


def test_gpu_bordered_batched_bitwise():
    B, s, rows, cols = 2, 32, 64, 64
    rng = np.random.default_rng(5)
    wb = jnp.asarray(rng.uniform(1, 10, (B, rows, cols)).astype(np.float32))
    got = fw_round_bordered_gpu(wb, 1, 1, block_size=s, interpret=True)
    for k in range(B):
        one = fw_round_bordered_gpu(wb[k], 1, 1, block_size=s, interpret=True)
        assert _eq(got[k], one)


def test_gpu_successor_round_bitwise():
    """The successor-carrying Triton round == the ref twin == the TPU
    kernel (distances AND next hops), single and batched."""
    n, s = 64, 32
    rng = np.random.default_rng(11)
    mask = rng.uniform(size=(n, n)) < 0.6
    w = np.where(mask, rng.uniform(1, 10, (n, n)), np.inf).astype(np.float32)
    np.fill_diagonal(w, 0.0)
    w = jnp.asarray(w)
    succ = jnp.where(
        jnp.isfinite(w),
        jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (n, n)), -1,
    )
    for b in (0, 1):
        gw, gs = fw_round_with_successors_gpu(w, succ, b, block_size=s,
                                              interpret=True)
        rw, rs = fw_round_with_successors_ref(w, succ, b, block_size=s)
        tw, ts = fw_round_with_successors(w, succ, b, block_size=s,
                                          interpret=True)
        assert _eq(gw, rw) and _eq(gs, rs)
        assert _eq(gw, tw) and _eq(gs, ts)
    # batched == per-graph
    wb, sb = jnp.stack([w, w.T]), jnp.stack([succ, succ.T])
    gw, gs = fw_round_with_successors_gpu(wb, sb, 1, block_size=s,
                                          interpret=True)
    for k in range(2):
        ow, os_ = fw_round_with_successors_gpu(wb[k], sb[k], 1, block_size=s,
                                               interpret=True)
        assert _eq(gw[k], ow) and _eq(gs[k], os_)


def test_gpu_round_rejects_bad_shapes():
    with pytest.raises(ValueError, match="multiple|n %"):
        fw_round_gpu(_graph(48, seed=1), 0, block_size=32, interpret=True)
    w = _graph(64, seed=1)
    with pytest.raises(ValueError, match="succ shape"):
        fw_round_with_successors_gpu(
            w, jnp.zeros((32, 32), jnp.int32), 0, block_size=32,
            interpret=True,
        )


# ------------------------------------------------- staged / solve dispatch
@pytest.mark.parametrize("name", ["min_plus", "plus_mul"])
def test_fw_staged_gpu_lowering_bitwise(name):
    """fw_staged(lowering="gpu") — the whole solve loop through the Triton
    round — == lowering="ref", idempotent and non-idempotent ⊕."""
    sr = SEMIRINGS[name]
    w = _graph(96, seed=17)
    kw = dict(block_size=32, bk=16, semiring=sr)
    got = fw_staged(w, lowering="gpu", interpret=True, **kw)
    ref = fw_staged(w, lowering="ref", **kw)
    assert _eq(got, ref)


def test_fw_staged_with_successors_gpu_lowering():
    w = _graph(96, seed=19)
    gd, gs = fw_staged_with_successors(w, block_size=32, lowering="gpu",
                                       interpret=True)
    rd, rs = fw_staged_with_successors(w, block_size=32, lowering="ref")
    assert _eq(gd, rd) and _eq(gs, rs)


@pytest.mark.parametrize("backend", ["gpu", "tpu", "ref"])
def test_solve_backend_bitwise(backend):
    """solve(backend=...) returns one identical closure per backend."""
    w = np.asarray(_graph(100, seed=23))
    got = solve(w, method="fused", backend=backend)
    ref = solve(w, method="fused", backend="ref")
    assert got.method == "fused"
    assert np.array_equal(np.asarray(got.dist), np.asarray(ref.dist))


def test_solve_backend_gpu_successors_and_batched():
    rng = np.random.default_rng(29)
    wb = rng.uniform(1, 10, (3, 80, 80)).astype(np.float32)
    for k in range(3):
        np.fill_diagonal(wb[k], 0.0)
    got = solve(wb, method="fused", backend="gpu")
    ref = solve(wb, method="fused", backend="ref")
    assert np.array_equal(np.asarray(got.dist), np.asarray(ref.dist))
    gs = solve(wb[0], method="fused", backend="gpu", successors=True)
    rs = solve(wb[0], method="fused", backend="ref", successors=True)
    assert np.array_equal(np.asarray(gs.dist), np.asarray(rs.dist))
    assert np.array_equal(np.asarray(gs.succ), np.asarray(rs.succ))


def test_solve_backend_validates():
    with pytest.raises(ValueError, match="unknown backend"):
        solve(np.zeros((8, 8), np.float32), backend="cuda")


# -------------------------------------------------------- engine / PlanKey
@pytest.mark.parametrize("backend", ["gpu", "ref"])
def test_engine_backend_warm_cache_no_retrace(backend):
    """Per-backend executables: second solve on the same key retraces
    nothing, and the plan key records the resolved backend."""
    w = np.asarray(_graph(72, seed=31))
    eng = ApspEngine(method="fused", backend=backend)
    a = eng.solve(w)
    b = eng.solve(w)
    (key,) = eng._cache
    assert key.backend == backend
    assert eng._cache[key].traces == 1
    assert eng.stats.hits == 1 and eng.stats.misses == 1
    assert np.array_equal(np.asarray(a.dist), np.asarray(b.dist))


def test_engine_backends_never_share_keys():
    """The same (n, dtype) on different backends → distinct executables
    with bitwise-identical results."""
    w = np.asarray(_graph(72, seed=37))
    dists = {}
    for be in ("gpu", "ref"):
        eng = ApspEngine(method="fused", backend=be)
        dists[be] = np.asarray(eng.solve(w).dist)
        (key,) = eng._cache
        assert key.backend == be
    assert np.array_equal(dists["gpu"], dists["ref"])


def test_engine_gpu_entry_models():
    """GPU entries carry the SMEM working-set + band-traffic models, not
    TPU VMEM arithmetic."""
    w = np.asarray(_graph(72, seed=41))
    eng = ApspEngine(method="fused", backend="gpu", block_size=32)
    eng.solve(w)
    (entry,) = eng._cache.values()
    assert entry.vmem_bytes == plan.gpu_round_smem_bytes(32, 32, word=4)
    assert entry.hbm_bytes_per_round == plan.gpu_round_hbm_bytes(
        96, 32, word=4
    )


# ------------------------------------------------ backend resolution layer
def test_resolve_pallas_backend():
    plat = jax.default_backend()
    want = ("tpu" if plat == "tpu"
            else "gpu" if plat in ("gpu", "cuda", "rocm") else "ref")
    assert compat.resolve_pallas_backend("auto") == want
    for be in ("tpu", "gpu", "ref"):
        assert compat.resolve_pallas_backend(be) == be
    with pytest.raises(ValueError, match="unknown backend"):
        compat.resolve_pallas_backend("cuda")


def test_resolve_backend_interpret_wrinkle():
    """Historical policy: an explicit interpret= under backend="auto" runs
    the TPU lowering (the interpreter), never the ref fallback."""
    from repro.apsp.engine import _resolve_backend

    if jax.default_backend() in ("tpu", "gpu", "cuda", "rocm"):
        pytest.skip("wrinkle only observable on a CPU-only host")
    assert _resolve_backend("auto", None) == "ref"
    assert _resolve_backend("auto", True) == "tpu"
    assert _resolve_backend("auto", False) == "tpu"
    assert _resolve_backend("gpu", True) == "gpu"


def test_pallas_tpu_lazy_import_helper():
    """compat.pallas_tpu either yields the module or raises the documented
    NotImplementedError naming the caller's need — never ImportError."""
    try:
        mod = compat.pallas_tpu("test needs it")
        assert hasattr(mod, "PrefetchScalarGridSpec")
    except NotImplementedError as e:
        assert "test needs it" in str(e)


# ------------------------------------------------- per-backend plan models
def test_fw_candidates_per_backend_sets():
    """Candidate-set pinning: TPU keeps the historical fused+staged pool,
    GPU is fused-only under the SMEM filter, ref is fused-only unfiltered —
    and no VMEM-model candidate leaks into a non-TPU pool."""
    kw = dict(block_sizes=(32, 64, 128), bks=(16, 32))
    tpu = plan.fw_candidates(256, backend="tpu", **kw)
    gpu = plan.fw_candidates(256, backend="gpu", **kw)
    ref = plan.fw_candidates(256, backend="ref", **kw)
    assert {c["impl"] for c in tpu} == {"fused", "staged"}
    assert {c["impl"] for c in gpu} == {"fused"}
    assert {c["impl"] for c in ref} == {"fused"}
    for be, pool in (("tpu", tpu), ("gpu", gpu), ("ref", ref)):
        assert all(c["backend"] == be for c in pool)
    # non-TPU candidates never carry TPU scratch arithmetic...
    assert all(c["vmem_bytes"] == 0 for c in gpu + ref)
    # ...and the GPU pool is filtered by its own SMEM model instead.
    for c in gpu:
        assert c["smem_bytes"] == plan.gpu_round_smem_bytes(
            c["block_size"], c["bk"], word=4
        )
        assert c["smem_bytes"] <= plan.GPU_SMEM_BUDGET
        assert c["occupancy"] >= 1
    # (block_size, bk) grids: ref covers the full grid; gpu is the SMEM-
    # filtered subset of it.
    grid = {(c["block_size"], c["bk"]) for c in ref}
    assert {(c["block_size"], c["bk"]) for c in gpu} <= grid
    assert plan.fw_candidates(256, backend="tpu") \
        == plan.fw_candidates(256)  # default unchanged
    with pytest.raises(ValueError, match="unknown backend"):
        plan.fw_candidates(256, backend="cuda")


def test_gpu_and_ref_planning_skip_the_vmem_table(monkeypatch):
    """The TPU VMEM table has no GPU device kinds: the gpu and ref pools,
    their ranking and the engine's gpu/ref plans must never read it."""
    def no_table():
        raise AssertionError("gpu/ref planning read the TPU VMEM table")

    monkeypatch.setattr(compat, "vmem_limit_bytes", no_table)
    for be in ("gpu", "ref"):
        assert plan.fw_candidates(256, backend=be, batch=4)
        assert plan.autotune_fw(256, backend=be, batch=4)
        eng = ApspEngine(method="fused", block_size=32, backend=be,
                         validate=False)
        entry = eng.plan_for(100, batch=4)
        assert entry.key.backend == be and entry.key.batch_block == 4
    with pytest.raises(AssertionError, match="VMEM table"):
        plan.fw_candidates(256, backend="tpu")


class _Device:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize(
    "platform,kind,want",
    [("tpu", "TPU v5 lite", 100 << 20), ("gpu", "NVIDIA H100", 100 << 20),
     ("cpu", "cpu", 100 << 20), ("tpu", "TPU v9 unknown", None)],
    ids=["v5e", "gpu_host", "cpu_host", "unknown_tpu"],
)
def test_vmem_budget_by_device_kind(monkeypatch, platform, kind, want):
    # Off a TPU the TPU kernels only run interpreted, as v5e rehearsals;
    # a TPU kind missing from the table is an error, never a default.
    monkeypatch.setattr(jax, "devices", lambda *a: [_Device(platform, kind)])
    if want is None:
        with pytest.raises(ValueError, match="no VMEM budget"):
            compat.vmem_limit_bytes()
    else:
        assert compat.vmem_limit_bytes() == want


def test_gpu_byte_models():
    # SMEM: 2s² tile copies + 2(s·bk + bk·s) staged slices, in words.
    assert plan.gpu_round_smem_bytes(32, 16, word=4) == \
        (2 * 32 * 32 + 2 * (32 * 16 + 16 * 32)) * 4
    assert plan.gpu_round_smem_bytes(32, 16, word=4, successors=True) == \
        2 * plan.gpu_round_smem_bytes(32, 16, word=4)
    # HBM: TPU tile traffic + band GMEM round-trips.
    T = 4
    extra = (2 * T + 2 * (T - 1) + 2 * T * T) * 32 * 32 * 4
    assert plan.gpu_round_hbm_bytes(128, 32, word=4) == \
        plan.fused_round_hbm_bytes(128, 32, word=4) + extra


def test_autotune_backend_stamp_and_ranking():
    """autotune_fw(backend=) ranks within the backend's own byte model and
    stamps every result — the per-key provenance the benchmarks persist."""
    for be in ("tpu", "gpu", "ref"):
        ranked = plan.autotune_fw(256, backend=be, top=5)
        assert all(c["backend"] == be for c in ranked)
        totals = [c["total_bytes"] for c in ranked]
        assert totals == sorted(totals)
    gpu = plan.autotune_fw(256, backend="gpu")
    assert all(c["impl"] == "fused" for c in gpu)
