"""Planning arithmetic for APSP solves — one home for the numbers.

Everything here is host-side integer/float arithmetic shared by the solver
front-end (``repro.apsp.solve``), the engine, and the launch tooling,
so block-size selection, padding, mesh factorization, and the roofline
byte models cannot drift between callers.  The formulas are documented in
EXPERIMENTS.md (§Roofline, §Perf).
"""
from __future__ import annotations

import math


# Bytes per element for the storage dtypes the kernels run.  A name map, not
# np.dtype(): plan stays host-side arithmetic with no jax/ml_dtypes import
# (bfloat16 is not a stock numpy dtype).
_WORD_BYTES = {
    "float64": 8, "int64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
}


def word_for(dtype=None, *, semiring=None) -> int:
    """Bytes per stored element for a solve — THE dtype axis of the byte
    models.

    Accepts a dtype name / numpy dtype / jnp scalar type, or a semiring
    whose lowering pins a storage dtype (``Semiring.dtype``; the pinned
    dtype wins over ``dtype=None``).  Defaults to 4 (f32/i32 words, the
    historical model) when neither names one.
    """
    if semiring is not None and getattr(semiring, "dtype", None) is not None:
        dtype = semiring.dtype
    if dtype is None:
        return 4
    name = getattr(dtype, "name", None) or getattr(dtype, "__name__", None) \
        or str(dtype)
    try:
        return _WORD_BYTES[name]
    except KeyError:
        raise ValueError(
            f"no byte-model word size for dtype {dtype!r}; "
            f"known: {sorted(_WORD_BYTES)}"
        ) from None


def padded_size(n: int, block: int) -> int:
    """Smallest multiple of ``block`` that is >= n."""
    return ((n + block - 1) // block) * block


def round_count(n: int, block_size: int) -> int:
    """Pivot rounds of blocked FW at a given tile size (padded n)."""
    return padded_size(n, block_size) // block_size


def auto_block_size(n: int, *, max_block: int = 128) -> int:
    """Pick a pivot-tile size for an n-vertex graph.

    128 (the paper's sweet spot on our VMEM budget) once n is large enough;
    below that, the largest power of two <= ~n/4 so padding waste stays
    bounded (< 33%) while phase 1 still amortizes — but never below
    ``compat.min_block_size()``: 128 on TPU, where Mosaic tiles blocks by
    lanes and small graphs pad up; 16 elsewhere.
    """
    min_block = _min_block()
    if n >= max_block * 2:
        return max(max_block, min_block)
    s = 1 << max(4, (max(n, 2) - 1).bit_length() - 2)
    return max(min(s, max_block), min_block)


def _min_block() -> int:
    from repro.utils import compat  # call-time: plan stays import-light

    return compat.min_block_size()


def _vmem_budget(vmem_budget: int | None) -> int:
    """An explicit budget, else the device's (``compat.vmem_limit_bytes``).
    Only TPU planning calls this: the other lowerings keep no VMEM scratch."""
    if vmem_budget is not None:
        return vmem_budget
    from repro.utils import compat

    return compat.vmem_limit_bytes()


def kernel_bk(
    s: int, bk: int, *, variant: str = "fori", backend: str = "tpu"
) -> int:
    """The phase-3 staging depth a round runs when ``bk`` is asked for.

    The Pallas TPU kernels' "fori" variant relaxes a tile over all s
    rank-1 steps in one loop, one rotate per
    ``minplus_matmul._PICK_GROUP`` steps, whatever ``bk`` says: its depth
    is s.  The "unroll" and "broadcast" variants, the Triton round and the
    XLA twins stage by ``bk`` (at most s).
    """
    if backend == "tpu" and variant == "fori":
        return s
    return min(bk, s)


def grid_k_step(k: int, bk: int) -> int:
    """``semiring_matmul``'s contraction step per grid step: the largest
    multiple of ``bk`` that divides ``k``, up to one lane tile (128) — an
    (m, kb) A block Mosaic accepts."""
    return max(c for c in range(bk, min(k, max(bk, 128)) + 1, bk) if k % c == 0)


def mesh_factorization(devices: int, pods: int = 1) -> tuple[int, int]:
    """(R, C) block-grid factorization for host-device meshes.

    R = product of the row axes (pod × data), C = the model axis.  Single
    source of truth: ``launch.mesh.make_host_mesh`` builds meshes from it
    (fw_dist_check runs on those) and derives its SUMMA comm bound from
    it, so the reported comm efficiency always matches the mesh the check
    actually ran on.
    """
    if pods > 1:
        rows = max(1, devices // pods // 2)
        return pods * rows, devices // pods // rows
    rows = max(1, devices // 2)
    return rows, devices // rows


def distributed_multiple(block_size: int, R: int, C: int) -> int:
    """n must be a multiple of this for ``fw_distributed`` on an R×C grid.

    (build_fw_shard_fn requires n % (R·s) == n % (C·s) == 0.)
    """
    return block_size * math.lcm(R, C)


def summa_comm_bound_bytes(n: int, R: int, C: int, word: int = 4) -> float:
    """SUMMA comm lower bound per device: n²(1/R + 1/C) words."""
    return n * n * (1.0 / R + 1.0 / C) * word


def dist_round_comm_bytes(
    n: int, R: int, C: int, s: int, *, word: int = 4, batch: int = 1
) -> float:
    """Comm bytes per device for ONE distributed round (what we implement).

    Three ⊕-broadcasts per round: the raw (s,s) pivot tile across the whole
    mesh plus the raw (s, n/C) row- and (n/R, s) column-panel slices along
    their mesh axes.  Summed over the n/s rounds this exceeds the SUMMA
    bound (``summa_comm_bound_bytes``) by exactly the redundant diagonal
    term — the model side of the measured-vs-model comm-efficiency number
    (the measured side comes from the collective ops in the compiled HLO;
    see launch/fw_dist_check --bench).
    """
    return batch * (s * s + s * (n // C) + (n // R) * s) * word


# (s,s) tiles of live values a fused-round step keeps on Mosaic's stack,
# which counts against the same scoped-VMEM limit as the scratch bands.
# Compiled for v5e, a batch block of 22 graphs at n=4096 f32 asked 109.41
# MiB against the 93.5 MiB of bands + tiles: 11.6 tiles per graph.
LIVE_TILES = 12


def lane_cache_bytes(s: int, *, word: int = 4, variant: str = "fori") -> int:
    """Bytes per graph of the round's phase-3 lane-broadcast cache
    (``kernels.fw_round._fill_lane_cache``): s columns, each broadcast to
    an (s, s) tile of 32-bit words (16-bit storage widens).  Only the
    "fori" variant keeps it."""
    return s ** 3 * max(word, 4) if variant == "fori" else 0


def bordered_round_vmem_bytes(
    rows: int, cols: int, s: int, bk: int, *, word: int = 4,
    variant: str = "fori", batch: int = 1,
) -> int:
    """VMEM per grid step of the bordered (distributed) fused round.

    Same shape as ``fused_round_vmem_bytes`` on a rectangular (rows, cols)
    bordered local matrix: the two closed border bands in persistent scratch
    (s·cols + rows·s words) plus the double-buffered (s,s) in/out tiles,
    the body's live tiles and the lane-broadcast cache, times the batch
    block.
    """
    bands = s * cols + rows * s
    tiles = 2 * 2 * s * s
    transient = s * bk * s if variant == "broadcast" else 0
    words = bands + tiles + LIVE_TILES * s * s + transient
    return batch * (
        words * word + lane_cache_bytes(s, word=word, variant=variant)
    )


def auto_bordered_batch_block(
    B: int, rows: int, cols: int, s: int, bk: int, *, word: int = 4,
    variant: str = "fori", vmem_budget: int | None = None,
) -> int:
    """Largest divisor of B whose bordered scratch bands fit VMEM — the one
    fitting loop shared by ``distributed_plan`` and the kernel wrapper."""
    vmem_budget = _vmem_budget(vmem_budget)
    for bb in range(B, 0, -1):
        if B % bb:
            continue
        if bordered_round_vmem_bytes(
            rows, cols, s, bk, word=word, variant=variant, batch=bb
        ) <= vmem_budget:
            return bb
    return 1


def distributed_plan(
    n: int,
    devices: int,
    *,
    grid: tuple[int, int] | None = None,
    batch: int = 1,
    block_size: int | None = None,
    pods: int = 1,
    word: int = 4,
    bk: int = 32,
    variant: str = "fori",
    vmem_budget: int | None = None,
) -> dict:
    """THE mesh-aware plan for a distributed solve — (R, C, s) + padding.

    Picks the (R, C) grid via ``mesh_factorization`` (``grid=(R, C)`` pins
    an existing mesh's factorization instead — what ``solve`` passes for a
    user-supplied mesh), the pivot width via ``auto_block_size``
    (overridable), and *auto-pads* n to the ``distributed_multiple``
    instead of raising on the n % (R·s) == 0 constraint — ``solve(method="distributed")``, ``ApspEngine`` and
    ``launch.fw_dist_check`` all plan through here so the padded shape, the
    per-device tile, and the comm model can never drift apart.

    Returns a dict with: ``R``/``C`` (mesh grid), ``block_size``,
    ``n_padded``, ``rounds``, ``tile`` ((n_r, n_c) local block),
    ``bordered`` (per-device bordered-matrix shape), ``batch_block`` (graphs
    per grid step of the bordered kernel), ``vmem_bytes`` (bordered-round
    scratch model), ``comm_bytes_per_round`` (implemented broadcasts, per
    device), ``summa_bound_bytes`` (the lower bound over the whole solve)
    and ``comm_model_efficiency`` (bound / implemented ≤ 1).
    """
    if devices < 1:
        raise ValueError(f"devices must be >= 1, got {devices}")
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    if grid is not None:
        R, C = grid
        if R * C != devices:
            raise ValueError(f"grid {grid} does not cover {devices} devices")
    else:
        R, C = mesh_factorization(devices, pods)
    if block_size is None:
        # The padding multiple is s·lcm(R, C), so auto_block_size's own
        # <33% waste bound no longer holds at its preferred tile; walk the
        # tile down until the *mesh* padding respects the same bound (the
        # fattest such tile wins), falling back to the least-padding
        # candidate when even the floor tile cannot (tiny n on a wide mesh).
        cands = []
        s = auto_block_size(n)
        floor = _min_block()
        while s >= floor:
            cands.append((s, padded_size(n, distributed_multiple(s, R, C))))
            s //= 2
        fitting = [(sc, mc) for sc, mc in cands if 3 * (mc - n) <= n]
        s, m = fitting[0] if fitting else min(
            cands, key=lambda t: (t[1], -t[0])
        )
    else:
        s = block_size
        m = padded_size(n, distributed_multiple(s, R, C))
    n_r, n_c = m // R, m // C
    rounds = m // s
    rows, cols = n_r + s, n_c + s
    bb = auto_bordered_batch_block(
        batch, rows, cols, s, bk, word=word, variant=variant,
        vmem_budget=vmem_budget,
    )
    # Both sides of the efficiency ratio scale with the batch (every round
    # broadcasts (B,·,·) slices; the SUMMA bound is per graph).
    per_round = dist_round_comm_bytes(m, R, C, s, word=word, batch=batch)
    bound = batch * summa_comm_bound_bytes(m, R, C, word)
    return dict(
        R=R, C=C, block_size=s, n=n, n_padded=m, rounds=rounds,
        tile=(n_r, n_c), bordered=(rows, cols), batch=batch, batch_block=bb,
        vmem_bytes=bordered_round_vmem_bytes(
            rows, cols, s, bk, word=word, variant=variant, batch=bb
        ),
        comm_bytes_per_round=per_round,
        summa_bound_bytes=bound,
        comm_model_efficiency=bound / (rounds * per_round),
    )


# Per-SM shared memory of an A100/H100-class part — the GPU analogue of the
# TPU VMEM budget.  The paper's whole contribution is trimming this very
# working set so more blocks co-reside per SM; the occupancy field of the
# GPU candidates is that trade made explicit.
GPU_SMEM_BUDGET = 164 << 10


def gpu_round_smem_bytes(
    s: int, bk: int, *, word: int = 4, variant: str = "fori",
    successors: bool = False,
) -> int:
    """On-chip working set per grid step of the Triton fused round
    (``kernels.fw_round_gpu``) — the GPU side of ``fused_round_vmem_bytes``.

    Unlike the TPU kernel there is no persistent scratch: the closed bands
    live in GMEM outputs, so the per-step footprint is just the (s,s) tile
    plus its accumulator copy (2·s² words, registers/shared) and the
    double-buffered bk-deep band slices the phase-3 relaxation streams
    (2·(s·bk + bk·s) words — the paper's shared-memory staging depth).  The
    "broadcast" variant materializes the (s, bk, s) product transient;
    successor tracking doubles everything (distance + next-hop tiles).
    """
    scale = 2 if successors else 1
    tiles = 2 * s * s
    slices = 2 * (s * bk + bk * s)
    transient = s * bk * s if variant == "broadcast" else 0
    return scale * (tiles + slices + transient) * word


def gpu_round_hbm_bytes(
    n: int, s: int, *, word: int = 4, batch: int = 1
) -> float:
    """HBM traffic for ONE GPU fused round.

    The TPU tile traffic (``fused_round_hbm_bytes``) plus the band buffers'
    GMEM round-trips — on the Triton backend the closed pivot bands are
    outputs, not VMEM scratch, so phases 1-2 write 2T band tiles, phase 2
    re-reads the closed diagonal 2(T-1) times, and every phase-3 step reads
    one (s,s) slice of each band: (2T + 2(T-1) + 2T²)·s² extra words.  This
    asymmetry against the TPU model is exactly why ``autotune_fw`` must
    rank within a backend rather than across.
    """
    T = padded_size(n, s) // s
    bands = (2 * T + 2 * (T - 1) + 2 * T * T) * s * s
    return fused_round_hbm_bytes(n, s, word=word, batch=batch) \
        + float(batch * bands * word)


def phase3_vmem_bytes(
    bm: int, bn: int, bk: int, *, word: int = 4, fused: bool = False
) -> int:
    """VMEM per phase-3 grid step: resident C + double-buffered A/B slices.

    fused=True adds the C_in accumulator block (the FW relaxation form).
    See EXPERIMENTS.md §VMEM budget for the derivation.
    """
    c_blocks = 2 if fused else 1
    return (c_blocks * bm * bn + 2 * (bm * bk + bk * bn)) * word


def fused_round_vmem_bytes(
    n: int, s: int, bk: int, *, word: int = 4, variant: str = "fori",
    batch: int = 1, successors: bool = False,
) -> int:
    """VMEM per fused-round grid step (``kernels.fw_round``).

    Persistent scratch holds both closed pivot bands (2·s·n words); the
    (s,s) input and output tiles are each double-buffered by the Pallas
    pipeline; the kernel body's live values take ``LIVE_TILES`` more (s,s)
    tiles.  The "fori" variant's phase 3 keeps the lane-broadcast cache,
    s³ 32-bit words (``lane_cache_bytes``); the "broadcast" variant instead
    materializes an (s, bk, s) product transient.  ``batch`` is the batch
    *block* of the batched grid: every term carries a per-graph leading
    dim, so the footprint scales linearly.  ``successors=True`` models
    ``fw_round_with_successors``: twice the bands, tiles and live values
    (distances and next hops), and no cache.  See EXPERIMENTS.md §Fused
    round.
    """
    bands = 2 * s * n
    tiles = 2 * 2 * s * s
    transient = s * bk * s if variant == "broadcast" else 0
    words = bands + tiles + LIVE_TILES * s * s + transient
    if successors:
        return batch * 2 * words * word
    return batch * (
        words * word + lane_cache_bytes(s, word=word, variant=variant)
    )


def fused_round_hbm_bytes(
    n: int, s: int, *, word: int = 4, batch: int = 1
) -> float:
    """HBM traffic for ONE fused round: every tile read+written exactly once
    at its grid step — T² + 2T - 1 steps of an (s,s) block each, ×batch
    graphs.

    Compare ``staged_hbm_bytes_per_round``: the multi-kernel round re-reads
    the pivot bands for phase 3 and round-trips the phase-2 splices through
    HBM; the fused round keeps all of that in scratch.
    """
    T = padded_size(n, s) // s
    return 2.0 * batch * (T * T + 2 * T - 1) * s * s * word


def fused_round_steps(n: int, s: int, *, batch: int = 1) -> int:
    """Grid steps of one fused round: T² phase-3 + 2(T-1) bands + 1 pivot,
    times the batch-grid leading dimension (graphs / batch block)."""
    T = padded_size(n, s) // s
    return batch * (T * T + 2 * T - 1)


def fused_solve_hbm_bytes(
    n: int, s: int, *, word: int = 4, batch: int = 1
) -> float:
    """Modeled HBM traffic of a WHOLE fused solve: n/s rounds ×
    ``fused_round_hbm_bytes`` — the numerator of an achieved-bandwidth
    figure."""
    return round_count(n, s) * fused_round_hbm_bytes(
        n, s, word=word, batch=batch
    )


def repair_hbm_bytes(
    n: int, s: int, *, word: int = 4, edges: int = 1,
    successors: bool = False,
) -> float:
    """HBM traffic of ONE fused rank-1 repair dispatch
    (``kernels.fw_repair``): E stage steps each read+write one (s, n) row
    band (byte-identical copy-out — the write is the price of the
    prefetch-safety rule), then T apply steps read+write every band once.
    Successor tracking doubles it (distance + next-hop tables).

    The repair-vs-resolve crossover the serving policy uses
    (``ApspEngine.should_repair``): this is ~2·(E+T)·s·n words against
    ``fused_solve_hbm_bytes``'s ~2·(n/s)·(T²+2T-1)·s² — repair wins by
    roughly a factor of n/s per small edge batch, which is also what the
    ``fw_repair/speedup`` ladder showed (388× at n=1024, a CPU-container
    figure, not a chip measurement).
    """
    m = padded_size(n, s)
    bands = edges + m // s
    return 2.0 * bands * s * m * word * (2 if successors else 1)


def repair_del_hbm_bytes(
    n: int, s: int, *, affected_rows: int, word: int = 4, edges: int = 1,
    successors: bool = False,
) -> float:
    """HBM traffic of ONE decremental repair (``kernels.fw_repair_del``).

    Stage 1 (marking) streams the closure once per deleted edge (the
    witness outer-product compare) plus the updated weights and the reset
    write — (2 + E)·n² words.  Stage 2 (the restricted row sweep) runs T
    rounds, each reading one (s, n) pivot band and reading+writing the
    (a, n) affected-row strip — T·(s + 2a)·n words against the full
    round's ~2n².  Successor tracking doubles it (distance + next-hop).

    The decremental crossover ``should_repair_del`` uses: at a ≪ n the
    sweep approaches the rank-1 repair's n/s advantage; as a → n it
    degrades past a full solve (the band assembly is pure overhead), which
    is exactly when ``ApspEngine.repair_del`` falls back.
    """
    m = padded_size(n, s)
    T = m // s
    mark = (2.0 + edges) * m * m * word
    sweep = T * (s + 2.0 * affected_rows) * m * word
    return (mark + sweep) * (2 if successors else 1)


def should_repair_del(
    n: int, affected_rows: int, *, block_size: int | None = None,
    word: int = 4, edges: int = 1, successors: bool = False,
    threshold: float = 0.5,
) -> bool:
    """The affected-fraction policy: is the restricted sweep still cheaper
    than a full fused re-solve once stage 1 has counted the damage?

    Unlike ``ApspEngine.should_repair`` (decided *before* any dispatch from
    the pending-update backlog), this runs *between* the two repair_del
    stages — the affected row count only exists after marking, and marking
    is O(E·n²), cheap enough to always run.  Compares
    ``repair_del_hbm_bytes`` against ``threshold ×`` the full solve's
    modeled traffic; at n=1024, s=128, f32 the crossover sits near
    a ≈ 0.37·n affected rows.
    """
    if affected_rows < 1:
        return False
    s = block_size or auto_block_size(n)
    cost = repair_del_hbm_bytes(
        n, s, affected_rows=affected_rows, word=word, edges=edges,
        successors=successors,
    )
    full = fused_solve_hbm_bytes(n, s, word=word) * (2 if successors else 1)
    return cost <= threshold * full


def achieved_hbm_gbps(
    n: int, s: int, seconds: float, *, word: int = 4, batch: int = 1
) -> float:
    """Achieved HBM bandwidth (GB/s) of a measured fused solve.

    Modeled solve bytes (``fused_solve_hbm_bytes``) over measured wall time
    — the number that makes "the round is bandwidth-bound" a figure instead
    of prose.  Compare against the device's peak (e.g. ~819 GB/s per v5e
    core); a ratio near 1 means the byte model, not compute, sets the
    runtime.  ``word`` carries the dtype axis: at a fixed graph, halving
    the word halves the bytes — if measured time does NOT halve with it,
    the solve has left the bandwidth-bound regime.
    """
    if seconds <= 0:
        raise ValueError(f"seconds must be > 0, got {seconds}")
    return fused_solve_hbm_bytes(n, s, word=word, batch=batch) / seconds / 1e9


def auto_batch_block(
    B: int,
    n: int,
    s: int,
    *,
    bk: int = 32,
    word: int = 4,
    variant: str = "fori",
    vmem_budget: int | None = None,
    successors: bool = False,
) -> int:
    """Largest divisor of B whose per-step scratch+tile footprint fits VMEM.

    The batched round's working set scales linearly in the batch block
    (per-graph scratch bands), so the best block is simply the fattest one
    the budget admits — bigger blocks mean fewer grid steps and wider
    VPU-lane occupancy per step.  ``successors=True`` plans the successor
    round (``fused_round_vmem_bytes``).
    """
    if B < 1:
        raise ValueError(f"batch size must be >= 1, got {B}")
    vmem_budget = _vmem_budget(vmem_budget)
    for bb in range(B, 0, -1):
        if B % bb:
            continue
        if fused_round_vmem_bytes(
            n, s, bk, word=word, variant=variant, batch=bb,
            successors=successors,
        ) <= vmem_budget:
            return bb
    return 1


def fw_candidates(
    n: int,
    *,
    backend: str = "tpu",
    batch: int = 1,
    vmem_budget: int | None = None,
    smem_budget: int = GPU_SMEM_BUDGET,
    word: int | None = None,
    dtype=None,
    lanes: int = 1,
    variant: str = "fori",
    block_sizes: tuple[int, ...] = (32, 64, 128, 256),
    bks: tuple[int, ...] = (8, 16, 32, 64, 128),
    hbm_budget: int | None = None,
    include_recursive: bool = False,
) -> list[dict]:
    """Model-filtered (block_size, bm, bn, bk) autotune candidates.

    Covers both round lowerings: ``impl="fused"`` (one dispatch/round; bm =
    bn = block_size by construction) and ``impl="staged"`` (4 dispatches;
    bm/bn from the phase-3 tile grid).  A candidate survives iff its
    per-step VMEM footprint fits ``vmem_budget`` (default: the device's
    scoped-VMEM limit, ``compat.vmem_limit_bytes``).  ``batch > 1`` models the batched grid: fused candidates gain a
    ``batch_block`` (the fattest divisor of ``batch`` the budget admits)
    and per-round HBM/step counts scale to the whole batch.  Deterministic
    — the benchmark key manifest is derived from it.

    Byte models are dtype- and packing-aware: ``dtype`` (or an explicit
    ``word``; word wins) sets the bytes per stored element, and ``lanes``
    (32 for the bit-packed or_and lowering — ``Semiring.lanes``) divides
    the per-*graph* traffic: each candidate carries
    ``hbm_bytes_per_graph = hbm_bytes_total / (batch·lanes)``, the number
    that makes an int16 or packed config comparable to f32 at the same
    logical workload.

    ``hbm_budget`` adds the residency axis: candidates whose working set
    cannot fit the budget are dropped (an HBM-resident fused solve of a
    matrix bigger than HBM is not a plan), and ``include_recursive=True``
    (implied by a budget) adds ``impl="recursive"`` out-of-core candidates
    per (block_size, leaf) with ``pcie_bytes_total`` from
    ``recursive_transfer_bytes``.  Every candidate carries
    ``total_bytes = hbm_bytes_total + pcie_bytes_total`` — the ranking key
    ``autotune_fw`` uses, which is what picks the leaf size.

    ``backend`` selects whose on-chip arithmetic filters the pool (every
    candidate is stamped with it):

      * ``"tpu"`` — the historical set: fused (VMEM scratch model), staged,
        and recursive candidates against ``vmem_budget``.  Each tile
        carries the distinct depths ``kernel_bk`` maps ``bks`` to: one,
        bk = s, for the "fori" variant, which does not read bk; staged
        candidates count and charge the ``grid_k_step`` slices the
        contraction kernel streams.
      * ``"gpu"`` — fused candidates ONLY (the Triton round is the one GPU
        lowering), filtered by ``gpu_round_smem_bytes`` against
        ``smem_budget`` with an ``occupancy`` field (blocks co-resident per
        SM — the paper's figure of merit) and HBM bytes from
        ``gpu_round_hbm_bytes`` (band GMEM traffic included); a
        ``num_warps`` occupancy hint rides along.
      * ``"ref"`` — fused candidates with NO on-chip filter (the XLA twin
        has no scratch); byte models as the TPU fused schedule.

    VMEM-model arithmetic never leaks into a non-TPU pool: the GPU/ref
    candidates carry ``vmem_bytes=0`` and their own filters.
    """
    if word is None:
        word = word_for(dtype)
    if lanes < 1:
        raise ValueError(f"lanes must be >= 1, got {lanes}")
    if backend not in ("tpu", "gpu", "ref"):
        raise ValueError(
            f"unknown backend {backend!r} for fw_candidates; "
            f"have ('tpu', 'gpu', 'ref')"
        )
    if hbm_budget is not None:
        include_recursive = True
    if backend == "tpu":
        # Mosaic's lane tile: sub-128 pivots do not compile for the chip.
        block_sizes = tuple(s for s in block_sizes if s % _min_block() == 0)
    out = []
    if backend != "tpu":
        for s in block_sizes:
            if s > max(n, 16):
                continue
            sp = min(s, n)
            m = padded_size(n, sp)
            if hbm_budget is not None and batch * m * m * word > hbm_budget:
                continue
            rounds = m // sp
            for bk in bks:
                if bk > sp:
                    continue
                if backend == "gpu":
                    smem = gpu_round_smem_bytes(
                        sp, bk, word=word, variant=variant
                    )
                    if smem > smem_budget:
                        continue
                    per_round = gpu_round_hbm_bytes(
                        m, sp, word=word, batch=batch
                    )
                    extra = dict(
                        smem_bytes=smem,
                        occupancy=max(1, smem_budget // smem),
                        num_warps=4 if sp <= 64 else 8,
                    )
                else:
                    per_round = fused_round_hbm_bytes(
                        m, sp, word=word, batch=batch
                    )
                    extra = {}
                out.append(dict(
                    impl="fused", backend=backend, block_size=sp, bm=sp,
                    bn=sp, bk=bk, batch=batch, batch_block=batch, word=word,
                    lanes=lanes, vmem_bytes=0,
                    hbm_bytes_per_round=per_round,
                    hbm_bytes_total=rounds * per_round,
                    hbm_bytes_per_graph=rounds * per_round / (batch * lanes),
                    pcie_bytes_total=0.0,
                    total_bytes=rounds * per_round,
                    steps_per_round=fused_round_steps(m, sp, batch=1),
                    dispatches_per_round=1,
                    **extra,
                ))
        return out
    vmem_budget = _vmem_budget(vmem_budget)
    for s in block_sizes:
        if s > max(n, 16):
            continue
        # Clamp serves caller-supplied block_sizes smaller than the default
        # grid (e.g. s=16 at n=8); with the defaults any admitted s <= n.
        sp = min(s, n)
        m = padded_size(n, sp)
        if hbm_budget is not None and batch * m * m * word > hbm_budget:
            # The HBM-resident lowerings need the whole padded matrix on
            # device; past the budget only the recursive stream qualifies.
            continue
        depths = dict.fromkeys(
            kernel_bk(sp, bk, variant=variant) for bk in bks if bk <= sp
        )
        for bk in depths:
            rounds = m // sp
            kb = grid_k_step(sp, bk)
            bb = auto_batch_block(
                batch, m, sp, bk=bk, word=word, variant=variant,
                vmem_budget=vmem_budget,
            ) if batch > 1 else 1
            v = fused_round_vmem_bytes(
                m, sp, bk, word=word, variant=variant, batch=bb
            )
            if v <= vmem_budget:
                per_round = fused_round_hbm_bytes(m, sp, word=word, batch=batch)
                out.append(dict(
                    impl="fused", backend="tpu", block_size=sp, bm=sp,
                    bn=sp, bk=bk,
                    batch=batch, batch_block=bb, word=word, lanes=lanes,
                    vmem_bytes=v,
                    hbm_bytes_per_round=per_round,
                    hbm_bytes_total=rounds * per_round,
                    hbm_bytes_per_graph=rounds * per_round / (batch * lanes),
                    pcie_bytes_total=0.0,
                    total_bytes=rounds * per_round,
                    steps_per_round=fused_round_steps(m, sp,
                                                      batch=batch // bb),
                    dispatches_per_round=1,
                ))
            for bm in (sp, 2 * sp):
                if bm > m:
                    continue
                v3 = phase3_vmem_bytes(bm, bm, kb, word=word, fused=True)
                if v3 <= vmem_budget:
                    per_round = batch * staged_hbm_bytes_per_round(
                        m, m, sp, bm=bm, bn=bm, word=word
                    )
                    out.append(dict(
                        impl="staged", backend="tpu", block_size=sp, bm=bm,
                        bn=bm, bk=bk,
                        batch=batch, batch_block=1, word=word, lanes=lanes,
                        vmem_bytes=v3,
                        hbm_bytes_per_round=per_round,
                        hbm_bytes_total=rounds * per_round,
                        hbm_bytes_per_graph=rounds * per_round
                        / (batch * lanes),
                        pcie_bytes_total=0.0,
                        total_bytes=rounds * per_round,
                        steps_per_round=batch * (m // bm) ** 2 * (sp // kb),
                        dispatches_per_round=4,
                    ))
    if include_recursive:
        for s in block_sizes:
            if s > max(n, 16):
                continue
            sp = min(s, n)
            m = padded_size(n, sp)
            lr = 1
            while lr * sp <= m:
                rp = recursive_plan(
                    n, leaf=lr * sp, hbm_budget=hbm_budget,
                    block_size=sp, batch=batch, word=word, variant=variant,
                )
                lr *= 2
                if (hbm_budget is not None
                        and rp["hbm_resident_bytes"] > hbm_budget):
                    continue
                total = rp["hbm_bytes_total"] + rp["transfer_bytes"]
                out.append(dict(
                    impl="recursive", backend="tpu", block_size=sp, bm=sp,
                    bn=sp,
                    bk=min(32, sp), batch=batch, batch_block=1, word=word,
                    lanes=lanes, leaf=rp["leaf"],
                    out_of_core=rp["out_of_core"],
                    vmem_bytes=fused_round_vmem_bytes(
                        rp["leaf"], sp, min(32, sp), word=word,
                        variant=variant,
                    ),
                    hbm_bytes_per_round=rp["hbm_bytes_total"] / rp["rounds"],
                    hbm_bytes_total=rp["hbm_bytes_total"],
                    hbm_bytes_per_graph=rp["hbm_bytes_total"]
                    / (batch * lanes),
                    pcie_bytes_total=float(rp["transfer_bytes"]),
                    total_bytes=total,
                    steps_per_round=rp["leaf_calls"] + rp["sweep_calls"],
                    dispatches_per_round=rp["panels"],
                ))
    return out


def autotune_fw(
    n: int,
    measure=None,
    *,
    backend: str = "tpu",
    batch: int = 1,
    vmem_budget: int | None = None,
    smem_budget: int = GPU_SMEM_BUDGET,
    dtype=None,
    lanes: int = 1,
    variant: str = "fori",
    top: int | None = None,
    hbm_budget: int | None = None,
) -> list[dict]:
    """Rank fused/staged round configs for an n-vertex solve.

    measure: optional callback ``cfg_dict -> seconds`` (e.g. a timed
    ``fw_staged`` call); when given, candidates are ranked by measured time
    and each dict gains ``"us"``.  Without it, ranking falls back to the
    model: total HBM bytes over all n/s rounds — per-round bytes alone
    would favor tiny pivots that pay for themselves in round count (the
    kernels are bandwidth-bound on the VPU roofline — EXPERIMENTS.md
    §Roofline) — with fused-before-staged dispatch count as tiebreak.
    ``batch=B`` ranks configs for a B-graph batched solve instead (same
    model, scaled; fused candidates carry the chosen ``batch_block``).
    ``dtype``/``lanes`` thread the storage lowering through the byte
    models (``fw_candidates``): a bf16/int16 solve halves every modeled
    byte count — and therefore the fitted VMEM footprints and the ranking
    — and a packed or_and solve additionally divides the per-graph bytes
    by 32, which is exactly why autotune ranks those lowerings first at
    equal logical work.  ``hbm_budget`` adds the residency axis: HBM-bound
    candidates that cannot fit are dropped, ``impl="recursive"``
    out-of-core candidates join the pool, and the model ranking switches
    to *total* (HBM + PCIe) bytes — which is what picks the leaf size (the
    fattest resident leaf minimizes streamed bytes at ≈ 2·m³/leaf).
    ``backend`` resolves the candidate pool (``fw_candidates(backend=)``)
    and every returned dict is stamped with it — ranking happens WITHIN a
    backend (TPU VMEM vs GPU SMEM byte models are not commensurable), and
    the stamp says which model ranked it.
    """
    cands = fw_candidates(n, backend=backend, batch=batch,
                          vmem_budget=vmem_budget, smem_budget=smem_budget,
                          dtype=dtype, lanes=lanes, variant=variant,
                          hbm_budget=hbm_budget)
    if not cands:
        raise ValueError(
            f"no viable {backend} round config for n={n} within its "
            f"on-chip budget; pass smaller block_sizes via fw_candidates"
        )
    if measure is not None:
        for c in cands:
            c["us"] = measure(c) * 1e6
        cands.sort(key=lambda c: c["us"])
    else:
        # total_bytes == hbm_bytes_total for the resident impls, so the
        # historical ordering is unchanged when no budget is given.
        cands.sort(key=lambda c: (c["total_bytes"],
                                  c["dispatches_per_round"]))
    return cands[:top] if top else cands


# --------------------------------------------------------------- recursive
# Planning arithmetic for the recursive (R-Kleene) out-of-core schedule
# (apsp/kleene.py).  Everything stays host-side integer math so the byte
# models, the executor, and the benchmarks share ONE traversal order — the
# measured-vs-model transfer check in launch/fw_oocore.py depends on the
# model mirroring the executor's panel loop exactly.


def kleene_ranges(
    rounds: int, leaf_rounds: int
) -> tuple[list[tuple[int, int]], int]:
    """Binary R-Kleene recursion over pivot-round ranges → in-order leaves.

    Splits [0, rounds) recursively at a leaf-aligned midpoint until every
    range holds at most ``leaf_rounds`` rounds.  Returns the leaf ranges in
    round order (executing them left to right IS the depth-first traversal
    of the 2×2 Kleene recursion — A11 before the off-diagonal products
    before A22) plus the recursion depth.  The executor (KleeneExecutor),
    ``recursive_plan``'s byte models, and the tests all consume this one
    decomposition, so schedule and model cannot drift.
    """
    if rounds < 1:
        raise ValueError(f"rounds must be >= 1, got {rounds}")
    if leaf_rounds < 1:
        raise ValueError(f"leaf_rounds must be >= 1, got {leaf_rounds}")
    out: list[tuple[int, int]] = []

    def split(lo: int, hi: int, depth: int) -> int:
        if hi - lo <= leaf_rounds:
            out.append((lo, hi))
            return depth
        # Leaf-aligned ceil-half split keeps every interior leaf full-width
        # (only the last panel may be ragged).
        half = -(-(hi - lo) // (2 * leaf_rounds)) * leaf_rounds
        mid = lo + half
        return max(split(lo, mid, depth + 1), split(mid, hi, depth + 1))

    depth = split(0, rounds, 1)
    return out, depth


def recursive_transfer_bytes(
    n_padded: int, s: int, leaf_rounds: int, *, word: int = 4, batch: int = 1
) -> tuple[int, int]:
    """(h2d, d2h) bytes of one out-of-core recursive solve — the model side
    of the 15%-of-measured acceptance check.

    Mirrors the executor's store traffic exactly: per leaf panel of width
    P, the resident pivot cross (the (m, P) column band + (P, m) row band,
    the (P, P) diagonal overlap fetched in both) streams in and back out
    (2·P·m each way), and every outside tile — the (m−P)² area excluding
    the cross — streams in for ONE deferred factor matmul and back out.
    Total ≈ 2·m³/P + O(m²) per direction: the leaf size is the streaming
    amortization knob, exactly the paper's staging-depth trade one memory
    level up.
    """
    m = n_padded
    ranges, _ = kleene_ranges(m // s, leaf_rounds)
    per_dir = 0
    for lo, hi in ranges:
        P = (hi - lo) * s
        per_dir += 2 * P * m + (m - P) * (m - P)
    per_dir *= word * batch
    return per_dir, per_dir


def recursive_hbm_resident_bytes(
    n_padded: int, s: int, leaf_rounds: int, *, word: int = 4,
    batch: int = 1, out_of_core: bool = True,
) -> int:
    """Peak device residency of the recursive schedule.

    Out of core, only the pivot cross plus its factor snapshots (4·P·m
    words: two resident bands + the two concatenated phase-2 factors) and
    up to three streamed sweep tiles (current + prefetched + retiring
    write-back, ≤ P² each) live on device — the matrix itself stays in the
    host store.  In core the full matrix is resident too.
    """
    m = n_padded
    P = min(leaf_rounds * s, m)
    panels = 4 * P * m + 3 * P * P
    if not out_of_core:
        panels += m * m
    return batch * panels * word


def recursive_plan(
    n: int,
    *,
    leaf: int | None = None,
    hbm_budget: int | None = None,
    block_size: int | None = None,
    batch: int = 1,
    word: int | None = None,
    dtype=None,
    bk: int = 32,
    variant: str = "fori",
) -> dict:
    """THE plan for a recursive (R-Kleene) solve — leaf size + streaming.

    Pads n exactly like the fused path (``auto_block_size`` +
    ``padded_size``; the recursive schedule replays the fused rounds at the
    same pivot width, which is what makes it bitwise-comparable), then
    resolves the leaf:

      * ``leaf=None`` with an ``hbm_budget``: the fattest power-of-two
        multiple of the block size whose out-of-core residency model fits
        the budget (bigger leaves amortize streaming — transfer ≈ 2·m³/leaf
        — so the fattest fitting leaf minimizes PCIe bytes).
      * ``leaf=None`` without a budget: min(m, 4·s) — a compute-granularity
        default for the in-core path.
      * explicit ``leaf``: validated (multiple of the block size), clamped
        to the padded size.

    ``out_of_core`` is True when the full matrix does not fit the budget;
    the returned byte models then mirror ``apsp.kleene``'s host-store
    traffic exactly (``recursive_transfer_bytes``).  Returns block_size /
    n_padded / rounds / leaf / leaf_rounds / ranges / panels / depth /
    out_of_core / matrix_bytes / hbm_resident_bytes / h2d_bytes /
    d2h_bytes / transfer_bytes / hbm_bytes_total / leaf_calls /
    sweep_calls.
    """
    if word is None:
        word = word_for(dtype)
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    s = block_size or auto_block_size(n)
    m = padded_size(n, s)
    T = m // s
    matrix_bytes = batch * m * m * word
    out_of_core = hbm_budget is not None and matrix_bytes > hbm_budget
    if leaf is None:
        if out_of_core:
            # Fattest power-of-two leaf whose streaming residency fits.
            lr = 1
            while (
                2 * lr * s <= m
                and recursive_hbm_resident_bytes(
                    m, s, 2 * lr, word=word, batch=batch
                ) <= hbm_budget
            ):
                lr *= 2
            leaf = lr * s
        else:
            leaf = min(m, 4 * s)
    else:
        if leaf % s:
            raise ValueError(
                f"leaf ({leaf}) must be a multiple of block_size ({s}) — "
                f"leaves replay whole fused pivot rounds"
            )
        leaf = min(leaf, m)
    lr = leaf // s
    ranges, depth = kleene_ranges(T, lr)
    h2d, d2h = (
        recursive_transfer_bytes(m, s, lr, word=word, batch=batch)
        if out_of_core else (0, 0)
    )
    # Device-side traffic model: every leaf round reads+writes the resident
    # cross (2·P·m each way), the sweep reads+writes each outside tile once
    # and streams the (m−P)·P factor operands past it.
    hbm_total = 0
    sweep_calls = 0
    npanels = len(ranges)
    for lo, hi in ranges:
        P = (hi - lo) * s
        hbm_total += (hi - lo) * 2 * (2 * P * m)
        hbm_total += 2 * (m - P) * (m - P) + 2 * (m - P) * P
        sweep_calls += (npanels - 1) ** 2
    hbm_total *= word * batch
    return dict(
        impl="recursive", block_size=s, n=n, n_padded=m, rounds=T,
        leaf=leaf, leaf_rounds=lr, ranges=ranges, panels=npanels,
        depth=depth, out_of_core=out_of_core, batch=batch, word=word,
        bk=min(bk, s), variant=variant,
        matrix_bytes=matrix_bytes,
        hbm_resident_bytes=recursive_hbm_resident_bytes(
            m, s, lr, word=word, batch=batch, out_of_core=out_of_core
        ),
        h2d_bytes=h2d, d2h_bytes=d2h, transfer_bytes=h2d + d2h,
        hbm_bytes_total=hbm_total,
        leaf_calls=npanels, sweep_calls=sweep_calls,
    )


def staged_hbm_bytes_per_round(
    n_r: int, n_c: int, s: int, *, bm: int = 256, bn: int = 256, word: int = 4
) -> float:
    """HBM traffic model for one round of the staged backend on one device.

    Per round on an (n_r, n_c) local block: phase 3 reads+writes W once
    (C tile resident across the k grid) and streams (bm×bk)/(bk×bn) panel
    slices; phase 2 reads+writes the two panels with the diag broadcast;
    phase 1 round-trips the diag tile.
    """
    return (
        2 * n_r * n_c                         # C in/out, resident over k
        + s * n_r * n_c * (1 / bm + 1 / bn)   # streamed panel slices
        + 4 * s * (n_r + n_c)                 # phase-2 panel r/w
        + 2 * s * s * 3                       # diag r/w + phase-2 reads
    ) * word
