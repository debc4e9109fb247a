"""Fused multi-stage round kernel: one ``pallas_call`` per pivot round.

The paper's 5× over the blocked baseline comes from running *all* phases of
a round as one multi-stage kernel with a reduced on-chip working set, so the
scheduler can hide panel-load latency behind compute.  The staged port
(``core.staged.fw_staged``) instead dispatched 4+ ``pallas_call``s per round
— phase 1, two phase-2 bands, phase 3 — with the closed pivot bands making a
full HBM round-trip (plus ``dynamic_slice``/``dynamic_update_slice`` copies)
between every pair of dispatches.  This kernel is the TPU re-derivation of
the paper's fusion (and of the panel-streaming idiom in Rucci et al.'s
blocked APSP on KNL):

  * **one grid, all phases** — a single 1-D grid of ``T² + 2T - 1`` steps
    (T = n/s tiles per side) covers the whole matrix; each program
    classifies its step as diagonal closure (phase 1), row/col band closure
    (phase 2), or full-matrix relaxation (phase 3) from ``program_id``
    against the traced pivot index.
  * **pivot-first visit order** — the tile each step owns is resolved
    through two scalar-prefetch order arrays built from the traced pivot
    ``b`` (``_round_order``): pivot tile first, then the 2(T-1) band tiles,
    then every tile again for phase 3.  Scalar-prefetch index maps are how
    Pallas lets a *data-dependent* schedule drive the DMA pipeline.
  * **bands staged through scratch** — the closed diagonal and both closed
    pivot bands live in VMEM scratch (``(s, n)`` + ``(n, s)``), written by
    the phase-1/2 steps and re-read in ``bk``-deep slices by every phase-3
    step, exactly as the paper streams m-deep panel slices through shared
    memory.  Nothing closed in this round touches HBM until its final value
    is known; cross-step communication never leaves the chip.
  * **native batch grid** — a (B, n, n) input adds a *leading* batch grid
    dimension: B graphs share ONE dispatch per round, the scalar-prefetch
    pivot schedule is broadcast across the batch (every graph runs the same
    round-b tile order), and the scratch bands carry a per-graph leading
    dim (``(bb, s, n)`` + ``(bb, n, s)`` for a batch block of bb graphs).
    Each batch block finishes its whole round before the grid advances to
    the next, so the band scratch is reused without cross-graph hazards.

Sequencing: the grid dimensions are all "arbitrary" (sequential on the
TensorCore), and *all* cross-step dataflow is through scratch — no step
reads an HBM block written earlier in the same round, so Pallas' input
prefetch (which may run ahead of the previous step's output DMA) can never
observe a stale tile.

Bit-identity: every per-element ⊕/⊗ chain is evaluated in exactly the order
of the 4-kernel lowering — phase 2 re-uses the same k-sequential recurrence,
and phase 3 re-relaxes *every* tile (bands and diagonal included, with the
closed values as accumulator input) through the same ``_stage_compute``
bk-chunk sequence as ``semiring_matmul``'s k grid.  Outputs are therefore
bitwise equal to ``fw_staged(unroll_rounds=True)`` for any semiring and
dtype, not just up to tolerance (tests/test_fw_round.py) — and the batched
grid runs the identical elementwise chain per graph, so batched outputs are
bitwise equal to B separate calls.

``fw_round_with_successors`` is the same multi-stage schedule carrying a
next-hop matrix: every phase applies the strict-improvement relaxation of
``core.paths`` (``cand < w`` rather than ⊕), with *four* scratch bands (the
closed distance bands plus their successor bands), so
``solve(successors=True, method="fused")`` no longer falls back to the
multi-dispatch blocked path.  Outputs bit-match
``fw_blocked_with_successors`` (distances and successor matrices).
Phase 3 (``_relax_succ_grouped``) rotates its three fixed operands once
per ``_PICK_GROUP`` steps and picks with static slices; phases 1–2 still
rotate on every step, since the tile they close is one of their operands
and changes inside the loop.

Phase 3 of the "fori" variant keeps a lane-broadcast cache: phase 3
visits tiles row-major and its a-operand (the col-band tile) depends on
the row alone, so each row's first step broadcasts that operand's s
columns across the lanes once, into an (s, s, s) scratch, and every step
of the row loads them (``_fill_lane_cache``, ``_relax_lane_cache``).  No
step then broadcasts across lanes, the XLU work that paced phase 3.

VMEM: scratch is ``bb·2·s·n`` words + the double-buffered (bb,s,s) in/out
tiles + ``bb·s³`` 32-bit words of lane cache —
``plan.fused_round_vmem_bytes(batch=bb)``; successor tracking doubles the
bands and tiles and keeps no cache.  ``plan.auto_batch_block`` picks the
largest batch block that fits the budget.

``fw_round_bordered`` is the distributed form of the same kernel: each
device of an R×C mesh holds an (n_r, n_c) block of W, and per round the raw
pivot tile and panel slices are ⊕-broadcast and stacked as a *border* onto
the local block::

        [ diag  row_panel ]      (s + n_r, s + n_c), pivot at tile (0, 0)
        [ col_  local     ]
        [ panel block     ]

One bordered round is then exactly this kernel's schedule on a rectangular
tile grid with the pivot pinned at (0,0): phase 1 closes the (s,s) corner,
phase 2 closes the border bands through the same scratch, phase 3 relaxes
every local tile against them — the paper's single-dispatch round, per
device, per round.  Two *owner-echo* scalars (``owner_row``/``owner_col``,
the bordered tile coordinates where the device's local block holds its own
copy of the global pivot bands, -1 elsewhere) splice the closed border
values over those copies, exactly as the square kernel splices its closed
bands — so the distributed solve is bitwise equal to the single-device
fused solve for every semiring, including non-idempotent ⊕ (plus_mul),
where a re-relaxed band would otherwise double-count
(tests/test_distributed.py).  See docs/KERNELS.md §Distributed round.

Each ``pallas_call`` passes ``name=``, which becomes its HLO op name in the
compiled program and in a device trace (``%fw_round.<i>``,
``%fw_round_with_successors.<i>``, ``%fw_round_bordered.<i>``), whatever
jit wraps it; the benchmark's kernel metrics match these names and
tests/test_tpu_compile.py pins them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.semiring import MIN_PLUS, Semiring
from repro.kernels.minplus_matmul import (
    _PICK_GROUP,
    _WIDE,
    Variant,
    _col,
    _fit_block,
    _rotate,
    _row,
    _stage_chunks,
)
from repro.utils import compat


def _round_order(b: jax.Array, T: int) -> tuple[jax.Array, jax.Array]:
    """Tile-visit order for pivot round ``b``: (oi, oj), each (T²+2T-1,).

    g=0 → pivot tile (b,b); g ∈ [1, T) → row-band tiles (b, j≠b);
    g ∈ [T, 2T-1) → col-band tiles (i≠b, b); g ≥ 2T-1 → phase 3 over all
    T² tiles in row-major order.  ``b`` is traced; the shapes are static.
    The order is *per round*, not per graph — a batched call broadcasts the
    same schedule to every graph in the batch.
    """
    b = jnp.asarray(b, jnp.int32)
    nz = jnp.arange(T - 1, dtype=jnp.int32)
    nz = jnp.where(nz < b, nz, nz + 1)  # 0..T-1 with b skipped
    full = jnp.arange(T, dtype=jnp.int32)
    oi = jnp.concatenate(
        [b[None], jnp.full((T - 1,), b, jnp.int32), nz, jnp.repeat(full, T)]
    )
    oj = jnp.concatenate(
        [b[None], nz, jnp.full((T - 1,), b, jnp.int32), jnp.tile(full, T)]
    )
    return oi, oj


def _bordered_order(tr: int, tc: int) -> tuple[jax.Array, jax.Array]:
    """Static tile-visit order for a bordered round (pivot at tile (0,0)).

    g=0 → corner (0,0); g ∈ [1, tc) → border-row tiles (0, j); g ∈
    [tc, tc+tr-1) → border-col tiles (i, 0); then phase 3 over all tr·tc
    tiles row-major.  tr·tc + tr + tc - 1 steps — the square ``_round_order``
    with b=0, generalized to a rectangular tile grid.
    """
    ri = jnp.arange(1, tr, dtype=jnp.int32)
    ci = jnp.arange(1, tc, dtype=jnp.int32)
    zero = jnp.zeros((1,), jnp.int32)
    oi = jnp.concatenate(
        [zero, jnp.zeros((tc - 1,), jnp.int32), ri,
         jnp.repeat(jnp.arange(tr, dtype=jnp.int32), tc)]
    )
    oj = jnp.concatenate(
        [zero, ci, jnp.zeros((tr - 1,), jnp.int32),
         jnp.tile(jnp.arange(tc, dtype=jnp.int32), tr)]
    )
    return oi, oj


# --------------------------------------------------------- phase recurrences
# The per-phase ⊕/⊗ chains, factored out of the kernel bodies so the TPU
# round (_round_kernel below) and the GPU round (kernels/fw_round_gpu.py)
# run the IDENTICAL per-element op sequence — bit-equality across backends
# holds by construction, not by parallel maintenance.  All of them are
# ellipsis-indexed: the same chain runs with or without a leading batch dim.
# ``mosaic=True`` (the TPU kernel bodies) picks rows/columns without gathers
# (``minplus_matmul._col``/``_row``); the XLA twins index them.  Phase 3 is
# ``minplus_matmul._stage_chunks``, the bk-chunk sequence of
# ``semiring_matmul``'s k grid.


def _close_diag(
    t: jax.Array, s: int, semiring: Semiring, mosaic: bool = False
) -> jax.Array:
    """Phase 1: close an (s,s) diagonal tile under k ∈ [0, s)."""

    def body(k, t):
        return semiring.add(
            t, semiring.mul(_col(t, k, mosaic), _row(t, k, mosaic))
        )

    return jax.lax.fori_loop(0, s, body, t)


def _close_row_panel(
    p: jax.Array, d: jax.Array, s: int, semiring: Semiring,
    mosaic: bool = False,
) -> jax.Array:
    """Phase 2 (row band): rows live in the pivot block → a-side is ``d``."""

    def body(k, p):
        return semiring.add(
            p, semiring.mul(_col(d, k, mosaic), _row(p, k, mosaic))
        )

    return jax.lax.fori_loop(0, s, body, p)


def _close_col_panel(
    p: jax.Array, d: jax.Array, s: int, semiring: Semiring,
    mosaic: bool = False,
) -> jax.Array:
    """Phase 2 (col band): columns live in the pivot block → b-side is ``d``."""

    def body(k, p):
        return semiring.add(
            p, semiring.mul(_col(p, k, mosaic), _row(d, k, mosaic))
        )

    return jax.lax.fori_loop(0, s, body, p)


def _fill_lane_cache(a, cache_ref, lead):
    """Entry k of ``cache_ref`` ← column k of ``a`` broadcast across lanes.

    The one place phase 3 of the round broadcasts a column: a group rotate
    per ``_PICK_GROUP`` columns, then a static lane broadcast per column.
    """
    K = a.shape[-1]
    G = _PICK_GROUP if K % _PICK_GROUP == 0 else 1

    def body(c, carry):
        ag = _rotate(a, c * G, -1).astype(cache_ref.dtype)
        for kk in range(G):
            cache_ref[lead + (c * G + kk,)] = jnp.broadcast_to(
                _col(ag, kk), ag.shape
            )
        return carry

    jax.lax.fori_loop(0, K // G, body, 0)


def _relax_lane_cache(acc, cache_ref, row_ref, col0, semiring, lead):
    """``_stage_chunks``' ascending-k chain ⊕(acc, ⊗(a[:,k], b[k,:])) with
    column k of ``a`` read from ``cache_ref`` (``_fill_lane_cache``) and the
    b-operand, the (s, s) block of ``row_ref`` at lane offset ``col0``,
    read ``_PICK_GROUP`` rows (one sublane tile) per aligned load: plain
    loads and a sublane broadcast per step, no lane broadcast and no
    rotate.  The cache holds exact copies, so the chain — and every bit —
    is ``_stage_chunks``'.
    """
    K = cache_ref.shape[-3]
    G = _PICK_GROUP if K % _PICK_GROUP == 0 else 1

    def body(c, acc):
        bg = row_ref[lead + (pl.ds(pl.multiple_of(c * G, G), G),
                             pl.ds(col0, K))]
        for kk in range(G):
            acc = semiring.add(acc, semiring.mul(
                cache_ref[lead + (c * G + kk,)].astype(acc.dtype),
                bg[..., kk:kk + 1, :],
            ))
        return acc

    return jax.lax.fori_loop(0, K // G, body, acc)


def _round_kernel(
    oi_ref, oj_ref, own_ref, w_ref, o_ref, row_ref, col_ref, *cache,
    tr: int, tc: int, s: int, bk: int, semiring: Semiring,
    variant: Variant, step_axis: int = 0,
):
    """One multi-stage round on a (tr, tc) tile grid.

    Square single-device rounds run it with tr == tc and the pivot-first
    order arrays; the distributed bordered round runs it rectangular with
    the pivot pinned at tile (0,0).  ``own_ref`` holds the two owner-echo
    tile coordinates (pr, pc): where the local block carries its own copy of
    the global pivot bands (bordered rounds on owner devices), the closed
    scratch values are spliced over those copies so non-idempotent ⊕ never
    re-relaxes an already-closed band.  (-1, -1) — the square case — makes
    every echo a no-op.

    ``cache`` is the lane-broadcast scratch of the "fori" variant
    (``_lane_cache_scratch``): phase 3 visits tiles row-major and its
    a-operand depends on the row alone, so the row's first step (j == 0)
    broadcasts the a-operand's columns once and every step of the row
    loads them.
    """
    g = pl.program_id(step_axis)
    i = oi_ref[g]
    j = oj_ref[g]
    b = oi_ref[0]  # the pivot index (step 0 visits the pivot tile)
    pr = own_ref[0]
    pc = own_ref[1]
    # Batched refs carry a leading batch-block dim; `lead` makes every
    # scratch index batch-rank-agnostic (compute uses ellipsis indexing).
    lead = (slice(None),) if w_ref.ndim == 3 else ()

    @pl.when(g == 0)
    def _phase1():
        t = _close_diag(w_ref[...], s, semiring, mosaic=True)
        o_ref[...] = t
        # Seed both scratch bands with the closed diagonal: phase-3 steps can
        # then read A/B slices unconditionally at any tile index, pivot
        # included (the splice fw_staged did with dynamic_update_slice).
        row_ref[lead + (slice(None), pl.ds(j * s, s))] = t
        col_ref[lead + (pl.ds(i * s, s), slice(None))] = t

    @pl.when((g >= 1) & (g < tc))
    def _phase2_row():
        d = row_ref[lead + (slice(None), pl.ds(b * s, s))]
        p = _close_row_panel(w_ref[...], d, s, semiring, mosaic=True)
        # Owner echo: the tile at border column pc is the device's broadcast
        # copy of the raw diagonal — its closed value is the phase-1 closure,
        # not the phase-2 recurrence (they differ for non-idempotent ⊕).
        p = jnp.where(j == pc, d, p)
        o_ref[...] = p
        row_ref[lead + (slice(None), pl.ds(j * s, s))] = p

    @pl.when((g >= tc) & (g < tc + tr - 1))
    def _phase2_col():
        d = row_ref[lead + (slice(None), pl.ds(b * s, s))]
        p = _close_col_panel(w_ref[...], d, s, semiring, mosaic=True)
        p = jnp.where(i == pr, d, p)
        o_ref[...] = p
        col_ref[lead + (pl.ds(i * s, s), slice(None))] = p

    @pl.when(g >= tc + tr - 1)
    def _phase3():
        a = col_ref[lead + (pl.ds(i * s, s), slice(None))]
        bb = row_ref[lead + (slice(None), pl.ds(j * s, s))]
        # Accumulator input: pivot-band tiles were rewritten this round, so
        # their current value lives in scratch (== a/bb), not in w_ref; the
        # owner-echo rows/cols are a device's local copies of the same bands.
        c = jnp.where(
            (i == b) | (i == pr), bb,
            jnp.where((j == b) | (j == pc), a, w_ref[...]),
        )
        if not cache:
            o_ref[...] = _stage_chunks(
                c, a, bb, bk, semiring, variant, mosaic=True
            )
            return
        (cache_ref,) = cache

        @pl.when(j == 0)
        def _fill():
            _fill_lane_cache(a, cache_ref, lead)

        o_ref[...] = _relax_lane_cache(
            c, cache_ref, row_ref, pl.multiple_of(j * s, s), semiring, lead
        )


def _relax_succ(k, t, ts, a, asucc, bb, mosaic: bool = False):
    """Strict-improvement relaxation step k, carrying successors.

    cand = a[:,k] ⊗ bb[k,:]; where cand < t the distance AND the next hop
    (asucc[:,k]) are taken — the exact update of ``core.paths``, ellipsis-
    indexed so the same chain runs with or without a leading batch dim.
    """
    cand = _col(a, k, mosaic) + _row(bb, k, mosaic)
    better = cand < t
    return (
        jnp.where(better, cand, t),
        jnp.where(better, _col(asucc, k, mosaic), ts),
    )


def _relax_succ_grouped(t, ts, a, asucc, bb):
    """Relax (t, ts) against all K steps of the fixed phase-3 operands,
    ``_PICK_GROUP`` at a time (``minplus_matmul._stage_chunks``' scheme).

    Each group rotates ``a``, ``asucc`` and ``bb`` once and runs its steps
    with static picks; the ascending-k ``_relax_succ`` chain is unchanged.
    A rotate is a pure data movement, so casting it back is exact.
    """
    K = a.shape[-1]
    G = _PICK_GROUP if K % _PICK_GROUP == 0 else 1

    def body(c, carry):
        ag = _rotate(a, c * G, -1).astype(a.dtype)
        asg = _rotate(asucc, c * G, -1).astype(asucc.dtype)
        bg = _rotate(bb, c * G, -2).astype(bb.dtype)
        t, ts = carry
        for kk in range(G):
            t, ts = _relax_succ(kk, t, ts, ag, asg, bg)
        return t, ts

    return jax.lax.fori_loop(0, K // G, body, (t, ts))


def _round_succ_kernel(
    oi_ref, oj_ref, w_ref, s_ref, ow_ref, os_ref,
    rw_ref, cw_ref, rs_ref, cs_ref,
    *, T: int, s: int, step_axis: int = 0,
):
    """One fused pivot round carrying a successor matrix (min-plus only).

    Same multi-stage schedule as ``_round_kernel`` with four scratch bands:
    closed distance row/col bands plus their successor bands.  Every phase
    uses the strict-improvement (<) update, so outputs bit-match
    ``core.paths.fw_blocked_with_successors``.
    """
    g = pl.program_id(step_axis)
    i = oi_ref[g]
    j = oj_ref[g]
    b = oi_ref[0]
    lead = (slice(None),) if w_ref.ndim == 3 else ()

    @pl.when(g == 0)
    def _phase1():
        def body(k, c):
            t, ts = c
            return _relax_succ(k, t, ts, t, ts, t, mosaic=True)

        t, ts = jax.lax.fori_loop(0, s, body, (w_ref[...], s_ref[...]))
        ow_ref[...] = t
        os_ref[...] = ts
        rw_ref[lead + (slice(None), pl.ds(j * s, s))] = t
        cw_ref[lead + (pl.ds(i * s, s), slice(None))] = t
        rs_ref[lead + (slice(None), pl.ds(j * s, s))] = ts
        cs_ref[lead + (pl.ds(i * s, s), slice(None))] = ts

    @pl.when((g >= 1) & (g < T))
    def _phase2_row():
        # Rows live in the pivot block → the a-side successor operand is the
        # closed diagonal's successor tile.
        d = rw_ref[lead + (slice(None), pl.ds(b * s, s))]
        ds = rs_ref[lead + (slice(None), pl.ds(b * s, s))]

        def body(k, c):
            p, ps = c
            return _relax_succ(k, p, ps, d, ds, p, mosaic=True)

        p, ps = jax.lax.fori_loop(0, s, body, (w_ref[...], s_ref[...]))
        ow_ref[...] = p
        os_ref[...] = ps
        rw_ref[lead + (slice(None), pl.ds(j * s, s))] = p
        rs_ref[lead + (slice(None), pl.ds(j * s, s))] = ps

    @pl.when((g >= T) & (g < 2 * T - 1))
    def _phase2_col():
        # Columns k live in the pivot block → the a-side is the panel's own
        # (evolving) distance/successor columns.
        d = rw_ref[lead + (slice(None), pl.ds(b * s, s))]

        def body(k, c):
            p, ps = c
            return _relax_succ(k, p, ps, p, ps, d, mosaic=True)

        p, ps = jax.lax.fori_loop(0, s, body, (w_ref[...], s_ref[...]))
        ow_ref[...] = p
        os_ref[...] = ps
        cw_ref[lead + (pl.ds(i * s, s), slice(None))] = p
        cs_ref[lead + (pl.ds(i * s, s), slice(None))] = ps

    @pl.when(g >= 2 * T - 1)
    def _phase3():
        a = cw_ref[lead + (pl.ds(i * s, s), slice(None))]
        asucc = cs_ref[lead + (pl.ds(i * s, s), slice(None))]
        bb = rw_ref[lead + (slice(None), pl.ds(j * s, s))]
        bsucc = rs_ref[lead + (slice(None), pl.ds(j * s, s))]
        c = jnp.where(i == b, bb, jnp.where(j == b, a, w_ref[...]))
        cs = jnp.where(i == b, bsucc, jnp.where(j == b, asucc, s_ref[...]))
        c, cs = _relax_succ_grouped(c, cs, a, asucc, bb)
        ow_ref[...] = c
        os_ref[...] = cs


def _resolve_batch_block(B: int, n: int, s: int, batch_block: int | None,
                         *, word: int, bk: int = 32, variant: str = "fori",
                         successors: bool = False) -> int:
    """Largest divisor of B (≤ requested) whose scratch bands fit VMEM."""
    if batch_block is not None:
        if B % batch_block:
            raise ValueError(
                f"batch_block={batch_block} must divide the batch size {B}"
            )
        return batch_block
    from repro.apsp import plan  # call-time import: apsp imports this module

    return plan.auto_batch_block(
        B, n, s, bk=bk, variant=variant, word=word, successors=successors
    )


def _lane_cache_scratch(pltpu, variant, s, dtype, lead=()):
    """The round's phase-3 lane-broadcast cache, ``lead + (s, s, s)``
    32-bit words (16-bit storage widens, as picks do), for the "fori"
    variant; the "unroll" and "broadcast" variants keep ``_stage_chunks``'
    bk-chunk lowerings and no cache."""
    if variant != "fori":
        return []
    return [pltpu.VMEM(lead + (s, s, s), _WIDE.get(jnp.dtype(dtype), dtype))]


def _batch_grid_spec(pltpu, B, bb, s, steps, scratch, extra_in=0,
                     num_prefetch=3):
    """PrefetchScalarGridSpec for the batched round: leading batch grid dim,
    (bb,s,s) tiles, per-graph scratch bands.  ``num_prefetch`` is 3 for the
    plain round (order arrays + owner-echo scalars) and 2 for the successor
    round (order arrays only)."""
    if num_prefetch == 3:
        idx = lambda bi, g, oi, oj, own: (bi, oi[g], oj[g])
    else:
        idx = lambda bi, g, oi, oj: (bi, oi[g], oj[g])
    spec = pl.BlockSpec((bb, s, s), idx)
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_prefetch,
        grid=(B // bb, steps),
        in_specs=[spec] * (1 + extra_in),
        out_specs=[spec] * (1 + extra_in) if extra_in else spec,
        scratch_shapes=scratch,
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "bk", "batch_block", "variant", "semiring",
                     "interpret"),
)
def fw_round(
    w: jax.Array,
    b: jax.Array | int,
    *,
    block_size: int = 128,
    bk: int = 32,
    batch_block: int | None = None,
    variant: Variant = "fori",
    semiring: Semiring = MIN_PLUS,
    interpret: bool | None = None,
) -> jax.Array:
    """One fused pivot round: all three phases in a single ``pallas_call``.

    w: (n, n) with n % block_size == 0, or (B, n, n) to run the same pivot
    round of B graphs through one dispatch (leading batch grid dimension);
    b: pivot round index (may be traced — it only feeds the scalar-prefetch
    order arrays, never a shape).
    bk: phase-3 staging depth of the "unroll" and "broadcast" variants
    (clamped to a divisor of block_size); "fori" runs all block_size steps
    in one loop and does not read it (``plan.kernel_bk``).
    batch_block: graphs per grid step in the batched case (must divide B;
    None → largest divisor whose scratch bands fit the VMEM budget).
    """
    if interpret is None:
        from repro.kernels.ops import default_interpret

        interpret = default_interpret()
    batched = w.ndim == 3
    n = w.shape[-1]
    s = block_size
    if w.ndim not in (2, 3) or w.shape[-2] != n or n % s:
        raise ValueError(
            f"w must be (n,n) or (B,n,n) with n % {s} == 0, got {w.shape}"
        )
    pltpu = compat.pallas_tpu("fw_round needs pallas TPU scratch + scalar prefetch")
    compat.check_tpu_lowering(w.dtype, interpret, s)
    T = n // s
    bk = _fit_block(s, bk)
    oi, oj = _round_order(b, T)
    own = jnp.full((2,), -1, jnp.int32)  # no owner echo in the square round
    word = jnp.dtype(w.dtype).itemsize
    if batched:
        B = w.shape[0]
        bb = _resolve_batch_block(
            B, n, s, batch_block, word=word, bk=bk, variant=variant
        )
        grid_spec = _batch_grid_spec(
            pltpu, B, bb, s, T * T + 2 * T - 1,
            [pltpu.VMEM((bb, s, n), w.dtype),  # closed row bands, per graph
             pltpu.VMEM((bb, n, s), w.dtype)]  # closed col bands, per graph
            + _lane_cache_scratch(pltpu, variant, s, w.dtype, (bb,)),
        )
        step_axis, semantics = 1, ("arbitrary", "arbitrary")
    else:
        spec = pl.BlockSpec((s, s), lambda g, oi, oj, own: (oi[g], oj[g]))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(T * T + 2 * T - 1,),
            in_specs=[spec],
            out_specs=spec,
            scratch_shapes=[
                pltpu.VMEM((s, n), w.dtype),  # closed row band (diag at col b)
                pltpu.VMEM((n, s), w.dtype),  # closed col band (diag at row b)
            ] + _lane_cache_scratch(pltpu, variant, s, w.dtype),
        )
        step_axis, semantics = 0, ("arbitrary",)
    kern = functools.partial(
        _round_kernel, tr=T, tc=T, s=s, bk=bk, semiring=semiring,
        variant=variant, step_axis=step_axis,
    )
    return pl.pallas_call(
        kern,
        name="fw_round",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype),
        interpret=interpret,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=semantics
        ),
    )(oi, oj, own, w)


def _resolve_bordered_batch_block(
    B: int, rows: int, cols: int, s: int, batch_block: int | None,
    *, word: int, bk: int = 32, variant: str = "fori",
    vmem_budget: int | None = None,
) -> int:
    """Largest divisor of B whose bordered scratch bands fit VMEM."""
    if batch_block is not None:
        if B % batch_block:
            raise ValueError(
                f"batch_block={batch_block} must divide the batch size {B}"
            )
        return batch_block
    from repro.apsp import plan  # call-time import: apsp imports this module

    return plan.auto_bordered_batch_block(
        B, rows, cols, s, bk, word=word, variant=variant,
        vmem_budget=vmem_budget,
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "bk", "batch_block", "variant", "semiring",
                     "interpret"),
)
def fw_round_bordered(
    w: jax.Array,
    owner_row: jax.Array | int = -1,
    owner_col: jax.Array | int = -1,
    *,
    block_size: int = 128,
    bk: int = 32,
    batch_block: int | None = None,
    variant: Variant = "fori",
    semiring: Semiring = MIN_PLUS,
    interpret: bool | None = None,
) -> jax.Array:
    """One fused *bordered* round: the distributed per-device dispatch.

    w: (rows, cols) or (B, rows, cols) pivot-bordered local matrix — the
    broadcast raw (s,s) pivot tile in the top-left corner, the raw pivot
    row/column panel slices as the first block-row/-column, the device's
    local W block as the remainder; rows % block_size == cols % block_size
    == 0.  Phases 1-3 of the round run in ONE ``pallas_call`` on the
    rectangular tile grid (pivot pinned at tile (0,0)); the returned matrix
    carries the closed border and the fully relaxed local block (callers
    slice ``[..., s:, s:]``).

    owner_row / owner_col: bordered *tile* coordinates at which the local
    block holds the device's own copy of the global pivot row/column band
    (-1 when it does not) — may be traced; they feed the owner-echo splice
    that keeps the solve bitwise equal to the single-device kernel for
    non-idempotent ⊕.  Both scalars are shared across a batch (ownership is
    a device property, not a graph property).
    """
    if interpret is None:
        from repro.kernels.ops import default_interpret

        interpret = default_interpret()
    batched = w.ndim == 3
    rows, cols = w.shape[-2:]
    s = block_size
    if w.ndim not in (2, 3) or rows % s or cols % s:
        raise ValueError(
            f"w must be (rows,cols) or (B,rows,cols) with both dims a "
            f"multiple of {s}, got {w.shape}"
        )
    pltpu = compat.pallas_tpu(
        "fw_round_bordered needs pallas TPU scratch + scalar prefetch"
    )
    compat.check_tpu_lowering(w.dtype, interpret, s)
    tr, tc = rows // s, cols // s
    bk = _fit_block(s, bk)
    oi, oj = _bordered_order(tr, tc)
    own = jnp.stack([
        jnp.asarray(owner_row, jnp.int32), jnp.asarray(owner_col, jnp.int32)
    ])
    steps = tr * tc + tr + tc - 1
    word = jnp.dtype(w.dtype).itemsize
    if batched:
        B = w.shape[0]
        bb = _resolve_bordered_batch_block(
            B, rows, cols, s, batch_block, word=word, bk=bk, variant=variant
        )
        grid_spec = _batch_grid_spec(
            pltpu, B, bb, s, steps,
            [pltpu.VMEM((bb, s, cols), w.dtype),  # closed border row band
             pltpu.VMEM((bb, rows, s), w.dtype)]  # closed border col band
            + _lane_cache_scratch(pltpu, variant, s, w.dtype, (bb,)),
        )
        step_axis, semantics = 1, ("arbitrary", "arbitrary")
    else:
        spec = pl.BlockSpec((s, s), lambda g, oi, oj, own: (oi[g], oj[g]))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(steps,),
            in_specs=[spec],
            out_specs=spec,
            scratch_shapes=[
                pltpu.VMEM((s, cols), w.dtype),  # closed border row band
                pltpu.VMEM((rows, s), w.dtype),  # closed border col band
            ] + _lane_cache_scratch(pltpu, variant, s, w.dtype),
        )
        step_axis, semantics = 0, ("arbitrary",)
    kern = functools.partial(
        _round_kernel, tr=tr, tc=tc, s=s, bk=bk, semiring=semiring,
        variant=variant, step_axis=step_axis,
    )
    return pl.pallas_call(
        kern,
        name="fw_round_bordered",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype),
        interpret=interpret,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=semantics
        ),
    )(oi, oj, own, w)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "batch_block", "interpret"),
)
def fw_round_with_successors(
    w: jax.Array,
    succ: jax.Array,
    b: jax.Array | int,
    *,
    block_size: int = 128,
    batch_block: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One fused pivot round carrying distances AND next hops (min-plus).

    w / succ: (n, n) or (B, n, n) distance and successor matrices (succ is
    integer next-hop indices, -1 = no path).  Returns the closed pair for
    pivot round ``b``; bit-matches one round of
    ``core.paths.fw_blocked_with_successors``.
    """
    if interpret is None:
        from repro.kernels.ops import default_interpret

        interpret = default_interpret()
    batched = w.ndim == 3
    n = w.shape[-1]
    s = block_size
    if w.ndim not in (2, 3) or w.shape[-2] != n or n % s:
        raise ValueError(
            f"w must be (n,n) or (B,n,n) with n % {s} == 0, got {w.shape}"
        )
    if succ.shape != w.shape:
        raise ValueError(f"succ shape {succ.shape} != w shape {w.shape}")
    pltpu = compat.pallas_tpu("fw_round_with_successors needs pallas TPU scratch")
    compat.check_tpu_lowering(w.dtype, interpret, s)
    T = n // s
    oi, oj = _round_order(b, T)
    word = max(jnp.dtype(w.dtype).itemsize, jnp.dtype(succ.dtype).itemsize)
    out_shape = (
        jax.ShapeDtypeStruct(w.shape, w.dtype),
        jax.ShapeDtypeStruct(succ.shape, succ.dtype),
    )
    if batched:
        B = w.shape[0]
        bb = _resolve_batch_block(
            B, n, s, batch_block, word=word, successors=True
        )
        grid_spec = _batch_grid_spec(
            pltpu, B, bb, s, T * T + 2 * T - 1,
            [pltpu.VMEM((bb, s, n), w.dtype),
             pltpu.VMEM((bb, n, s), w.dtype),
             pltpu.VMEM((bb, s, n), succ.dtype),
             pltpu.VMEM((bb, n, s), succ.dtype)],
            extra_in=1,
            num_prefetch=2,
        )
        step_axis, semantics = 1, ("arbitrary", "arbitrary")
    else:
        spec = pl.BlockSpec((s, s), lambda g, oi, oj: (oi[g], oj[g]))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(T * T + 2 * T - 1,),
            in_specs=[spec, spec],
            out_specs=[spec, spec],
            scratch_shapes=[
                pltpu.VMEM((s, n), w.dtype),     # closed distance row band
                pltpu.VMEM((n, s), w.dtype),     # closed distance col band
                pltpu.VMEM((s, n), succ.dtype),  # successor row band
                pltpu.VMEM((n, s), succ.dtype),  # successor col band
            ],
        )
        step_axis, semantics = 0, ("arbitrary",)
    kern = functools.partial(
        _round_succ_kernel, T=T, s=s, step_axis=step_axis
    )
    return pl.pallas_call(
        kern,
        name="fw_round_with_successors",
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=semantics
        ),
    )(oi, oj, w, succ)
