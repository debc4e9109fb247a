"""Decremental repair: affected-set marking + the restricted row sweep.

PR 7's rank-1 repair (``kernels/fw_repair.py``) absorbs ⊕-*improving* edge
updates in O(E·n²); deletions and worsenings are structural — the old
closure holds commitments no ⊕-merge can undo — and until now forced a full
O(n³) re-solve.  This module is the decremental fast path
(``ApspEngine.repair_del``): a two-stage repair whose cost scales with the
*affected* region, not the matrix.

**Stage 1 — marking** (``mark_affected``, host/XLA).  A pair (i, j) can
only change when its shortest path is witnessed through a deleted edge
(u, v) with old weight w₀::

    affected(i, j)  ⇐  d0[i,u] ⊗ w₀ ⊗ d0[v,j] == d0[i,j]  and
                       d0[i,j] ≠ 0̄

(sub-path optimality: if the optimal i→j path used the edge, its prefix
to u and suffix from v are themselves optimal, so the witness meets the
closure value; the test over-approximates — a pair with an *equal-cost*
path through the edge that happened to route elsewhere is marked too,
which costs work but never correctness.  An edge on NO shortest path
witnesses strictly ⊕-worse everywhere, so its affected set is exactly
empty — the serving layer's cheap "nothing to do" exit).  Affected
entries are reset to the *updated* weight ``w1[i,j]`` (their direct
edge), unaffected entries keep their old closure value — deletions only
⊕-worsen, and an unaffected pair's optimal path is still intact, so its
value is final.
For the bit-packed or_and lowering the test is per *lane*:
``aff = d0[:,u] & d0[u,v] & d0[v,:]`` is exactly the lane set whose
reachability was witnessed through the deleted word-plane bits, and the
reset splices ``w1`` bits into those lanes only.

**Stage 2 — the restricted row sweep** (``fw_repair_del_sweep``).  Only
rows with ≥ 1 affected entry (the affected row set S, |S| = a) can change;
every other row is already closed.  The sweep is blocked FW restricted to
those rows: per pivot block b it (1) assembles the (s, n) pivot band —
static rows read from the reset matrix, evolving rows ∈ S spliced in from
the compact (a, n) strip — (2) closes the band with the *same*
``_close_diag`` / ``_close_row_panel`` recurrences as the fused round,
(3) closes the strip's block columns (``_close_col_panel``) and relaxes the
whole strip against the closed band through the same ``_stage_compute``
bk-chunk sequence (``_stage_chunks``), and (4) strip rows inside the pivot
block take their band-closed values.  Per-round traffic is (s + 2a)·n words
against the full round's 2n² — ``plan.repair_del_hbm_bytes`` models the
crossover ``plan.should_repair_del`` falls back on.

Correctness contract (KERNELS.md §Decremental repair):

  * **⊕-idempotent semirings only** (min_plus / max_plus / max_min /
    or_and, any storage lowering).  The sweep's static rows are relaxed
    zero times instead of once-per-pivot — a value no-op exactly when
    ``x ⊕ x == x``.  Non-idempotent plus_mul sums over *all* paths; no
    restricted recomputation is sound there and ``ApspEngine.repair_del``
    falls back to a full re-solve (still bitwise, trivially).
  * **exact arithmetic** — integer-valued weights (the same contract as
    the rank-1 repair): the witness equality and the "intact rows are
    final" argument both assume ⊕/⊗ chains reproduce path costs exactly.
  * the result then equals a full re-solve of the updated graph *in
    value*, hence bitwise on exactly-represented weights — dist AND succ
    (tie-free weights make the next hop unique, so the strict-<
    relaxation lands on the re-solve's successor).

The Pallas lowering (``_sweep_round``) is one ``pallas_call`` per round on
a (T + Ta·T)-step grid — band closure first, then the strip tiles — with
the closed band in (s, n) VMEM scratch and the closed strip block-columns
in (a, s) scratch, reusing the fused round's phase helpers so TPU and the
XLA twin (``fw_repair_del_sweep_ref``) are bitwise by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.semiring import MIN_PLUS, Semiring
from repro.kernels.fw_round import (
    _close_col_panel,
    _close_diag,
    _close_row_panel,
    _relax_succ,
)
from repro.kernels.minplus_matmul import Variant, _fit_block, _stage_chunks
from repro.utils import compat


# ------------------------------------------------------------------ stage 1
def _affected_mask(dist, u, v, wold, ecount, semiring: Semiring):
    """The affected-set over-approximation: OR of per-edge witness tests.

    dist: (m, m) closure; u/v/wold: (E_pad,) deletion endpoints + the
    *old* weight each edge carried (entries ≥ ecount are padding and
    masked out); returns a bool (m, m) mask — or, for the bit-packed
    or_and lowering, an int32 lane mask per entry (wold is then the old
    word bits: only lanes that actually held the edge can be affected).
    """
    packed = "packed" in semiring.name
    zero = jnp.asarray(semiring.zero, dist.dtype)
    init = jnp.zeros(dist.shape, jnp.int32 if packed else bool)

    def body(e, aff):
        ue, ve = u[e], v[e]
        du = jax.lax.dynamic_slice_in_dim(dist, ue, 1, axis=-1)   # (m, 1)
        dv = jax.lax.dynamic_slice_in_dim(dist, ve, 1, axis=-2)   # (1, m)
        wit = semiring.mul(semiring.mul(du, wold[e]), dv)
        if packed:
            upd = wit  # lanes whose reachability is witnessed through (u,v)
        else:
            upd = (wit == dist) & (dist != zero)
        live = e < ecount
        return aff | jnp.where(live, upd, init)

    return jax.lax.fori_loop(0, u.shape[0], body, init)


def mark_affected(
    dist: jax.Array,
    w1: jax.Array,
    u: jax.Array,
    v: jax.Array,
    wold: jax.Array,
    ecount: jax.Array | int,
    *,
    semiring: Semiring = MIN_PLUS,
):
    """Stage 1: (d_init, affected-row mask, affected-entry count).

    dist: the pre-deletion closure; w1: the *updated* weight matrix (the
    deletions already applied); u/v/wold/ecount: the deleted-edge batch
    with each edge's pre-deletion weight.  d_init resets every affected
    entry to its direct edge in w1 and keeps the (final) closure value
    everywhere else — the admissible start state the restricted sweep
    closes.
    """
    aff = _affected_mask(dist, u, v, wold, ecount, semiring)
    if "packed" in semiring.name:
        d_init = (dist & ~aff) | (w1 & aff)
        hit = aff != 0
    else:
        d_init = jnp.where(aff, w1, dist)
        hit = aff
    return d_init, hit.any(axis=-1), jnp.sum(hit, dtype=jnp.int32)


def mark_affected_with_successors(
    dist: jax.Array,
    succ: jax.Array,
    w1: jax.Array,
    u: jax.Array,
    v: jax.Array,
    wold: jax.Array,
    ecount: jax.Array | int,
    *,
    semiring: Semiring = MIN_PLUS,
):
    """Stage 1 with a next-hop table: affected entries also reset their
    successor to the direct-edge initialization (``_init_successors(w1)``),
    exactly the start state a full re-solve of w1 uses."""
    from repro.core.paths import _init_successors

    aff = _affected_mask(dist, u, v, wold, ecount, semiring)
    d_init = jnp.where(aff, w1, dist)
    s_init = jnp.where(aff, _init_successors(w1), succ)
    return d_init, s_init, aff.any(axis=-1), jnp.sum(aff, dtype=jnp.int32)


# ------------------------------------------------------- stage 2 (XLA twin)
def _band_overlay(static, A, rows, o, s):
    """The (s, m) pivot band at row offset o: static rows from the reset
    matrix, evolving rows ∈ S spliced in from the strip.  Returns the band
    plus the (in_blk, local) coordinates the round's final splice reuses."""
    m = static.shape[-1]
    band = jax.lax.dynamic_slice(static, (o, 0), (s, m))
    local = rows - o
    in_blk = (local >= 0) & (local < s)
    # Out-of-block strip rows scatter to index s — out of bounds — and drop;
    # padding rows (index m) never land in any block.
    safe = jnp.where(in_blk, local, s)
    band = band.at[safe].set(A, mode="drop")
    return band, in_blk, local


def fw_repair_del_sweep_ref(
    d_init: jax.Array,
    rows: jax.Array,
    *,
    block_size: int,
    bk: int = 32,
    variant: Variant = "fori",
    semiring: Semiring = MIN_PLUS,
) -> jax.Array:
    """Execution-grade XLA twin of the restricted row sweep.

    d_init: (m, m) reset matrix from ``mark_affected`` (m % block_size
    == 0); rows: (a_pad,) sorted affected row indices, padded with m
    (out-of-range ⇒ inert).  Returns the repaired (m, m) closure.  The
    per-element ⊕/⊗ chains are the fused round's own recurrences, so the
    Pallas lowering (``fw_repair_del_sweep``) is bitwise equal.
    """
    s = block_size
    m = d_init.shape[-1]
    bk = _fit_block(s, bk)
    T = m // s
    # Gather the strip; pad rows clip to row m-1 (a padding row of the
    # matrix) and evolve as inert duplicates — every write-back drops them.
    A = jnp.take(d_init, rows, axis=0, mode="clip")

    def round_body(b, A):
        o = b * s
        band, in_blk, local = _band_overlay(d_init, A, rows, o, s)
        diag = _close_diag(jax.lax.dynamic_slice(band, (0, o), (s, s)),
                           s, semiring)
        band = _close_row_panel(band, diag, s, semiring)
        band = jax.lax.dynamic_update_slice(band, diag, (0, o))
        acol = _close_col_panel(
            jax.lax.dynamic_slice(A, (0, o), (A.shape[0], s)), diag, s,
            semiring,
        )
        # Phase-3 accumulator: the strip's block columns take their closed
        # values (the fused round's col-band splice), then every strip
        # element relaxes through the same bk-chunk sequence.
        A = jax.lax.dynamic_update_slice(A, acol, (0, o))
        A = _stage_chunks(A, acol, band, bk, semiring, variant)
        # Strip rows inside the pivot block were closed in the band; their
        # phase-3 value is discarded in favor of the band closure (a value
        # no-op for idempotent ⊕ — the sweep's contract).
        closed = jnp.take(band, jnp.where(in_blk, local, 0), axis=0,
                          mode="clip")
        return jnp.where(in_blk[:, None], closed, A)

    A = jax.lax.fori_loop(0, T, round_body, A)
    return d_init.at[rows].set(A, mode="drop")


def fw_repair_del_sweep_with_successors_ref(
    d_init: jax.Array,
    s_init: jax.Array,
    rows: jax.Array,
    *,
    block_size: int,
) -> tuple[jax.Array, jax.Array]:
    """The restricted row sweep carrying a next-hop table (min-plus float).

    Same schedule as ``fw_repair_del_sweep_ref`` with every phase running
    the strict-improvement relaxation of ``core.paths`` (``_relax_succ``),
    and four band/strip pairs (distance + successor).  This XLA lowering is
    execution-grade on every backend — successor tables are a serving-side
    (host-walked) structure, so no Pallas variant exists yet (headroom,
    like the distributed solve being distance-only).
    """
    s = block_size
    m = d_init.shape[-1]
    T = m // s
    A = jnp.take(d_init, rows, axis=0, mode="clip")
    As = jnp.take(s_init, rows, axis=0, mode="clip")

    def round_body(b, carry):
        A, As = carry
        o = b * s
        band, in_blk, local = _band_overlay(d_init, A, rows, o, s)
        bands, _, _ = _band_overlay(s_init, As, rows, o, s)

        diag = jax.lax.dynamic_slice(band, (0, o), (s, s))
        dsucc = jax.lax.dynamic_slice(bands, (0, o), (s, s))

        def p1(k, c):
            t, ts = c
            return _relax_succ(k, t, ts, t, ts, t)

        diag, dsucc = jax.lax.fori_loop(0, s, p1, (diag, dsucc))

        def p2r(k, c):
            p, ps = c
            return _relax_succ(k, p, ps, diag, dsucc, p)

        band, bands = jax.lax.fori_loop(0, s, p2r, (band, bands))
        band = jax.lax.dynamic_update_slice(band, diag, (0, o))
        bands = jax.lax.dynamic_update_slice(bands, dsucc, (0, o))

        acol = jax.lax.dynamic_slice(A, (0, o), (A.shape[0], s))
        acols = jax.lax.dynamic_slice(As, (0, o), (As.shape[0], s))

        def p2c(k, c):
            p, ps = c
            return _relax_succ(k, p, ps, p, ps, diag)

        acol, acols = jax.lax.fori_loop(0, s, p2c, (acol, acols))
        A = jax.lax.dynamic_update_slice(A, acol, (0, o))
        As = jax.lax.dynamic_update_slice(As, acols, (0, o))

        def p3(k, c):
            t, ts = c
            return _relax_succ(k, t, ts, acol, acols, band)

        A, As = jax.lax.fori_loop(0, s, p3, (A, As))
        safe = jnp.where(in_blk, local, 0)
        closed = jnp.take(band, safe, axis=0, mode="clip")
        closeds = jnp.take(bands, safe, axis=0, mode="clip")
        return (
            jnp.where(in_blk[:, None], closed, A),
            jnp.where(in_blk[:, None], closeds, As),
        )

    A, As = jax.lax.fori_loop(0, T, round_body, (A, As))
    return (
        d_init.at[rows].set(A, mode="drop"),
        s_init.at[rows].set(As, mode="drop"),
    )


# --------------------------------------------------- stage 2 (Pallas round)
def _sweep_order(b: jax.Array, T: int, Ta: int) -> tuple[jax.Array, jax.Array]:
    """Step → (strip row tile, column tile) for one sweep round.

    g ∈ [0, T): band closure, pivot column first (g=0 is the diagonal);
    then Ta groups of T strip steps, each visiting its row tile's pivot
    column (the ``_close_col_panel`` step) before the other columns.
    """
    b = jnp.asarray(b, jnp.int32)
    nz = jnp.arange(T - 1, dtype=jnp.int32)
    nz = jnp.where(nz < b, nz, nz + 1)  # 0..T-1 with b skipped
    cols = jnp.concatenate([b[None], nz])  # (T,) pivot-first column order
    oj = jnp.tile(cols, Ta + 1)
    oi = jnp.concatenate(
        [jnp.zeros((T,), jnp.int32),
         jnp.repeat(jnp.arange(Ta, dtype=jnp.int32), T)]
    )
    return oi, oj


def _sweep_round_kernel(
    oi_ref, oj_ref, band_ref, a_ref, ob_ref, oa_ref, bscr_ref, cscr_ref,
    *, T: int, s: int, sa: int, bk: int, semiring: Semiring, variant: Variant,
):
    """One restricted round: close the assembled band, relax the strip.

    Every step writes BOTH outputs (closed-band steps echo the strip tile
    through unchanged and vice versa — later steps overwrite, so the
    copy-out of a multi-buffered output block is never undefined).
    """
    g = pl.program_id(0)
    r = oi_ref[g]
    j = oj_ref[g]
    b = oj_ref[0]  # step 0 visits the pivot column

    @pl.when(g == 0)
    def _phase1():
        t = _close_diag(band_ref[...], s, semiring, mosaic=True)
        bscr_ref[:, pl.ds(j * s, s)] = t
        ob_ref[...] = t
        oa_ref[...] = a_ref[...]

    @pl.when((g >= 1) & (g < T))
    def _phase2_row():
        d = bscr_ref[:, pl.ds(b * s, s)]
        p = _close_row_panel(band_ref[...], d, s, semiring, mosaic=True)
        bscr_ref[:, pl.ds(j * s, s)] = p
        ob_ref[...] = p
        oa_ref[...] = a_ref[...]

    @pl.when((g >= T) & (j == b))
    def _phase2_col():
        d = bscr_ref[:, pl.ds(b * s, s)]
        p = _close_col_panel(a_ref[...], d, s, semiring, mosaic=True)
        cscr_ref[pl.ds(r * sa, sa), :] = p
        oa_ref[...] = p
        ob_ref[...] = bscr_ref[:, pl.ds(j * s, s)]

    @pl.when((g >= T) & (j != b))
    def _phase3():
        a = cscr_ref[pl.ds(r * sa, sa), :]
        bb = bscr_ref[:, pl.ds(j * s, s)]
        oa_ref[...] = _stage_chunks(
            a_ref[...], a, bb, bk, semiring, variant, mosaic=True
        )
        ob_ref[...] = bb


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "bk", "variant", "semiring", "interpret"),
)
def _sweep_round(
    band: jax.Array,
    A: jax.Array,
    b: jax.Array | int,
    *,
    block_size: int,
    bk: int = 32,
    variant: Variant = "fori",
    semiring: Semiring = MIN_PLUS,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One restricted round as ONE ``pallas_call``: T band-closure steps
    followed by Ta·T strip steps, the closed band staged through (s, m)
    VMEM scratch and the closed strip block-columns through (a_pad, s)
    scratch — the fused round's dataflow on a band + strip working set.

    band: (s, m) assembled pivot band (static rows overlaid with the
    current strip values — ``_band_overlay``); A: (a_pad, m) strip;
    b: pivot block index (traced, feeds the scalar-prefetch order only).
    Returns (closed band, relaxed strip); the in-block strip-row splice
    happens in the driver, outside the kernel.
    """
    if interpret is None:
        from repro.kernels.ops import default_interpret

        interpret = default_interpret()
    s = block_size
    m = band.shape[-1]
    a_pad = A.shape[0]
    if band.shape != (s, m) or m % s or A.shape[1] != m:
        raise ValueError(f"bad band/strip shapes {band.shape} / {A.shape}")
    sa = min(s, a_pad)
    if a_pad % sa:
        raise ValueError(f"a_pad={a_pad} must be a multiple of sa={sa}")
    pltpu = compat.pallas_tpu(
        "fw_repair_del needs pallas TPU scratch + scalar prefetch"
    )
    compat.check_tpu_lowering(band.dtype, interpret, s)
    T = m // s
    Ta = a_pad // sa
    bk = _fit_block(s, bk)
    oi, oj = _sweep_order(b, T, Ta)
    band_spec = pl.BlockSpec((s, s), lambda g, oi, oj: (0, oj[g]))
    a_spec = pl.BlockSpec((sa, s), lambda g, oi, oj: (oi[g], oj[g]))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T + Ta * T,),
        in_specs=[band_spec, a_spec],
        out_specs=[band_spec, a_spec],
        scratch_shapes=[
            pltpu.VMEM((s, m), band.dtype),      # closed pivot band
            pltpu.VMEM((a_pad, s), band.dtype),  # closed strip block-cols
        ],
    )
    kern = functools.partial(
        _sweep_round_kernel, T=T, s=s, sa=sa, bk=bk, semiring=semiring,
        variant=variant,
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct(band.shape, band.dtype),
            jax.ShapeDtypeStruct(A.shape, A.dtype),
        ),
        interpret=interpret,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",)
        ),
    )(oi, oj, band, A)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "bk", "variant", "semiring", "interpret"),
)
def fw_repair_del_sweep(
    d_init: jax.Array,
    rows: jax.Array,
    *,
    block_size: int,
    bk: int = 32,
    variant: Variant = "fori",
    semiring: Semiring = MIN_PLUS,
    interpret: bool | None = None,
) -> jax.Array:
    """The restricted row sweep, Pallas-lowered: one ``_sweep_round``
    dispatch per pivot block, XLA gather/scatter gluing the band overlay
    and the in-block row splice between dispatches (O(a·m) each — the
    O(s·m²) work lives in the kernel).  Bitwise equal to
    ``fw_repair_del_sweep_ref`` — the kernel runs the identical phase
    recurrences on identical operands.
    """
    s = block_size
    m = d_init.shape[-1]
    if d_init.ndim != 2 or d_init.shape[0] != m or m % s:
        raise ValueError(
            f"d_init must be (m,m) with m % {s} == 0, got {d_init.shape}"
        )
    T = m // s
    A = jnp.take(d_init, rows, axis=0, mode="clip")

    def round_body(b, A):
        o = b * s
        band, in_blk, local = _band_overlay(d_init, A, rows, o, s)
        band, A = _sweep_round(
            band, A, b, block_size=s, bk=bk, variant=variant,
            semiring=semiring, interpret=interpret,
        )
        closed = jnp.take(band, jnp.where(in_blk, local, 0), axis=0,
                          mode="clip")
        return jnp.where(in_blk[:, None], closed, A)

    A = jax.lax.fori_loop(0, T, round_body, A)
    return d_init.at[rows].set(A, mode="drop")
