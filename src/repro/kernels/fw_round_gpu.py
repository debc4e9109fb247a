"""Fused multi-stage round for the Pallas Triton/Mosaic-GPU backend.

The source paper is a CUDA kernel: its multi-stage round keeps the pivot
tile in shared memory while the row/column panels stream past, so the SM
scheduler can hide global-memory latency behind relaxation compute.  This
module is that schedule through Pallas' Triton lowering — the same fused
round as ``kernels/fw_round.py`` (ONE dispatch per pivot round, pivot-first
tile order, phases classified from ``program_id``) re-expressed with the
resources a GPU grid actually has:

  * **no scalar prefetch** — Triton has no ``PrefetchScalarGridSpec``; the
    ``_round_order``/``_bordered_order`` visit arrays ride along as plain
    int32 tensor operands (full-array BlockSpecs) and each step reads its
    tile coordinates ``oi[g], oj[g]`` directly.  Order construction is
    SHARED with the TPU kernel — one schedule, two lowerings.
  * **no VMEM scratch** — cross-step state (the closed pivot row/col bands)
    lives in two extra *outputs* mapped to the same block every step, i.e.
    global memory, the moral equivalent of the paper keeping the closed
    panel in L2 between phases of the same launch.  The wrapper discards
    them; ``plan.gpu_round_hbm_bytes`` charges their traffic.
  * **full-matrix refs + dynamic tiles** — instead of per-step (s,s) block
    remapping, the kernel sees whole in/out matrices and addresses tile
    (i·s, j·s) with ``pl.ds``; the (s,s) tile and the bk-deep band
    slices are what Triton stages through shared memory/registers —
    ``plan.gpu_round_smem_bytes`` models that working set against the
    per-SM shared-memory budget the way ``fused_round_vmem_bytes`` models
    VMEM.

Bit-identity: every phase body calls the SAME ``_close_diag`` /
``_close_row_panel`` / ``_close_col_panel`` / ``_stage_chunks`` recurrences as
``fw_round._round_kernel`` (and the successor round reuses ``_relax_succ``),
so outputs are bitwise equal to the TPU kernel and the ``kernels/ref.py``
twins on every semiring × storage lowering, batched and bordered —
tests/test_fw_round_gpu.py pins this in interpret mode.

Sequencing caveat: the round's phase ordering (diag → bands → full relax,
communicated through the band buffers) requires the grid steps to execute
*in order*, which Pallas interpret mode guarantees and a real Triton launch
does not (CUDA blocks are scheduled concurrently).  On hardware this kernel
must be driven with a sequential/persistent grid (1 program per step axis,
as lowered here) — the batched leading grid dimension is the parallel one.
Correctness on this container is asserted in interpret mode
(``kernels.ops.default_gpu_interpret``), per the plan/engine dispatch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.semiring import MIN_PLUS, Semiring
from repro.kernels.fw_round import (
    _bordered_order,
    _close_col_panel,
    _close_diag,
    _close_row_panel,
    _relax_succ,
    _round_order,
)
from repro.kernels.minplus_matmul import Variant, _fit_block, _stage_chunks

# Default Triton occupancy hints (overridable per-call; plan.fw_candidates
# sweeps them for the GPU backend).
NUM_WARPS = 4
NUM_STAGES = 2


def _tile(lead, i, j, s):
    """Index tuple for the (s,s) tile at tile coordinates (i, j)."""
    return lead + (pl.ds(i * s, s), pl.ds(j * s, s))


def _round_kernel_gpu(
    oi_ref, oj_ref, own_ref, w_ref, o_ref, row_ref, col_ref,
    *, tr: int, tc: int, s: int, bk: int, semiring: Semiring,
    variant: Variant, step_axis: int = 0,
):
    """One multi-stage round on a (tr, tc) tile grid — GPU lowering.

    Same signature role-for-role as ``fw_round._round_kernel``: the three
    scalar-prefetch operands become ordinary tensor inputs, the two VMEM
    scratch bands become the trailing GMEM outputs.  ``w_ref``/``o_ref``
    are the FULL (rows, cols) matrices (with an optional leading batch-block
    dim); each step addresses its tile dynamically.
    """
    g = pl.program_id(step_axis)
    i = oi_ref[g]
    j = oj_ref[g]
    b = oi_ref[0]  # the pivot index (step 0 visits the pivot tile)
    pr = own_ref[0]
    pc = own_ref[1]
    lead = (slice(None),) if w_ref.ndim == 3 else ()

    @pl.when(g == 0)
    def _phase1():
        t = _close_diag(w_ref[_tile(lead, i, j, s)], s, semiring)
        o_ref[_tile(lead, i, j, s)] = t
        # Seed both bands with the closed diagonal (the TPU kernel's scratch
        # seed): phase-3 steps then read A/B slices unconditionally at any
        # tile index, pivot included.
        row_ref[lead + (slice(None), pl.ds(j * s, s))] = t
        col_ref[lead + (pl.ds(i * s, s), slice(None))] = t

    @pl.when((g >= 1) & (g < tc))
    def _phase2_row():
        d = row_ref[lead + (slice(None), pl.ds(b * s, s))]
        p = _close_row_panel(w_ref[_tile(lead, i, j, s)], d, s, semiring)
        # Owner echo — see fw_round._round_kernel: the border tile at column
        # pc is a broadcast copy of the raw diagonal, whose closed value is
        # the phase-1 closure (≠ the phase-2 recurrence for non-idempotent ⊕).
        p = jnp.where(j == pc, d, p)
        o_ref[_tile(lead, i, j, s)] = p
        row_ref[lead + (slice(None), pl.ds(j * s, s))] = p

    @pl.when((g >= tc) & (g < tc + tr - 1))
    def _phase2_col():
        d = row_ref[lead + (slice(None), pl.ds(b * s, s))]
        p = _close_col_panel(w_ref[_tile(lead, i, j, s)], d, s, semiring)
        p = jnp.where(i == pr, d, p)
        o_ref[_tile(lead, i, j, s)] = p
        col_ref[lead + (pl.ds(i * s, s), slice(None))] = p

    @pl.when(g >= tc + tr - 1)
    def _phase3():
        a = col_ref[lead + (pl.ds(i * s, s), slice(None))]
        bb = row_ref[lead + (slice(None), pl.ds(j * s, s))]
        # Accumulator input: pivot-band tiles were rewritten this round, so
        # their current value lives in the band buffers, not in w_ref.
        c = jnp.where(
            (i == b) | (i == pr), bb,
            jnp.where((j == b) | (j == pc), a,
                      w_ref[_tile(lead, i, j, s)]),
        )
        o_ref[_tile(lead, i, j, s)] = _stage_chunks(
            c, a, bb, bk, semiring, variant
        )


def _round_succ_kernel_gpu(
    oi_ref, oj_ref, w_ref, s_ref, ow_ref, os_ref,
    rw_ref, cw_ref, rs_ref, cs_ref,
    *, T: int, s: int, step_axis: int = 0,
):
    """The fused successor-carrying round (min-plus), GPU lowering.

    Mirrors ``fw_round._round_succ_kernel`` with the four scratch bands as
    GMEM outputs; every relaxation goes through the shared ``_relax_succ``
    strict-improvement chain, so outputs bit-match the TPU kernel and
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
            return _relax_succ(k, t, ts, t, ts, t)

        t, ts = jax.lax.fori_loop(
            0, s,
            body,
            (w_ref[_tile(lead, i, j, s)],
             s_ref[_tile(lead, i, j, s)]),
        )
        ow_ref[_tile(lead, i, j, s)] = t
        os_ref[_tile(lead, i, j, s)] = ts
        rw_ref[lead + (slice(None), pl.ds(j * s, s))] = t
        cw_ref[lead + (pl.ds(i * s, s), slice(None))] = t
        rs_ref[lead + (slice(None), pl.ds(j * s, s))] = ts
        cs_ref[lead + (pl.ds(i * s, s), slice(None))] = ts

    @pl.when((g >= 1) & (g < T))
    def _phase2_row():
        d = rw_ref[lead + (slice(None), pl.ds(b * s, s))]
        ds = rs_ref[lead + (slice(None), pl.ds(b * s, s))]

        def body(k, c):
            p, ps = c
            return _relax_succ(k, p, ps, d, ds, p)

        p, ps = jax.lax.fori_loop(
            0, s,
            body,
            (w_ref[_tile(lead, i, j, s)],
             s_ref[_tile(lead, i, j, s)]),
        )
        ow_ref[_tile(lead, i, j, s)] = p
        os_ref[_tile(lead, i, j, s)] = ps
        rw_ref[lead + (slice(None), pl.ds(j * s, s))] = p
        rs_ref[lead + (slice(None), pl.ds(j * s, s))] = ps

    @pl.when((g >= T) & (g < 2 * T - 1))
    def _phase2_col():
        d = rw_ref[lead + (slice(None), pl.ds(b * s, s))]

        def body(k, c):
            p, ps = c
            return _relax_succ(k, p, ps, p, ps, d)

        p, ps = jax.lax.fori_loop(
            0, s,
            body,
            (w_ref[_tile(lead, i, j, s)],
             s_ref[_tile(lead, i, j, s)]),
        )
        ow_ref[_tile(lead, i, j, s)] = p
        os_ref[_tile(lead, i, j, s)] = ps
        cw_ref[lead + (pl.ds(i * s, s), slice(None))] = p
        cs_ref[lead + (pl.ds(i * s, s), slice(None))] = ps

    @pl.when(g >= 2 * T - 1)
    def _phase3():
        a = cw_ref[lead + (pl.ds(i * s, s), slice(None))]
        asucc = cs_ref[lead + (pl.ds(i * s, s), slice(None))]
        bb = rw_ref[lead + (slice(None), pl.ds(j * s, s))]
        bsucc = rs_ref[lead + (slice(None), pl.ds(j * s, s))]
        c = jnp.where(
            i == b, bb,
            jnp.where(j == b, a, w_ref[_tile(lead, i, j, s)]),
        )
        cs = jnp.where(
            i == b, bsucc,
            jnp.where(j == b, asucc, s_ref[_tile(lead, i, j, s)]),
        )

        def body(k, carry):
            t, ts = carry
            return _relax_succ(k, t, ts, a, asucc, bb)

        c, cs = jax.lax.fori_loop(0, s, body, (c, cs))
        ow_ref[_tile(lead, i, j, s)] = c
        os_ref[_tile(lead, i, j, s)] = cs


def _gpu_specs(batched, bb, steps, rows, cols, s):
    """(matrix, order-vector, owner, row-band, col-band) BlockSpecs + grid.

    Every spec maps to block 0 along the step axis — the whole matrix and
    both band buffers are visible to (and shared by) every step, which is
    how the round's cross-step dataflow works without TPU scratch.  The
    leading batch grid dimension (batched case) DOES advance blocks, so
    batch blocks never share band state.
    """
    if batched:
        grid = None, steps  # caller fills the batch extent
        mat = pl.BlockSpec((bb, rows, cols), lambda bi, g: (bi, 0, 0))
        vec = pl.BlockSpec((steps,), lambda bi, g: (0,))
        own = pl.BlockSpec((2,), lambda bi, g: (0,))
        row = pl.BlockSpec((bb, s, cols), lambda bi, g: (bi, 0, 0))
        col = pl.BlockSpec((bb, rows, s), lambda bi, g: (bi, 0, 0))
    else:
        grid = (steps,)
        mat = pl.BlockSpec((rows, cols), lambda g: (0, 0))
        vec = pl.BlockSpec((steps,), lambda g: (0,))
        own = pl.BlockSpec((2,), lambda g: (0,))
        row = pl.BlockSpec((s, cols), lambda g: (0, 0))
        col = pl.BlockSpec((rows, s), lambda g: (0, 0))
    return grid, mat, vec, own, row, col


def _resolve_gpu_batch_block(B: int, batch_block: int | None) -> int:
    """GPU batch block: default to the whole batch (one band buffer per
    graph lives in GMEM, not on-chip, so there is no VMEM-style pressure to
    subdivide; explicit blocks must divide B as on TPU)."""
    if batch_block is None:
        return B
    if B % batch_block:
        raise ValueError(
            f"batch_block={batch_block} must divide the batch size {B}"
        )
    return batch_block


def _gpu_call(kern, grid, in_specs, out_specs, out_shape, interpret,
              num_warps, num_stages):
    from repro.utils import compat

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=None if interpret else compat.gpu_compiler_params(
            num_warps=num_warps, num_stages=num_stages
        ),
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "bk", "batch_block", "variant", "semiring",
                     "num_warps", "num_stages", "interpret"),
)
def fw_round_gpu(
    w: jax.Array,
    b: jax.Array | int,
    *,
    block_size: int = 128,
    bk: int = 32,
    batch_block: int | None = None,
    variant: Variant = "fori",
    semiring: Semiring = MIN_PLUS,
    num_warps: int = NUM_WARPS,
    num_stages: int = NUM_STAGES,
    interpret: bool | None = None,
) -> jax.Array:
    """One fused pivot round on the Triton backend — ``fw_round``'s twin.

    Same contract: w is (n, n) or (B, n, n) with n % block_size == 0, b is
    the (possibly traced) pivot round index; returns the round-closed
    matrix, bitwise equal to ``fw_round`` and ``ref.fw_round_ref``.
    ``interpret=None`` auto-interprets when no GPU is attached
    (``ops.default_gpu_interpret``); num_warps/num_stages are Triton
    occupancy hints (ignored in interpret mode).
    """
    if interpret is None:
        from repro.kernels.ops import default_gpu_interpret

        interpret = default_gpu_interpret()
    batched = w.ndim == 3
    n = w.shape[-1]
    s = block_size
    if w.ndim not in (2, 3) or w.shape[-2] != n or n % s:
        raise ValueError(
            f"w must be (n,n) or (B,n,n) with n % {s} == 0, got {w.shape}"
        )
    T = n // s
    bk = _fit_block(s, bk)
    oi, oj = _round_order(b, T)
    own = jnp.full((2,), -1, jnp.int32)  # no owner echo in the square round
    steps = T * T + 2 * T - 1
    if batched:
        B = w.shape[0]
        bb = _resolve_gpu_batch_block(B, batch_block)
        grid, mat, vec, ownspec, row, col = _gpu_specs(True, bb, steps, n, n, s)
        grid = (B // bb, grid[1])
        band_lead = (B,)
        step_axis = 1
    else:
        grid, mat, vec, ownspec, row, col = _gpu_specs(False, 1, steps, n, n, s)
        band_lead = ()
        step_axis = 0
    kern = functools.partial(
        _round_kernel_gpu, tr=T, tc=T, s=s, bk=bk, semiring=semiring,
        variant=variant, step_axis=step_axis,
    )
    out, _, _ = _gpu_call(
        kern, grid,
        in_specs=[vec, vec, ownspec, mat],
        out_specs=(mat, row, col),
        out_shape=(
            jax.ShapeDtypeStruct(w.shape, w.dtype),
            jax.ShapeDtypeStruct(band_lead + (s, n), w.dtype),
            jax.ShapeDtypeStruct(band_lead + (n, s), w.dtype),
        ),
        interpret=interpret, num_warps=num_warps, num_stages=num_stages,
    )(oi, oj, own, w)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "bk", "batch_block", "variant", "semiring",
                     "num_warps", "num_stages", "interpret"),
)
def fw_round_bordered_gpu(
    w: jax.Array,
    owner_row: jax.Array | int = -1,
    owner_col: jax.Array | int = -1,
    *,
    block_size: int = 128,
    bk: int = 32,
    batch_block: int | None = None,
    variant: Variant = "fori",
    semiring: Semiring = MIN_PLUS,
    num_warps: int = NUM_WARPS,
    num_stages: int = NUM_STAGES,
    interpret: bool | None = None,
) -> jax.Array:
    """One fused *bordered* round on the Triton backend.

    Same contract as ``fw_round_bordered``: w is the (rows, cols) or
    (B, rows, cols) pivot-bordered local matrix (pivot tile at (0,0)),
    owner_row/owner_col are the owner-echo tile coordinates (-1 = none).
    Bitwise equal to the TPU kernel and ``ref.fw_round_bordered_ref``.
    """
    if interpret is None:
        from repro.kernels.ops import default_gpu_interpret

        interpret = default_gpu_interpret()
    batched = w.ndim == 3
    rows, cols = w.shape[-2:]
    s = block_size
    if w.ndim not in (2, 3) or rows % s or cols % s:
        raise ValueError(
            f"w must be (rows,cols) or (B,rows,cols) with both dims a "
            f"multiple of {s}, got {w.shape}"
        )
    tr, tc = rows // s, cols // s
    bk = _fit_block(s, bk)
    oi, oj = _bordered_order(tr, tc)
    own = jnp.stack([
        jnp.asarray(owner_row, jnp.int32), jnp.asarray(owner_col, jnp.int32)
    ])
    steps = tr * tc + tr + tc - 1
    if batched:
        B = w.shape[0]
        bb = _resolve_gpu_batch_block(B, batch_block)
        grid, mat, vec, ownspec, row, col = _gpu_specs(
            True, bb, steps, rows, cols, s
        )
        grid = (B // bb, grid[1])
        band_lead = (B,)
        step_axis = 1
    else:
        grid, mat, vec, ownspec, row, col = _gpu_specs(
            False, 1, steps, rows, cols, s
        )
        band_lead = ()
        step_axis = 0
    kern = functools.partial(
        _round_kernel_gpu, tr=tr, tc=tc, s=s, bk=bk, semiring=semiring,
        variant=variant, step_axis=step_axis,
    )
    out, _, _ = _gpu_call(
        kern, grid,
        in_specs=[vec, vec, ownspec, mat],
        out_specs=(mat, row, col),
        out_shape=(
            jax.ShapeDtypeStruct(w.shape, w.dtype),
            jax.ShapeDtypeStruct(band_lead + (s, cols), w.dtype),
            jax.ShapeDtypeStruct(band_lead + (rows, s), w.dtype),
        ),
        interpret=interpret, num_warps=num_warps, num_stages=num_stages,
    )(oi, oj, own, w)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "batch_block", "num_warps", "num_stages",
                     "interpret"),
)
def fw_round_with_successors_gpu(
    w: jax.Array,
    succ: jax.Array,
    b: jax.Array | int,
    *,
    block_size: int = 128,
    batch_block: int | None = None,
    num_warps: int = NUM_WARPS,
    num_stages: int = NUM_STAGES,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """The fused successor-carrying round (min-plus) on the Triton backend.

    Same contract as ``fw_round_with_successors``; bit-matches it and one
    round of ``core.paths.fw_blocked_with_successors``.
    """
    if interpret is None:
        from repro.kernels.ops import default_gpu_interpret

        interpret = default_gpu_interpret()
    batched = w.ndim == 3
    n = w.shape[-1]
    s = block_size
    if w.ndim not in (2, 3) or w.shape[-2] != n or n % s:
        raise ValueError(
            f"w must be (n,n) or (B,n,n) with n % {s} == 0, got {w.shape}"
        )
    if succ.shape != w.shape:
        raise ValueError(f"succ shape {succ.shape} != w shape {w.shape}")
    T = n // s
    oi, oj = _round_order(b, T)
    steps = T * T + 2 * T - 1
    if batched:
        B = w.shape[0]
        bb = _resolve_gpu_batch_block(B, batch_block)
        grid, mat, vec, _, row, col = _gpu_specs(True, bb, steps, n, n, s)
        grid = (B // bb, grid[1])
        band_lead = (B,)
        step_axis = 1
    else:
        grid, mat, vec, _, row, col = _gpu_specs(False, 1, steps, n, n, s)
        band_lead = ()
        step_axis = 0
    kern = functools.partial(_round_succ_kernel_gpu, T=T, s=s,
                             step_axis=step_axis)
    ow, os_, *_ = _gpu_call(
        kern, grid,
        in_specs=[vec, vec, mat, mat],
        out_specs=(mat, mat, row, col, row, col),
        out_shape=(
            jax.ShapeDtypeStruct(w.shape, w.dtype),
            jax.ShapeDtypeStruct(succ.shape, succ.dtype),
            jax.ShapeDtypeStruct(band_lead + (s, n), w.dtype),
            jax.ShapeDtypeStruct(band_lead + (n, s), w.dtype),
            jax.ShapeDtypeStruct(band_lead + (s, n), succ.dtype),
            jax.ShapeDtypeStruct(band_lead + (n, s), succ.dtype),
        ),
        interpret=interpret, num_warps=num_warps, num_stages=num_stages,
    )(oi, oj, w, succ)
    return ow, os_
