"""Staged semiring matmul — the paper's doubly-dependent (phase 3) kernel.

This is the TPU re-derivation of the paper's core idea (§4 of the paper):

  * CUDA: the doubly-dependent 32×32 tile lives in *registers* (one slice per
    thread); only a 32×m slice (m=8) of each singly-dependent panel sits in
    shared memory per stage; stages are separated by __syncthreads so the
    scheduler can overlap other blocks' loads with compute.

  * TPU/Pallas: the output tile C (bm×bn) stays resident in VMEM across the
    innermost ``k`` grid dimension (``dimension_semantics = (parallel,
    parallel, arbitrary)`` revisits the same output block), while BlockSpecs
    stream only (bm×bk) / (bk×bn) panel slices per grid step.  Pallas
    double-buffers the next slice's HBM→VMEM DMA against the current
    stage's compute — the same latency-hiding the paper bought by shrinking
    shared-memory residency.  The inner k-loop carries rank-1 tropical
    updates in VREGs (the register-residency analogue).

VMEM budget per grid step (fp32, fused variant):
    C (bm·bn) + A-slice (bm·kb) + B-slice (kb·bn) + C_in (bm·bn), ×2 for
    double buffering of the streamed operands.
    bm=bn=256, kb=128: 2·2·256·256·4 + 2·2·(256·128)·4 ≈ 1.5MB, far below
    the scoped-VMEM limit (``compat.vmem_limit_bytes``); the practical
    pipeline depth is set by Pallas (2-stage).

The (min,+) semiring cannot use the MXU (which only fuses (×,+)), so the
compute unit is the VPU; tiles are shaped to the (8,128) vreg lattice.

**Gather-free picks.**  Every rank-1 step needs column k of one operand
and row k of the other.  The XLA twins index them (``x[..., :, k, None]``),
which lowers to a gather; Mosaic has no gather and no ``dynamic_slice`` on
values.  ``_col``/``_row`` with ``mosaic=True`` pick without either: a
static ``k`` is a static slice, a traced ``k`` rotates the array so that
row/column k lands at index 0 (``_rotate``: ``pltpu.roll``, a pure data
movement) and slices it statically.  Both are exact for every dtype, so the per-element
⊕/⊗ chain — and hence every bitwise kernel == twin guarantee — is the
same whichever pick runs.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.semiring import MIN_PLUS, Semiring
from repro.utils import compat

Variant = Literal["fori", "unroll", "broadcast"]

# Mosaic rotates 32-bit data only; 16-bit operands widen for the pick, which
# is exact (bf16 ⊂ f32, int16 ⊂ int32).
_WIDE = {jnp.dtype(jnp.bfloat16): jnp.float32, jnp.dtype(jnp.int16): jnp.int32}

# Rank-1 steps per rotate in the kernels' "fori" loops (``_stage_chunks``,
# ``fw_round._relax_succ_grouped``, and the plain round's lane cache, whose
# 8-row loads of the b-operand are one sublane tile of 32-bit words).  The
# grouping took the per-step rotates out; on a v5e the lane broadcast of
# column k that each step still makes is what paces such a loop (the plain
# round caches it: ``fw_round._fill_lane_cache``).
_PICK_GROUP = 8


def _rotate(x: jax.Array, k, axis: int) -> jax.Array:
    """``x`` rotated along ``axis`` so that index ``k`` lands at 0
    (``pltpu.roll``; 16-bit data is widened first)."""
    from jax.experimental.pallas import tpu as pltpu

    if x.dtype in _WIDE:
        x = x.astype(_WIDE[x.dtype])
    m = x.shape[axis]
    return pltpu.roll(x, (m - k) % m, axis % x.ndim)


def _col(x: jax.Array, k, mosaic: bool = False) -> jax.Array:
    """Column k of (…, m, n) ``x`` as an (…, m, 1) operand."""
    if isinstance(k, int):
        return x[..., :, k:k + 1]
    if not mosaic:
        return x[..., :, k, None]
    return _rotate(x, k, -1)[..., :, 0:1].astype(x.dtype)


def _row(x: jax.Array, k, mosaic: bool = False) -> jax.Array:
    """Row k of (…, m, n) ``x`` as a (…, 1, n) operand."""
    if isinstance(k, int):
        return x[..., k:k + 1, :]
    if not mosaic:
        return x[..., k, None, :]
    return _rotate(x, k, -2)[..., 0:1, :].astype(x.dtype)


def _stage_compute(
    acc: jax.Array,
    a_blk: jax.Array,
    b_blk: jax.Array,
    semiring: Semiring,
    variant: Variant,
) -> jax.Array:
    """⊕-accumulate one (bm×bk)·(bk×bn) panel-slice stage into acc.

    All indexing is ellipsis-relative so the same per-element ⊕/⊗ chain runs
    with or without a leading batch-block dim ((bb,bm,bk)·(bb,bk,bn) → the
    batched grid) — the 2-D lowering is unchanged op for op.
    """
    bk = a_blk.shape[-1]
    if variant == "broadcast":
        # Materializes (bm, bk, bn) in VMEM — fewer, fatter VPU ops.
        prod = semiring.add_reduce(
            semiring.mul(a_blk[..., :, :, None], b_blk[..., None, :, :]),
            axis=-2,
        )
        return semiring.add(acc, prod)

    def body(kk, acc):
        # Rank-1 tropical update; a column/row pair broadcast across VREGs.
        return semiring.add(
            acc, semiring.mul(_col(a_blk, kk), _row(b_blk, kk))
        )

    if variant == "unroll":
        # The paper's loop-unrolling optimization (§4, "standard
        # optimizations ... unrolling loops"): python loop → straight-line HLO.
        for kk in range(bk):
            acc = body(kk, acc)
        return acc
    return jax.lax.fori_loop(0, bk, body, acc)


def _stage_chunks(acc, a, b, bk, semiring, variant, mosaic=False):
    """Relax ``acc`` against (…, m, K)·(…, K, n) operands in ascending
    bk-deep stages — the k-sequence of ``semiring_matmul``'s grid.

    ``mosaic`` (kernel bodies) runs the "fori" variant as one loop over
    all K steps of the full operands, ``_PICK_GROUP`` at a time: a bk-wide
    chunk is narrower than a lane tile and Mosaic cannot rotate it, so the
    loop rotates the full operands once per group and picks the group's
    columns/rows with static slices.  The chain is the same ascending-k
    sequence either way.
    """
    if mosaic and variant == "fori":
        K = a.shape[-1]
        G = _PICK_GROUP if K % _PICK_GROUP == 0 else 1

        def body(c, acc):
            ag = _rotate(a, c * G, -1)
            bg = _rotate(b, c * G, -2)
            for kk in range(G):
                acc = semiring.add(acc, semiring.mul(
                    _col(ag, kk).astype(a.dtype),
                    _row(bg, kk).astype(b.dtype),
                ))
            return acc

        return jax.lax.fori_loop(0, K // G, body, acc)
    for k0 in range(0, a.shape[-1], bk):
        acc = _stage_compute(
            acc, a[..., :, k0:k0 + bk], b[..., k0:k0 + bk, :], semiring,
            variant,
        )
    return acc


def _matmul_kernel(
    a_ref, b_ref, o_ref, *, bk: int, semiring: Semiring, variant: Variant,
    k_axis: int = 2,
):
    """C = A ⊗⊕ B (no input accumulator)."""
    k = pl.program_id(k_axis)

    @pl.when(k == 0)
    def _():
        o_ref[...] = jnp.full(o_ref.shape, semiring.zero, o_ref.dtype)

    o_ref[...] = _stage_chunks(
        o_ref[...], a_ref[...], b_ref[...], bk, semiring, variant, mosaic=True
    )


def _fused_kernel(
    c_ref, a_ref, b_ref, o_ref, *, bk: int, semiring: Semiring,
    variant: Variant, k_axis: int = 2,
):
    """C_out = C_in ⊕ (A ⊗⊕ B) — the FW phase-3 relaxation, C resident."""
    k = pl.program_id(k_axis)

    @pl.when(k == 0)
    def _():
        o_ref[...] = c_ref[...]

    o_ref[...] = _stage_chunks(
        o_ref[...], a_ref[...], b_ref[...], bk, semiring, variant, mosaic=True
    )


def _fit_block(dim: int, want: int) -> int:
    """Largest divisor of dim that is ≤ want (keeps grids exact for any n)."""
    want = min(want, dim)
    for b in range(want, 0, -1):
        if dim % b == 0:
            return b
    return dim


def _grid_call(kernel, out_shape, grid, in_specs, out_specs, interpret, *args):
    # Last grid dim is the sequential contraction; any leading dims (output
    # tiles, and the batch dim of a batched call) are parallel.
    compiler_params = compat.tpu_compiler_params(
        dimension_semantics=("parallel",) * (len(grid) - 1) + ("arbitrary",)
    )
    return pl.pallas_call(
        kernel,
        name="semiring_matmul",
        out_shape=out_shape,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        interpret=interpret,
        compiler_params=compiler_params,
    )(*args)


@functools.partial(
    jax.jit,
    static_argnames=("semiring", "bm", "bn", "bk", "variant", "interpret"),
)
def semiring_matmul(
    a: jax.Array,
    b: jax.Array,
    c: jax.Array | None = None,
    *,
    semiring: Semiring = MIN_PLUS,
    bm: int = 256,
    bn: int = 256,
    bk: int = 32,
    variant: Variant = "fori",
    interpret: bool = False,
) -> jax.Array:
    """Blocked, staged C [⊕=] A ⊗⊕ B, optionally over a leading batch dim.

    a (m,k) or (B,m,k), b (k,n) or (B,k,n), optional c of the matching
    shape.  m % bm == n % bn == k % bk == 0.  ``bk`` is the staging depth —
    the TPU analogue of the paper's m=8 shared-memory slice.  Each k grid
    step streams a ``kb``-deep slice (a lane-tile multiple, so the (bm, kb)
    A block is one Mosaic accepts) and runs its kb/bk stages in ascending
    order — the same per-element chain as one bk stage per step.
    ``variant`` selects the inner-loop lowering ("fori" | "unroll" |
    "broadcast"), mirroring the paper's instruction-level optimization
    axis.  Batched inputs run the B semiring products through ONE dispatch
    with a leading (parallel) batch grid dimension; per-element results are
    identical to B separate calls.
    """
    if a.ndim == 3:
        if b.ndim != 3 or a.shape[0] != b.shape[0]:
            raise ValueError(f"batched operands disagree: {a.shape} @ {b.shape}")
        B, m, k = a.shape
        k2, n = b.shape[1:]
    else:
        B = None
        m, k = a.shape
        k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {a.shape} @ {b.shape}")
    compat.check_tpu_lowering(a.dtype, interpret)
    bm, bn, bk = _fit_block(m, bm), _fit_block(n, bn), _fit_block(k, bk)
    if m % bm or n % bn or k % bk:
        raise ValueError(f"shape ({m},{k})x({k2},{n}) not divisible by ({bm},{bn},{bk})")
    from repro.apsp import plan  # call-time import: apsp imports this module

    kb = plan.grid_k_step(k, bk)
    if B is None:
        grid = (m // bm, n // bn, k // kb)
        a_spec = pl.BlockSpec((bm, kb), lambda i, j, kk: (i, kk))
        b_spec = pl.BlockSpec((kb, bn), lambda i, j, kk: (kk, j))
        c_spec = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
        out_shape = jax.ShapeDtypeStruct((m, n), a.dtype)
        k_axis = 2
    else:
        grid = (B, m // bm, n // bn, k // kb)
        a_spec = pl.BlockSpec((1, bm, kb), lambda g, i, j, kk: (g, i, kk))
        b_spec = pl.BlockSpec((1, kb, bn), lambda g, i, j, kk: (g, kk, j))
        c_spec = pl.BlockSpec((1, bm, bn), lambda g, i, j, kk: (g, i, j))
        out_shape = jax.ShapeDtypeStruct((B, m, n), a.dtype)
        k_axis = 3

    if c is None:
        kern = functools.partial(
            _matmul_kernel, bk=bk, semiring=semiring, variant=variant,
            k_axis=k_axis,
        )
        return _grid_call(kern, out_shape, grid, [a_spec, b_spec], c_spec, interpret, a, b)
    kern = functools.partial(
        _fused_kernel, bk=bk, semiring=semiring, variant=variant, k_axis=k_axis
    )
    return _grid_call(
        kern, out_shape, grid, [c_spec, a_spec, b_spec], c_spec, interpret, c, a, b
    )
