"""Phase-2 (singly dependent panel) Pallas kernels.

The row band W[b,*] (s × n) and column band W[*,b] (n × s) each depend on
the already-closed diagonal tile and on themselves (row/column k feeds
iterations k' > k), so k is sequential *within* a tile but tiles along the
band are independent → grid over the band, diagonal broadcast to every
program.

VMEM per program: diag (s·s) + panel tile (s·bt or bt·s).  With s=128,
bt=512, fp32: 64KB + 256KB — small enough that many band tiles pipeline.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

from repro.core.semiring import MIN_PLUS, Semiring
from repro.kernels.minplus_matmul import _fit_block
from repro.utils import compat


def _row_kernel(d_ref, p_ref, o_ref, *, semiring: Semiring):
    from repro.kernels.fw_round import _close_row_panel  # import cycle

    o_ref[...] = _close_row_panel(
        p_ref[...], d_ref[...], d_ref.shape[-1], semiring, mosaic=True
    )


def _col_kernel(d_ref, p_ref, o_ref, *, semiring: Semiring):
    from repro.kernels.fw_round import _close_col_panel  # import cycle

    o_ref[...] = _close_col_panel(
        p_ref[...], d_ref[...], d_ref.shape[-1], semiring, mosaic=True
    )


@functools.partial(jax.jit, static_argnames=("bt", "semiring", "interpret"))
def fw_phase2_row(
    diag: jax.Array,
    band: jax.Array,
    *,
    bt: int = 512,
    semiring: Semiring = MIN_PLUS,
    interpret: bool = False,
) -> jax.Array:
    """Update the row band (s, n): band ⊕= diag ⊗ band, k sequential.

    Batched: diag (B,s,s) with band (B,s,n) closes all B bands in one
    dispatch — the batch is a leading (parallel) grid dimension.
    """
    s, n = band.shape[-2:]
    # Largest divisor of n that is <= bt, so any band length works with the
    # default bt (e.g. n=640 → bt=320); the per-element k-chain is bt-
    # independent, so results are bitwise identical across choices.
    bt = _fit_block(n, bt)
    kern = functools.partial(_row_kernel, semiring=semiring)
    if band.ndim == 2:
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((s, n), band.dtype),
            grid=(n // bt,),
            in_specs=[
                pl.BlockSpec((s, s), lambda j: (0, 0)),
                pl.BlockSpec((s, bt), lambda j: (0, j)),
            ],
            out_specs=pl.BlockSpec((s, bt), lambda j: (0, j)),
            interpret=interpret,
            compiler_params=compat.tpu_compiler_params(
                dimension_semantics=("parallel",)
            ),
        )(diag, band)
    B = band.shape[0]
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((B, s, n), band.dtype),
        grid=(B, n // bt),
        in_specs=[
            pl.BlockSpec((1, s, s), lambda g, j: (g, 0, 0)),
            pl.BlockSpec((1, s, bt), lambda g, j: (g, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, s, bt), lambda g, j: (g, 0, j)),
        interpret=interpret,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel")
        ),
    )(diag, band)


@functools.partial(jax.jit, static_argnames=("bt", "semiring", "interpret"))
def fw_phase2_col(
    diag: jax.Array,
    band: jax.Array,
    *,
    bt: int = 512,
    semiring: Semiring = MIN_PLUS,
    interpret: bool = False,
) -> jax.Array:
    """Update the column band (n, s): band ⊕= band ⊗ diag, k sequential.

    Batched: diag (B,s,s) with band (B,n,s), one dispatch for all B bands.
    """
    n, s = band.shape[-2:]
    bt = _fit_block(n, bt)
    kern = functools.partial(_col_kernel, semiring=semiring)
    if band.ndim == 2:
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((n, s), band.dtype),
            grid=(n // bt,),
            in_specs=[
                pl.BlockSpec((s, s), lambda i: (0, 0)),
                pl.BlockSpec((bt, s), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((bt, s), lambda i: (i, 0)),
            interpret=interpret,
            compiler_params=compat.tpu_compiler_params(
                dimension_semantics=("parallel",)
            ),
        )(diag, band)
    B = band.shape[0]
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((B, n, s), band.dtype),
        grid=(B, n // bt),
        in_specs=[
            pl.BlockSpec((1, s, s), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((1, bt, s), lambda g, i: (g, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, s), lambda g, i: (g, i, 0)),
        interpret=interpret,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel")
        ),
    )(diag, band)
