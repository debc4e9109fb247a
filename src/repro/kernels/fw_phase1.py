"""Phase-1 (independent / diagonal block) Pallas kernel.

One (s,s) tile, s sequential FW iterations.  The tile is loaded into VMEM
once, the k-loop carries the whole tile as a value (VREG-resident working
set, the paper's "registers" idea applied to the diagonal phase), and the
result is stored once.  There is no grid: phase 1 is O(s³) work on O(s²)
data and is never the bottleneck (the paper runs it as a single thread
block).
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

from repro.core.semiring import MIN_PLUS, Semiring
from repro.utils import compat


def _phase1_kernel(w_ref, o_ref, *, semiring: Semiring):
    from repro.kernels.fw_round import _close_diag  # core imports this module

    # Ellipsis-relative chain: the same with or without a leading batch dim
    # ((B,s,s) tiles from the batched grid).
    o_ref[...] = _close_diag(
        w_ref[...], w_ref.shape[-1], semiring, mosaic=True
    )


@functools.partial(jax.jit, static_argnames=("semiring", "interpret"))
def fw_phase1(
    tile: jax.Array, *, semiring: Semiring = MIN_PLUS, interpret: bool = False
) -> jax.Array:
    """In-place FW closure of one (s,s) diagonal tile, or (B,s,s) of them.

    A batched input closes all B diagonal tiles in ONE dispatch with a
    leading (parallel) batch grid dimension — one program per graph.
    """
    s = tile.shape[-1]
    if tile.ndim not in (2, 3) or tile.shape[-2] != s:
        raise ValueError(f"diagonal tile must be (s,s) or (B,s,s), got {tile.shape}")
    kern = functools.partial(_phase1_kernel, semiring=semiring)
    if tile.ndim == 2:
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((s, s), tile.dtype),
            interpret=interpret,
            compiler_params=compat.tpu_compiler_params(dimension_semantics=()),
        )(tile)
    B = tile.shape[0]
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((B, s, s), tile.dtype),
        grid=(B,),
        in_specs=[pl.BlockSpec((1, s, s), lambda g: (g, 0, 0))],
        out_specs=pl.BlockSpec((1, s, s), lambda g: (g, 0, 0)),
        interpret=interpret,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel",)
        ),
    )(tile)
