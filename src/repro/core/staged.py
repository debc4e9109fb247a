"""The paper's algorithm: staged blocked Floyd-Warshall on Pallas kernels.

Per round b (pivot block [b·s, (b+1)·s)):
  1. phase-1 kernel closes the diagonal tile (VREG-resident k-loop);
  2. phase-2 kernels close the row/column bands (diag broadcast per program);
  3. the staged phase-3 kernel relaxes the whole matrix against the two
     bands, streaming bk-deep panel slices through VMEM while each output
     tile stays resident (the paper's register-residency + staged-load
     combination).

The whole-matrix phase 3 also re-relaxes the pivot bands; that is a
deliberate no-op (they are already closed under k ∈ block and ⊕ is
idempotent) which keeps the grid uniform — the TPU analogue of the paper
keeping all thread blocks identical.

One argument, ``lowering``, names the implementation that runs a round
(``LOWERINGS``):

  * ``"pallas"`` (the default) — the whole round is ONE ``pallas_call``
    (``kernels.fw_round``): every program classifies its tile from
    ``program_id`` vs. the pivot index and runs the matching stage, with the
    closed pivot bands staged through VMEM scratch instead of HBM
    round-trips.  1 dispatch/round, no ``dynamic_slice`` band copies.
  * ``"ref"`` — that round's bitwise XLA twin (``kernels.ref``), what the
    engine runs off the TPU, where the Pallas interpreter's grid emulation
    would dominate wall-clock.
  * ``"gpu"`` — the Triton round (``kernels.fw_round_gpu``).
  * ``"phases"`` — the seed's 4-dispatch round (phase 1, 2×phase 2,
    phase 3) with the bands spliced via ``dynamic_update_slice``; kept as
    the bitwise reference the others are tested against.

``apsp.engine`` maps the resolved backend to this value in one place.
Every lowering is **natively batched**: a (B, n, n) input runs every round
of all B graphs through the kernels' leading batch grid dimension — one
dispatch per round for the whole batch, NOT a ``vmap`` that replays the
round loop per graph.  Per-element chains are unchanged, so batched outputs
are bitwise equal to B separate solves.

The round loop is a ``jax.lax.fori_loop`` over rounds: the body is traced
once with a traced block offset, so the jaxpr holds a *constant* number of
pallas_calls regardless of n — compile time is O(1) in the round count.
``unroll_rounds=True`` restores the seed's trace-time python loop; it
changes the loop form only, never the lowering.  Every lowering in either
loop form is bit-identical (tests/test_apsp_solve.py,
tests/test_fw_round.py).

``fw_staged_with_successors`` drives the fused successor-tracking round
(``kernels.fw_round_with_successors``): the same schedule carrying a
next-hop matrix, bit-matching ``core.paths.fw_blocked_with_successors``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.semiring import MIN_PLUS, Semiring
from repro.kernels.fw_phase1 import fw_phase1
from repro.kernels.fw_phase2 import fw_phase2_col, fw_phase2_row
from repro.kernels.fw_round import fw_round, fw_round_with_successors
from repro.kernels.minplus_matmul import _fit_block, semiring_matmul
from repro.kernels.ref import _dyn_slice, _dyn_update

# The implementations a round can run under (module docstring).
LOWERINGS = ("pallas", "ref", "gpu", "phases")


def _check_lowering(lowering: str) -> None:
    if lowering not in LOWERINGS:
        raise ValueError(f"unknown lowering {lowering!r}; have {LOWERINGS}")


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_size", "bm", "bn", "bk", "batch_block", "variant", "semiring",
        "interpret", "unroll_rounds", "lowering",
    ),
)
def fw_staged(
    w: jax.Array,
    *,
    block_size: int = 128,
    bm: int = 256,
    bn: int = 256,
    bk: int = 32,
    batch_block: int | None = None,
    variant: str = "fori",
    semiring: Semiring = MIN_PLUS,
    interpret: bool | None = None,
    unroll_rounds: bool = False,
    lowering: str = "pallas",
) -> jax.Array:
    """Staged blocked FW (the paper's 'Staged Load' implementation).

    w: (n,n) or (B,n,n), n % block_size == 0 (``repro.apsp.solve`` pads
      arbitrary n).  Batched input closes all B graphs with one kernel
      dispatch per round (leading batch grid dimension).
    bm/bn/bk: phase-3 output-tile and staging-depth parameters (the fused
      round works on (s,s) tiles, so bm/bn only affect ``"phases"``).
    batch_block: graphs per grid step of the batched fused round (None →
      the fattest divisor of B that fits the VMEM budget).
    unroll_rounds: trace-time python round loop instead of fori_loop
      (O(n/s) trace size; only useful for trace inspection and tests).
    lowering: the implementation that runs a round, one of ``LOWERINGS``
      (module docstring); ``interpret=None`` under ``"gpu"``
      auto-interprets when no GPU is attached.  Outputs are bit-identical
      across all of them.
    """
    _check_lowering(lowering)
    if interpret is None and lowering != "gpu":
        from repro.kernels.ops import default_interpret

        interpret = default_interpret()
    n = w.shape[-1]
    s = block_size
    if w.ndim not in (2, 3) or w.shape[-2] != n:
        raise ValueError(f"w must be (n,n) or (B,n,n), got {w.shape}")
    if n % s:
        raise ValueError(f"n={n} not a multiple of block_size={s}")
    # Phase-3 staging depth cannot exceed the pivot width.
    bk_eff = min(bk, s)
    bm_eff, bn_eff = min(bm, n), min(bn, n)
    # Phase-2 band tile must divide the band length (e.g. n=640 → bt=320).
    bt_eff = _fit_block(n, 512)

    if lowering != "phases":
        if lowering == "ref":
            from repro.kernels.ref import fw_round_ref

            def round_body(b, w):
                return fw_round_ref(
                    w, b, block_size=s, bk=bk_eff, variant=variant,
                    semiring=semiring,
                )
        elif lowering == "gpu":
            from repro.kernels.fw_round_gpu import fw_round_gpu

            def round_body(b, w):
                return fw_round_gpu(
                    w, b, block_size=s, bk=bk_eff, batch_block=batch_block,
                    variant=variant, semiring=semiring, interpret=interpret,
                )
        else:
            def round_body(b, w):
                return fw_round(
                    w, b, block_size=s, bk=bk_eff, batch_block=batch_block,
                    variant=variant, semiring=semiring, interpret=interpret,
                )

        if unroll_rounds:
            for b in range(n // s):
                w = round_body(b, w)
            return w
        return jax.lax.fori_loop(0, n // s, round_body, w)

    def round_body(b, w):
        o = b * s
        diag = fw_phase1(
            _dyn_slice(w, o, o, s, s), semiring=semiring, interpret=interpret,
        )
        row_band = fw_phase2_row(
            diag, _dyn_slice(w, o, 0, s, n), bt=bt_eff,
            semiring=semiring, interpret=interpret,
        )
        # The diagonal tile inside the row band must be the closed one; the
        # row kernel recomputed that slice against itself which is a no-op
        # for idempotent ⊕, but we overwrite for exactness under any ⊕.
        row_band = _dyn_update(row_band, diag, 0, o)
        col_band = fw_phase2_col(
            diag, _dyn_slice(w, 0, o, n, s), bt=bt_eff,
            semiring=semiring, interpret=interpret,
        )
        col_band = _dyn_update(col_band, diag, o, 0)
        w = _dyn_update(w, row_band, o, 0)
        w = _dyn_update(w, col_band, 0, o)
        return semiring_matmul(
            col_band, row_band, w, semiring=semiring, bm=bm_eff, bn=bn_eff,
            bk=bk_eff, variant=variant, interpret=interpret,
        )

    if unroll_rounds:
        for b in range(n // s):
            w = round_body(b, w)
        return w
    return jax.lax.fori_loop(0, n // s, round_body, w)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "batch_block", "interpret",
                     "unroll_rounds", "lowering"),
)
def fw_staged_with_successors(
    w: jax.Array,
    *,
    block_size: int = 128,
    batch_block: int | None = None,
    interpret: bool | None = None,
    unroll_rounds: bool = False,
    lowering: str = "pallas",
) -> tuple[jax.Array, jax.Array]:
    """Staged FW with native next-hop tracking through the fused round.

    w: (n,n) or (B,n,n) min-plus distance matrix, n % block_size == 0.
    Returns (dist, succ): succ[..., i, j] = next vertex after i on the
    shortest i→j path, -1 where no path exists.  ``lowering`` takes the
    values of ``fw_staged``'s: one ``pallas_call`` per round for the whole
    batch under "pallas"; "ref" swaps in the bitwise XLA lowering, "gpu"
    the Triton round, and "phases" the phase-by-phase round of
    ``core.paths.blocked_round_with_successors``.  Outputs bit-match
    ``core.paths.fw_blocked_with_successors`` per graph.
    """
    from repro.core.paths import (
        _init_successors,
        blocked_round_with_successors,
    )

    _check_lowering(lowering)
    if interpret is None and lowering != "gpu":
        from repro.kernels.ops import default_interpret

        interpret = default_interpret()
    n = w.shape[-1]
    s = block_size
    if w.ndim not in (2, 3) or w.shape[-2] != n:
        raise ValueError(f"w must be (n,n) or (B,n,n), got {w.shape}")
    if n % s:
        raise ValueError(f"n={n} not a multiple of block_size={s}")
    succ = _init_successors(w)

    if lowering == "ref":
        from repro.kernels.ref import fw_round_with_successors_ref

        def round_body(b, carry):
            return fw_round_with_successors_ref(*carry, b, block_size=s)
    elif lowering == "phases":
        step = functools.partial(blocked_round_with_successors, block_size=s)
        if w.ndim == 3:
            step = jax.vmap(step, in_axes=(0, 0, None))

        def round_body(b, carry):
            return step(*carry, b)
    elif lowering == "gpu":
        from repro.kernels.fw_round_gpu import fw_round_with_successors_gpu

        def round_body(b, carry):
            return fw_round_with_successors_gpu(
                *carry, b, block_size=s, batch_block=batch_block,
                interpret=interpret,
            )
    else:
        def round_body(b, carry):
            return fw_round_with_successors(
                *carry, b, block_size=s, batch_block=batch_block,
                interpret=interpret,
            )

    if unroll_rounds:
        carry = (w, succ)
        for b in range(n // s):
            carry = round_body(b, carry)
        return carry
    return jax.lax.fori_loop(0, n // s, round_body, (w, succ))
