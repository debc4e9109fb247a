"""Fused rank-1 repair kernel: a batch of edge updates in ONE dispatch.

A closed distance matrix absorbs an ⊕-improving edge update (u, v, w)
through the rank-1 repair recurrence

    d' = d ⊕ (d[:, u] ⊗ w) ⊗ d[v, :]

— O(n²) work against the O(n³) full re-solve (RAPID-Graph's dynamic-
programming-reuse framing of FW; the recurrence is one outer-product
semiring matmul, the primitive ``kernels/`` already ships).  ``w`` is the
⊕-*delta* merged into edge (u, v): the improved weight itself for the
idempotent semirings (min_plus / max_plus / max_min / or_and), the additive
weight delta for plus_mul.  The repaired matrix equals the full closure of
the updated W exactly when

  * every update is an ⊕-improvement (the new closure can only gain paths
    through the updated edge — edge *removals* / min-plus weight increases
    are structural and need a re-solve; ``serve/registry.py`` classifies),
  * the closure's diagonal is the ⊗-identity (no ⊕-improving cycles), and
  * no optimal path needs the updated edge twice (automatic for the
    idempotent semirings without improving cycles; a DAG for plus_mul).

A *batch* of E updates applies sequentially — edge e must see the matrix
already repaired by edges 0..e-1 — yet the kernel runs the whole batch as
ONE ``pallas_call`` over a 1-D grid of E + T steps (T = n/s row bands):

  * **steps g < E (stage)** — step e loads the row band holding pivot row
    v_e (scalar-prefetch block order, like ``fw_round``'s pivot-first
    schedule), extracts the row, replays the corrections from edges
    e' < e out of the scratch rows (a masked fixed-trip ``fori_loop`` —
    the same incremental chain a full sequential application would give
    that row), and stores the *evolved* pivot row into VMEM scratch
    ``(E, n)``.  The step's output write is a byte-identical copy of the
    band it read, so Pallas' input prefetch (which may run ahead of a
    previous step's output DMA) can never observe a stale tile — the
    same sequencing rule as ``fw_round``: cross-step dataflow stays in
    scratch.
  * **steps g ≥ E (apply)** — step E+t loads band t and folds in all E
    updates in order: ``c = c ⊕ (c[:, u_e] ⊗ w_e) ⊗ scratch[e]``.  Because
    scratch row e is exactly the state of row v_e after updates < e, this
    per-band evolution is elementwise identical to applying the E updates
    one by one to the whole matrix — ``fw_repair_ref`` in ``ref.py`` is
    that direct loop, and the two are bitwise equal for every semiring
    lowering (tests/test_fw_repair.py).

Edge operands ride the scalar-prefetch channel as three int32 vectors
(u, v, and the weight *bit pattern* — f32/bf16 weights are bitcast, int16
widened — so the kernel decodes the exact value the host encoded).  No-op
padding edges (u = v = 0, w = the ⊕-identity: ⊗ with the annihilator kills
the candidate) let callers pad E to a fixed plan-key bucket.

``fw_repair_with_successors`` threads the next-hop table through the same
two phases with a second scratch block: a strict-improvement relaxation
(``cand < d``, matching ``core.paths``/``fw_round_with_successors``) where
an improved (i, j) takes first hop ``v_e`` when i == u_e and ``succ[i, u_e]``
otherwise.  min-plus only, like every successor path in the repo.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.semiring import MIN_PLUS, Semiring
from repro.kernels.minplus_matmul import _col
from repro.utils import compat


def encode_weights(w, dtype) -> jax.Array:
    """(E,) weights in the matrix dtype → (E,) int32 bit patterns.

    The scalar-prefetch channel is int32; the kernel inverts this encoding
    bit-exactly (``_decode_weight``), so kernel and ref twin see identical
    weight values for any supported dtype.
    """
    dt = jnp.dtype(dtype)
    w = jnp.asarray(w, dt)
    if dt == jnp.dtype(jnp.float32):
        return jax.lax.bitcast_convert_type(w, jnp.int32)
    if dt == jnp.dtype(jnp.bfloat16):
        return jax.lax.bitcast_convert_type(w, jnp.int16).astype(jnp.int32)
    if dt == jnp.dtype(jnp.int16):
        return w.astype(jnp.int32)
    if dt == jnp.dtype(jnp.int32):
        return w
    raise NotImplementedError(f"fw_repair: unsupported dtype {dt}")


def _decode_weight(wb: jax.Array, dtype) -> jax.Array:
    """int32 bit pattern → (1, 1) weight in the matrix dtype (bit-exact).

    The pattern is broadcast to a vector first: Mosaic bitcasts vectors,
    not scalars."""
    dt = jnp.dtype(dtype)
    wb = jnp.full((1, 1), wb, jnp.int32)
    if dt == jnp.dtype(jnp.float32):
        return jax.lax.bitcast_convert_type(wb, jnp.float32)
    if dt == jnp.dtype(jnp.bfloat16):
        return jax.lax.bitcast_convert_type(wb.astype(jnp.int16), jnp.bfloat16)
    if dt == jnp.dtype(jnp.int16):
        return wb.astype(jnp.int16)
    return wb


def _lane_pick(ref, k, n: int) -> jax.Array:
    """Column k of a band ref as an (s, 1) operand without a gather: load
    the lane tile that holds column k, then rotate k to lane 0 (exact)."""
    w = 128 if n % 128 == 0 else n
    base = pl.multiple_of((k // w) * w, w)
    return _col(ref[:, pl.ds(base, w)], k % w, mosaic=True)


def _repair_kernel(order_ref, u_ref, v_ref, wb_ref, d_ref, o_ref, scr_ref,
                   *, n, s, E, semiring):
    g = pl.program_id(0)
    dtype = o_ref.dtype
    row_g = pl.ds(g, 1)
    # Stage steps copy their band out byte-identically (see module doc);
    # apply steps evolve it in place.
    o_ref[...] = d_ref[...]

    @pl.when(g < E)
    def _stage():
        # Scratch row g holds pivot row v_g as it evolves: r ⊕= (r[u_e2] ⊗
        # w_e2) ⊗ scratch[e2], masked to e2 < g.  The masked trips read
        # scratch rows that are not yet (or never) staged — garbage values
        # whose results ``jnp.where`` discards.
        scr_ref[row_g, :] = d_ref[pl.ds(v_ref[g] - order_ref[g] * s, 1), :]

        def correction(e2, carry):
            w2 = _decode_weight(wb_ref[e2], dtype)
            r = scr_ref[row_g, :]
            ru = _col(r, u_ref[e2], mosaic=True)
            cand = semiring.mul(
                semiring.mul(ru, w2), scr_ref[pl.ds(e2, 1), :]
            )
            scr_ref[row_g, :] = jnp.where(e2 < g, semiring.add(r, cand), r)
            return carry

        jax.lax.fori_loop(0, E, correction, 0)

    @pl.when(g >= E)
    def _apply():
        # Band t = g - E folds in all E updates in order.
        def body(e2, carry):
            w2 = _decode_weight(wb_ref[e2], dtype)
            du = _lane_pick(o_ref, u_ref[e2], n)
            cand = semiring.mul(
                semiring.mul(du, w2), scr_ref[pl.ds(e2, 1), :]
            )
            o_ref[...] = semiring.add(o_ref[...], cand)
            return carry

        jax.lax.fori_loop(0, E, body, 0)


def _repair_succ_kernel(order_ref, u_ref, v_ref, wb_ref, d_ref, s_ref,
                        od_ref, os_ref, scrd_ref, scrs_ref, *, n, s, E):
    g = pl.program_id(0)
    dtype = od_ref.dtype
    row_g = pl.ds(g, 1)
    od_ref[...] = d_ref[...]
    os_ref[...] = s_ref[...]

    @pl.when(g < E)
    def _stage():
        v_g = v_ref[g]
        row = pl.ds(v_g - order_ref[g] * s, 1)
        scrd_ref[row_g, :] = d_ref[row, :]
        scrs_ref[row_g, :] = s_ref[row, :]

        def correction(e2, carry):
            w2 = _decode_weight(wb_ref[e2], dtype)
            u2, v2 = u_ref[e2], v_ref[e2]
            r = scrd_ref[row_g, :]
            rs = scrs_ref[row_g, :]
            ru = _col(r, u2, mosaic=True)
            cand = (ru + w2) + scrd_ref[pl.ds(e2, 1), :]
            better = jnp.logical_and(cand < r, e2 < g)
            hop = jnp.where(v_g == u2, v2, _col(rs, u2, mosaic=True))
            scrd_ref[row_g, :] = jnp.where(better, cand, r)
            scrs_ref[row_g, :] = jnp.where(better, hop, rs)
            return carry

        jax.lax.fori_loop(0, E, correction, 0)

    @pl.when(g >= E)
    def _apply():
        ridx = order_ref[g] * s + jax.lax.broadcasted_iota(
            jnp.int32, (s, 1), 0
        )

        def body(e2, carry):
            w2 = _decode_weight(wb_ref[e2], dtype)
            u2, v2 = u_ref[e2], v_ref[e2]
            c = od_ref[...]
            cs = os_ref[...]
            du = _lane_pick(od_ref, u2, n)
            cand = (du + w2) + scrd_ref[pl.ds(e2, 1), :]
            better = cand < c
            hop = jnp.where(
                ridx == u2, v2, _lane_pick(os_ref, u2, n)
            )
            od_ref[...] = jnp.where(better, cand, c)
            os_ref[...] = jnp.where(better, hop, cs)
            return carry

        jax.lax.fori_loop(0, E, body, 0)


def _repair_order(v: jax.Array, T: int, s: int) -> jax.Array:
    """Block-row visit order: E stage steps at band v_e // s, then all T."""
    return jnp.concatenate(
        [jnp.asarray(v, jnp.int32) // s, jnp.arange(T, dtype=jnp.int32)]
    )


def _check_args(d, u, v, w, s):
    n = d.shape[-1]
    if d.ndim != 2 or d.shape[0] != n or n % s:
        raise ValueError(
            f"d must be (n, n) with n % {s} == 0, got {d.shape}"
        )
    E = len(u)
    if not (len(v) == len(w) == E) or E < 1:
        raise ValueError(
            f"u/v/w must be equal-length non-empty edge vectors, got "
            f"{len(u)}/{len(v)}/{len(w)}"
        )
    return n, E


@functools.partial(
    jax.jit, static_argnames=("block_size", "semiring", "interpret")
)
def fw_repair(
    d: jax.Array,
    u: jax.Array,
    v: jax.Array,
    w: jax.Array,
    *,
    block_size: int = 128,
    semiring: Semiring = MIN_PLUS,
    interpret: bool | None = None,
) -> jax.Array:
    """Repair closed (n, n) ``d`` for E ⊕-improving edge updates, fused.

    d: a *closed* matrix (a solve output) with n % block_size == 0;
    u/v: (E,) int32 edge endpoints; w: (E,) ⊕-delta weights in d.dtype.
    One dispatch for the whole batch; see the module docstring for the
    exactness conditions and the two-phase grid.
    """
    if interpret is None:
        from repro.kernels.ops import default_interpret

        interpret = default_interpret()
    s = block_size
    n, E = _check_args(d, u, v, w, s)
    pltpu = compat.pallas_tpu("fw_repair needs pallas TPU scratch + scalar prefetch")
    compat.check_tpu_lowering(d.dtype, interpret, s)
    T = n // s
    u = jnp.asarray(u, jnp.int32)
    v = jnp.asarray(v, jnp.int32)
    wb = encode_weights(w, d.dtype)
    order = _repair_order(v, T, s)
    spec = pl.BlockSpec((s, n), lambda g, order, u, v, wb: (order[g], 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(E + T,),
        in_specs=[spec],
        out_specs=spec,
        scratch_shapes=[pltpu.VMEM((E, n), d.dtype)],  # evolved pivot rows
    )
    kern = functools.partial(_repair_kernel, n=n, s=s, E=E, semiring=semiring)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(d.shape, d.dtype),
        interpret=interpret,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",)
        ),
    )(order, u, v, wb, d)


@functools.partial(
    jax.jit, static_argnames=("block_size", "interpret")
)
def fw_repair_with_successors(
    d: jax.Array,
    succ: jax.Array,
    u: jax.Array,
    v: jax.Array,
    w: jax.Array,
    *,
    block_size: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """min-plus repair carrying the next-hop table: (dist', succ').

    The strict-improvement relaxation (``cand < d``) mirrors
    ``fw_round_with_successors``; an improved pair (i, j) takes hop v_e
    when i == u_e, else the cached ``succ[i, u_e]``.
    """
    if interpret is None:
        from repro.kernels.ops import default_interpret

        interpret = default_interpret()
    s = block_size
    n, E = _check_args(d, u, v, w, s)
    if succ.shape != d.shape:
        raise ValueError(f"succ must match d, got {succ.shape} vs {d.shape}")
    pltpu = compat.pallas_tpu("fw_repair_with_successors needs pallas TPU scratch")
    compat.check_tpu_lowering(d.dtype, interpret, s)
    T = n // s
    u = jnp.asarray(u, jnp.int32)
    v = jnp.asarray(v, jnp.int32)
    wb = encode_weights(w, d.dtype)
    order = _repair_order(v, T, s)
    idx = lambda g, order, u, v, wb: (order[g], 0)
    dspec = pl.BlockSpec((s, n), idx)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(E + T,),
        in_specs=[dspec, dspec],
        out_specs=[dspec, dspec],
        scratch_shapes=[
            pltpu.VMEM((E, n), d.dtype),      # evolved pivot rows
            pltpu.VMEM((E, n), jnp.int32),    # their next-hop rows
        ],
    )
    kern = functools.partial(_repair_succ_kernel, n=n, s=s, E=E)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(d.shape, d.dtype),
            jax.ShapeDtypeStruct(succ.shape, jnp.int32),
        ],
        interpret=interpret,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",)
        ),
    )(order, u, v, wb, d, jnp.asarray(succ, jnp.int32))
