"""Multi-pod distributed blocked Floyd-Warshall (shard_map).

Scales the paper's single-GPU 3-phase algorithm to a 2-D/3-D device mesh —
the SUMMA-style distribution (cf. communication-avoiding FW, Solomonik et
al.):

  * W (n,n) is block-distributed: rows over the mesh row axes (``pod`` ×
    ``data``), columns over the mesh column axis (``model``); each device
    holds an (n/R, n/C) block.  Batched (B, n, n) inputs shard the trailing
    two dims the same way (every device holds B local blocks).
  * Per round b (pivot block of width s):
      1. the raw diagonal tile is broadcast with a masked ``pmin`` (owner
         contributes its tile, everyone else +inf — the ⊕-identity makes
         the reduction a broadcast in log(P) hops);
      2. the raw pivot row/column panel slices are pmin-broadcast along the
         row/column mesh axes;
      3. every device closes the broadcast pivot tile and panel slices and
         relaxes its local block against them.
  * Comm per device per round: s² + s·n/C + s·n/R words; over n/s rounds
    → n²(1/R + 1/C) — the SUMMA bound (``plan.summa_comm_bound_bytes``;
    the implemented volume is ``plan.dist_round_comm_bytes``, and
    ``launch.fw_dist_check --bench`` checks both against the collectives in
    the compiled HLO).

Step 3 has three lowerings, picked by ``backend``:

  * ``"fused"`` (default) — the raw pivot tile and panel slices are stacked
    as a *border* onto the local block and the whole round (phases 1-3)
    runs as ONE ``pallas_call`` per device: ``kernels.fw_round_bordered``,
    the paper's single-dispatch multi-stage round on the rectangular
    bordered tile grid (on CPU its bitwise XLA lowering
    ``kernels.ref.fw_round_bordered_ref`` executes instead).  Owner-echo
    coordinates splice the closed border over the device's own copies of
    the global pivot bands, which makes the distributed solve *bitwise*
    equal to the single-device fused solve for every semiring
    (tests/test_distributed.py).
  * ``"jnp"`` — the original per-phase jnp lowering (close diag, close
    panels, chunked phase-3 relaxation) — the counting backend
    ``launch.fw_dryrun`` lowers for cost analysis.
  * ``"pallas"`` — per-phase lowering with the phase-3 relaxation on the
    staged ``semiring_matmul`` kernel.

Relaxing the pivot bands again during phase 3 is a no-op for idempotent ⊕
(they are already closed under k ∈ block), which keeps every device's
program identical — no diverging control flow, pure SPMD.

Fault tolerance: the algorithm is a monotone fixed-point iteration, so any
round boundary is a consistent checkpoint, and *re-running* a round on
restart is harmless (relaxations are idempotent).  ``fw_distributed``
executes in jitted chunks of ``rounds_per_call`` rounds and invokes a host
callback between chunks for checkpointing (see ``train/checkpoint.py`` for
the manager used by the launcher).
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.semiring import MIN_PLUS, Semiring


def _axis_size(mesh: Mesh, axes: Sequence[str] | str) -> int:
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def _my_index(axes: Sequence[str] | str) -> jax.Array:
    """Flattened device index along a (possibly compound) mesh axis."""
    if isinstance(axes, str):
        axes = (axes,)
    idx = jnp.int32(0)
    for a in axes:
        size = (
            jax.lax.axis_size(a)
            if hasattr(jax.lax, "axis_size")
            else jax.lax.psum(1, a)  # older jax: count participants
        )
        idx = idx * size + jax.lax.axis_index(a)
    return idx


# The version shim lives in utils.compat now (the MoE a2a layer shares it);
# the old private name stays importable for existing callers.
from repro.utils.compat import shard_map as _shard_map  # noqa: E402
from repro.kernels.ref import _dyn_slice, _dyn_update  # noqa: E402


_UNROLL_INNER = False  # counting mode: python-loop the k iterations so
# cost_analysis sees true trip-multiplied FLOPs (launch/fw_dryrun.py)


def _loop(n, body, init):
    if _UNROLL_INNER:
        x = init
        for k in range(n):
            x = body(k, x)
        return x
    return jax.lax.fori_loop(0, n, body, init)


def _phase1(diag, semiring):
    s = diag.shape[-1]

    def body(k, t):
        return semiring.add(
            t, semiring.mul(t[..., :, k, None], t[..., k, None, :])
        )

    return _loop(s, body, diag)


def _phase2_row(diag, panel, semiring):
    s = diag.shape[-1]

    def body(k, p):
        return semiring.add(
            p, semiring.mul(diag[..., :, k, None], p[..., k, None, :])
        )

    return _loop(s, body, panel)


def _phase2_col(diag, panel, semiring):
    s = diag.shape[-1]

    def body(k, p):
        return semiring.add(
            p, semiring.mul(p[..., :, k, None], diag[..., k, None, :])
        )

    return _loop(s, body, panel)


def _phase3_jnp(w, col_panel, row_panel, semiring, chunk: int = 8):
    """Local W ⊕= col_panel ⊗ row_panel without an (n_r, s, n_c) blowup.

    Processes the contraction in k-chunks (the staged idea, in jnp): each
    chunk materializes (…, n_r, chunk, n_c) — `chunk` controls the
    transient.  Batch-rank-agnostic (ellipsis indexing).
    """
    s = col_panel.shape[-1]

    def _outer(a, b):
        return semiring.add_reduce(
            semiring.mul(a[..., :, :, None], b[..., None, :, :]), axis=-2
        )

    def body(i, w):
        a = _dyn_slice(col_panel, 0, i * chunk, w.shape[-2], chunk)
        b = _dyn_slice(row_panel, i * chunk, 0, chunk, w.shape[-1])
        return semiring.add(w, _outer(a, b))

    if s % chunk:
        return semiring.add(w, _outer(col_panel, row_panel))
    return _loop(s // chunk, body, w)


def _phase3_pallas(w, col_panel, row_panel, semiring, interpret):
    from repro.kernels.minplus_matmul import semiring_matmul

    n_r, n_c = w.shape
    bm = 256 if n_r % 256 == 0 else n_r
    bn = 256 if n_c % 256 == 0 else n_c
    bk = min(32, col_panel.shape[1])
    return semiring_matmul(
        col_panel, row_panel, w, semiring=semiring, bm=bm, bn=bn, bk=bk,
        interpret=interpret,
    )


def build_fw_shard_fn(
    mesh: Mesh,
    n: int,
    *,
    block_size: int = 128,
    row_axes: Sequence[str] | str = "data",
    col_axes: Sequence[str] | str = "model",
    semiring: Semiring = MIN_PLUS,
    backend: str = "fused",
    bk: int = 32,
    variant: str = "fori",
    batch_block: int | None = None,
    interpret: bool | None = None,
    fused_lowering: str = "auto",
    lookahead: bool = False,
    phase2_shard: bool = False,
    batched: bool = False,
):
    """Returns (sharded_step_fn, in_sharding) for `rounds_per_call` rounds.

    sharded_step_fn(w, first_round, num_rounds) runs rounds [first_round,
    first_round+num_rounds) — it is jit-compiled once and reused for every
    chunk.  n, block_size, mesh shape are static; ``batched=True`` expects
    (B, n, n) input (trailing dims sharded, every device holds B blocks).

    backend: "fused" — the whole round as one bordered ``fw_round``
    dispatch per device (module docstring); "jnp"/"pallas" — the per-phase
    lowerings.  ``fused_lowering`` picks the fused round's execution:
    "pallas" (the kernel; interpret per ``interpret``), "ref" (its bitwise
    XLA lowering) or "auto" (ref on CPU, pallas elsewhere — the same policy
    as ``apsp.solve``).  ``bk``/``variant`` are the phase-3 staging knobs of
    the fused round; with the defaults the distributed solve is bitwise
    equal to the single-device ``solve(method="fused")``.

    phase2_shard (beyond-paper, §Perf; per-phase backends only): the panel
    closures are j-(resp. i-) independent, so instead of every device
    redundantly closing its full (s, n_c) panel slice, each device closes a
    1/R (resp. 1/C) chunk and the chunks are all-gathered.  Compute drops
    R×/C× for ~2× panel comm — a clear win whenever the workload is
    compute-bound (the Pallas backend).
    """
    if interpret is None:
        from repro.kernels.ops import default_interpret

        interpret = default_interpret()
    if fused_lowering == "auto":
        from repro.kernels.ops import default_interpret

        fused_lowering = "ref" if default_interpret() else "pallas"
    R = _axis_size(mesh, row_axes)
    C = _axis_size(mesh, col_axes)
    s = block_size
    n_r, n_c = n // R, n // C
    if n % (R * s) or n % (C * s) or n_r % s or n_c % s:
        raise ValueError(
            f"n={n} must give per-device blocks divisible by block_size={s} "
            f"on mesh R={R}, C={C} — plan through apsp.plan.distributed_plan"
            f" (or apsp.solve(method='distributed')), which auto-pads"
        )
    if phase2_shard and (backend == "fused" or batched):
        raise ValueError(
            "phase2_shard applies to the per-phase backends (jnp/pallas) on "
            "unbatched input; the fused bordered round closes panels inside "
            "the kernel"
        )

    row_t = (row_axes,) if isinstance(row_axes, str) else tuple(row_axes)
    col_t = (col_axes,) if isinstance(col_axes, str) else tuple(col_axes)
    dims = (
        row_t if len(row_t) > 1 else row_t[0],
        col_t if len(col_t) > 1 else col_t[0],
    )
    spec = P(None, *dims) if batched else P(*dims)

    if fused_lowering == "ref":
        from repro.kernels.ref import fw_round_bordered_ref

        def bordered_round(aug, pr, pc):
            return fw_round_bordered_ref(
                aug, pr, pc, block_size=s, bk=bk, variant=variant,
                semiring=semiring,
            )
    else:
        from repro.kernels.fw_round import fw_round_bordered

        def bordered_round(aug, pr, pc):
            return fw_round_bordered(
                aug, pr, pc, block_size=s, bk=bk, variant=variant,
                batch_block=batch_block, semiring=semiring,
                interpret=interpret,
            )

    def one_round(b, wl):
        o = b * s
        my_r = _my_index(row_t)
        my_c = _my_index(col_t)
        owner_r = o // n_r
        owner_c = o // n_c
        row_in = o - owner_r * n_r
        col_in = o - owner_c * n_c
        zero = jnp.asarray(semiring.zero, wl.dtype)

        # --- broadcast the raw pivot tile and panel slices (masked ⊕-
        # reduce across the mesh == broadcast from the owner).
        diag_raw = _dyn_slice(wl, row_in, col_in, s, s)
        is_owner = jnp.logical_and(my_r == owner_r, my_c == owner_c)
        diag_raw = jnp.where(is_owner, diag_raw, zero)
        diag = _bcast(diag_raw, row_t + col_t, semiring)

        rp_raw = _dyn_slice(wl, row_in, 0, s, n_c)
        rp_raw = jnp.where(my_r == owner_r, rp_raw, zero)
        rp_raw = _bcast(rp_raw, row_t, semiring)

        cp_raw = _dyn_slice(wl, 0, col_in, n_r, s)
        cp_raw = jnp.where(my_c == owner_c, cp_raw, zero)
        cp_raw = _bcast(cp_raw, col_t, semiring)

        if backend == "fused":
            # --- the paper's single-dispatch round, per device: stack the
            # raw pivot tile + panels as a border and run the whole round
            # (phases 1-3) through the bordered fw_round schedule.  The
            # owner-echo tile coordinates point at the device's own copies
            # of the global pivot bands inside the bordered matrix.
            aug = jnp.concatenate([
                jnp.concatenate([diag, rp_raw], axis=-1),
                jnp.concatenate([cp_raw, wl], axis=-1),
            ], axis=-2)
            pr = jnp.where(my_r == owner_r, 1 + row_in // s, -1)
            pc = jnp.where(my_c == owner_c, 1 + col_in // s, -1)
            aug = bordered_round(aug, pr, pc)
            return aug[..., s:, s:]

        # --- per-phase lowerings: close diag + panels, then relax.
        diag = _phase1(diag, semiring)
        if phase2_shard and n_c % R == 0 and not batched:
            wch = n_c // R
            chunk = jax.lax.dynamic_slice(rp_raw, (0, my_r * wch), (s, wch))
            chunk = _phase2_row(diag, chunk, semiring)
            rp = jax.lax.all_gather(chunk, row_t, axis=1, tiled=True)
        else:
            rp = _phase2_row(diag, rp_raw, semiring)

        if phase2_shard and n_r % C == 0 and not batched:
            hch = n_r // C
            chunk = jax.lax.dynamic_slice(cp_raw, (my_c * hch, 0), (hch, s))
            chunk = _phase2_col(diag, chunk, semiring)
            cp = jax.lax.all_gather(chunk, col_t, axis=0, tiled=True)
        else:
            cp = _phase2_col(diag, cp_raw, semiring)

        # --- write panels back on owners (select keeps SPMD uniform).
        wl_rows = _dyn_update(wl, rp, row_in, 0)
        wl = jnp.where(my_r == owner_r, wl_rows, wl)
        wl_cols = _dyn_update(wl, cp, 0, col_in)
        wl = jnp.where(my_c == owner_c, wl_cols, wl)

        # --- phase 3: relax the whole local block (pivot bands → no-op).
        if backend == "pallas":
            wl = _phase3_pallas(wl, cp, rp, semiring, interpret)
        else:
            wl = _phase3_jnp(wl, cp, rp, semiring)
        return wl

    def _bcast(x, axes, sr):
        """⊕-reduction broadcast for any semiring (pmin/pmax/psum as fits)."""
        if sr.add is jnp.minimum:
            return jax.lax.pmin(x, axes)
        if sr.add is jnp.maximum:
            return jax.lax.pmax(x, axes)
        return jax.lax.psum(x, axes)  # PLUS_MUL: zero = 0 ⇒ sum-broadcast

    def chunk_fn(wl, first_round, num_rounds):
        def body(i, wl):
            return one_round(first_round + i, wl)

        return jax.lax.fori_loop(0, num_rounds, body, wl)

    sharded = _shard_map(
        functools.partial(chunk_fn),
        mesh=mesh,
        in_specs=(spec, P(), P()),
        out_specs=spec,
    )
    in_sharding = NamedSharding(mesh, spec)
    return sharded, in_sharding


def build_repair_shard_fn(
    mesh: Mesh,
    n: int,
    *,
    row_axes: Sequence[str] | str = "data",
    col_axes: Sequence[str] | str = "model",
    semiring: Semiring = MIN_PLUS,
    edges: int,
):
    """Shard-mapped rank-1 repair over the mesh: (sharded_fn, in_sharding).

    The distributed form of ``kernels.fw_repair``: the closure shards as in
    ``build_fw_shard_fn`` ((n/R, n/C) block per device) and each of the
    ``edges`` updates is one masked ⊕-broadcast pair — the owner row block
    contributes the *current* pivot row v_e along the row axes, the owner
    column block the current column u_e along the column axes (everyone
    else the ⊕-identity, so the pmin/pmax/psum reduction IS the broadcast,
    bit-exactly) — followed by the identical local elementwise chain
    ``d ⊕= (d[:, u_e] ⊗ w_e) ⊗ d[v_e, :]``.  Because every device applies
    the same per-element ⊕/⊗ chain to the same evolving values, the result
    is bitwise equal to the single-device repair (tests/test_fw_repair.py,
    8-virtual-device subprocess).

    ``n`` must already be padded to the mesh multiple
    (``plan.distributed_plan``); u/v index the padded matrix; weights
    ride replicated in the matrix dtype.  Distance-only, like the
    distributed solve.
    """
    row_t = (row_axes,) if isinstance(row_axes, str) else tuple(row_axes)
    col_t = (col_axes,) if isinstance(col_axes, str) else tuple(col_axes)
    R, C = _axis_size(mesh, row_t), _axis_size(mesh, col_t)
    if n % R or n % C:
        raise ValueError(f"n={n} must divide over the {R}x{C} mesh grid")
    nr, nc = n // R, n // C
    zero = semiring.zero

    def _bcast(x, axes):
        if semiring.add is jnp.minimum:
            return jax.lax.pmin(x, axes)
        if semiring.add is jnp.maximum:
            return jax.lax.pmax(x, axes)
        return jax.lax.psum(x, axes)  # PLUS_MUL / packed: zero = 0

    def local_fn(dl, u, v, w):
        my_r, my_c = _my_index(row_t), _my_index(col_t)

        def body(e, dl):
            ue, ve = u[e], v[e]
            we = jax.lax.dynamic_index_in_dim(w, e, keepdims=False)
            own_c = ue // nc
            col = jax.lax.dynamic_slice(dl, (0, ue - own_c * nc), (nr, 1))
            col = jnp.where(my_c == own_c, col, jnp.full_like(col, zero))
            col = _bcast(col, col_t)
            own_r = ve // nr
            row = jax.lax.dynamic_slice(dl, (ve - own_r * nr, 0), (1, nc))
            row = jnp.where(my_r == own_r, row, jnp.full_like(row, zero))
            row = _bcast(row, row_t)
            cand = semiring.mul(semiring.mul(col, we), row)
            return semiring.add(dl, cand)

        return jax.lax.fori_loop(0, edges, body, dl)

    dims = (
        row_t if len(row_t) > 1 else row_t[0],
        col_t if len(col_t) > 1 else col_t[0],
    )
    spec = P(*dims)
    sharded = _shard_map(
        local_fn, mesh=mesh, in_specs=(spec, P(), P(), P()), out_specs=spec,
    )
    return sharded, NamedSharding(mesh, spec)


def fw_distributed(
    w: np.ndarray | jax.Array,
    mesh: Mesh,
    *,
    block_size: int = 128,
    row_axes: Sequence[str] | str = "data",
    col_axes: Sequence[str] | str = "model",
    semiring: Semiring = MIN_PLUS,
    backend: str = "fused",
    bk: int = 32,
    variant: str = "fori",
    batch_block: int | None = None,
    interpret: bool | None = None,
    fused_lowering: str = "auto",
    rounds_per_call: int | None = None,
    checkpoint_cb: Callable[[int, jax.Array], None] | None = None,
    start_round: int = 0,
    phase2_shard: bool = False,
) -> jax.Array:
    """Run distributed FW to completion; returns the (sharded) result.

    w: (n, n) adjacency matrix — or (B, n, n) to close B graphs at once
    (trailing dims sharded over the mesh; one collective per round carries
    the whole batch).  n must satisfy the mesh-divisibility constraint;
    ``apsp.solve(method="distributed")`` auto-pads arbitrary n via
    ``plan.distributed_plan`` before calling in here.

    backend: "fused" (default — one bordered ``fw_round`` dispatch per
    device per round) | "jnp" | "pallas" (per-phase lowerings).

    checkpoint_cb(next_round, w) is called after every jitted chunk —
    restart by passing ``start_round`` = the last checkpointed round.  Any
    round boundary is a consistent checkpoint and re-running a round is
    harmless (module docstring, Fault tolerance).
    """
    batched = w.ndim == 3
    n = w.shape[-1]
    s = block_size
    rounds = n // s
    if rounds_per_call is None:
        rounds_per_call = rounds
    sharded, sharding = build_fw_shard_fn(
        mesh, n, block_size=s, row_axes=row_axes, col_axes=col_axes,
        semiring=semiring, backend=backend, bk=bk, variant=variant,
        batch_block=batch_block, interpret=interpret,
        fused_lowering=fused_lowering, phase2_shard=phase2_shard,
        batched=batched,
    )
    step = jax.jit(sharded, static_argnames=(), donate_argnums=(0,))
    # Host arrays go straight to their shards; nothing lands whole on one
    # device first.
    wl = jax.device_put(w, sharding)
    b = start_round
    while b < rounds:
        todo = min(rounds_per_call, rounds - b)
        wl = step(wl, jnp.int32(b), jnp.int32(todo))
        b += todo
        if checkpoint_cb is not None:
            checkpoint_cb(b, wl)
    return wl
