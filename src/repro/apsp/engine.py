"""Batched APSP execution engine: the one path from a configuration to a
runner, with a plan/executable cache and ragged bucketing.

Every solve runs here.  The stateless ``api.solve`` builds an engine from
its options and calls ``ApspEngine.solve`` once; serving workloads keep
one engine, because the same (n, B, dtype) shapes recur thousands of times
and request batches arrive *ragged* (mixed graph sizes).  This module owns
the dispatch-and-padding policy (``_resolve_shape``), the backend policy
(``_resolve_backend``), the one choice of round lowering (``_build``), the
input coercion and padding, and the result type:

  * **plan/executable cache** — each distinct
    ``(n_padded, batch, dtype, semiring, method, block dims)`` key is
    planned once: block size and batch block resolved, VMEM/HBM modeled
    (``plan.fused_round_vmem_bytes(batch=…)``), and a jitted runner built.
    Repeated solves on the same key skip planning AND tracing entirely —
    ``ExecutablePlan.traces`` counts actual retraces (it increments only
    while JAX traces the runner), so tests can assert cache hits compile
    nothing.
  * **``solve_many``** — takes a ragged list of graphs, buckets them by
    ``(method, n_padded, block_size, dtype)``, pads each bucket into one
    (B, m, m) batch, and runs each bucket through the kernels' native batch
    grid (one dispatch per round for the whole bucket).  Results come back
    in input order and match per-graph ``solve`` bit-for-bit — bucketing is
    a scheduling decision, never a numerics decision.
  * **successors** — ``solve_many(successors=True)`` threads the fused
    successor round (``fw_staged_with_successors``) per bucket, the
    batched-routing-tables scenario ``serve.engine.RoutingEngine`` builds
    on.
  * **meshes** — an engine constructed with ``mesh=`` and
    method="distributed" caches shard-mapped batched executables instead
    (the fused bordered round per device — ``core.distributed``); plan
    keys carry the mesh signature, so ragged ``solve_many`` buckets shard
    across devices with the same no-retrace guarantee.
  * **trace names** — every solve runner is jitted as ``apsp_solve`` (its
    compiled module is ``jit_apsp_solve``), the repair runners as
    ``apsp_repair``, ``apsp_repair_del_mark`` and ``apsp_repair_del_sweep``,
    the edge-list packer as ``apsp_pack``; ``solve`` and ``solve_many`` run
    inside the host span ``apsp.solve``, ``pack_edges`` inside
    ``apsp.pack``.
    A profiler trace thus tells the solve program's device time apart
    from the engine's own work around it (tests/test_tpu_compile.py pins
    the names; the benchmark's ``solve.*`` metrics read them).

The engine is single-process state; it holds no device buffers beyond
JAX's own executable cache.  Thread-safety is the caller's concern (the
serving layer serializes refreshes).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.apsp import plan
from repro.core.floyd_warshall import fw_blocked, fw_naive, fw_numpy
from repro.core.paths import fw_blocked_with_successors, fw_with_successors
from repro.core.semiring import (
    I16_INF,
    I16_NINF,
    LOWERED_SEMIRINGS,
    MIN_PLUS,
    PACK_LANES,
    SEMIRINGS,
    Semiring,
    lower_semiring,
)
from repro.core.staged import fw_staged, fw_staged_with_successors
from repro.utils import compat

METHODS = (
    "auto", "numpy", "naive", "blocked", "staged", "fused", "recursive",
    "distributed",
)

# Methods that can track next-hop successor matrices (min-plus only).
SUCCESSOR_METHODS = ("naive", "blocked", "staged", "fused")

# Below this size a padded tile pass does more work than the n sweeps of the
# naive kernel; "auto" stays on the naive rung.
_NAIVE_CUTOFF = 64


class NegativeCycleError(ValueError):
    """The distance matrix certifies a negative cycle (diag < 0)."""


@dataclasses.dataclass(frozen=True)
class APSPResult:
    """Outcome of ``solve``: distances plus how they were computed.

    dist: (n, n) or (B, n, n) closure, unpadded.
    succ: next-hop matrix of the same shape (None unless successors=True);
          succ[i, j] = -1 where no i→j path exists.
    backend: the resolved backend whose lowering ran the staged/fused round
          ("tpu" | "gpu" | "ref"); None for the other methods.
    """

    dist: jax.Array | np.ndarray
    succ: jax.Array | np.ndarray | None
    method: str
    semiring: str
    block_size: int | None
    n: int
    padded_n: int
    backend: str | None = None

    @property
    def batched(self) -> bool:
        return np.ndim(self.dist) == 3


def negative_cycle_mask(dist) -> jax.Array:
    """Per-graph bool: does the (…, n, n) closure certify a negative cycle?"""
    diag = jnp.diagonal(jnp.asarray(dist), axis1=-2, axis2=-1)
    return jnp.any(diag < 0, axis=-1)


def _resolve_semiring(semiring: Semiring | str) -> Semiring:
    if isinstance(semiring, str):
        sr = SEMIRINGS.get(semiring) or LOWERED_SEMIRINGS.get(semiring)
        if sr is None:
            raise ValueError(
                f"unknown semiring {semiring!r}; have "
                f"{sorted(SEMIRINGS) + sorted(LOWERED_SEMIRINGS)}"
            )
        return sr
    return semiring


def _is_min_plus(sr: Semiring) -> bool:
    """min_plus or one of its storage lowerings (negative-cycle semantics)."""
    return sr is MIN_PLUS or sr.name.startswith("min_plus")


def pack_edges(src, dst, n: int) -> jax.Array:
    """Pack per-graph edge lists into the or_and_packed word table.

    src, dst: (B, E) int arrays, or (E,) for one graph: edge ``e`` of graph
    ``g`` runs ``src[g, e] → dst[g, e]``.  Duplicate edges and self-loops
    are legal; an edge with an endpoint outside [0, n) is dropped.  Returns
    the (ceil(B/32), n, n) int32 words of ``pack_reachability``'s layout
    (graph g at word g // 32, bit g % 32) with every graph's diagonal bit
    set (the ⊗-identity, -1, in a word of 32 graphs), so the table is ready
    for the packed closure.

    Peak memory is the words plus O(B·E): each graph's edges are sorted to
    drop repeats, after which the bits landing on one word entry are
    distinct and a scatter-add of them is their OR.  No (B, n, n) array is
    made.  Traceable; ``ApspEngine.pack_edges`` runs it jitted.
    """
    src = jnp.asarray(src)
    dst = jnp.asarray(dst)
    if src.ndim == 1:
        src, dst = src[None], dst[None]
    if src.ndim != 2 or src.shape != dst.shape or src.shape[0] < 1:
        raise ValueError(
            f"src and dst must both be (E,) or (B, E) with B >= 1, got "
            f"{src.shape} and {dst.shape}"
        )
    if not (jnp.issubdtype(src.dtype, jnp.integer)
            and jnp.issubdtype(dst.dtype, jnp.integer)):
        raise ValueError(
            f"src and dst must be integer vertex ids, got {src.dtype} and "
            f"{dst.dtype}"
        )
    B = src.shape[0]
    G = -(-B // PACK_LANES)
    s, d = jax.lax.sort(
        (src.astype(jnp.int32), dst.astype(jnp.int32)), dimension=1,
        num_keys=2,
    )
    repeat = jnp.zeros_like(s, dtype=bool).at[:, 1:].set(
        (s[:, 1:] == s[:, :-1]) & (d[:, 1:] == d[:, :-1])
    )
    inside = (s >= 0) & (s < n) & (d >= 0) & (d < n)
    g = jnp.arange(B, dtype=jnp.int32)[:, None]
    bit = jnp.left_shift(jnp.int32(1), g % PACK_LANES)
    bit = jnp.where(repeat | ~inside, 0, bit)
    words = jnp.zeros((G, n, n), jnp.int32).at[
        jnp.broadcast_to(g // PACK_LANES, s.shape),
        jnp.clip(s, 0, n - 1), jnp.clip(d, 0, n - 1),
    ].add(bit)
    # Every graph's diagonal holds its ⊗-identity bit; lanes past B stay
    # empty graphs, as in pack_reachability.
    lanes = jnp.minimum(B - PACK_LANES * jnp.arange(G), PACK_LANES)
    diag = jnp.where(lanes == PACK_LANES, -1,
                     jnp.left_shift(1, lanes % PACK_LANES) - 1)
    idx = jnp.arange(n)
    return words.at[:, idx, idx].set(diag.astype(jnp.int32)[:, None])


def _resolve_method(method: str, n: int, successors: bool) -> str:
    if method not in METHODS:
        raise ValueError(f"unknown method {method!r}; have {METHODS}")
    if method != "auto":
        return method
    if n <= _NAIVE_CUTOFF:
        return "naive"
    # The Pallas kernels run natively on TPU; on CPU they interpret (slow),
    # so auto prefers the jnp blocked path there.  The same split applies to
    # successor tracking: fused-with-successors on TPU, blocked on CPU.
    if successors:
        return "fused" if jax.default_backend() == "tpu" else "blocked"
    return "staged" if jax.default_backend() == "tpu" else "blocked"


def _resolve_shape(
    method: str, n: int, successors: bool, block_size: int | None,
    *, mesh=None, row_axes="data", col_axes="model",
    hbm_budget: int | None = None, batch: int = 1, word: int = 4,
) -> tuple[str, int | None, int]:
    """(method, block_size, n_padded) — THE dispatch-and-padding policy.

    The engine's plan and bucket keys both read it, so a bucket can never
    pad or dispatch differently from a single solve of the same input.  For
    method="distributed" the padding multiple depends on the mesh grid, not
    just the tile size: with a mesh it routes through
    ``plan.distributed_plan`` (auto-padding to the mesh multiple); without
    one it returns n unchanged and the caller raises.  ``hbm_budget``
    (device bytes) promotes any in-core tiled method to "recursive" when
    the padded matrix (batch · m² · word bytes) would not fit — recursive
    pads identically to fused at the same block size, so the promotion
    never changes the padded shape, only the schedule.
    """
    meth = _resolve_method(method, n, successors)
    if meth == "distributed" and mesh is not None:
        from repro.core.distributed import _axis_size

        R = _axis_size(mesh, row_axes)
        C = _axis_size(mesh, col_axes)
        dp = plan.distributed_plan(
            n, R * C, grid=(R, C), block_size=block_size
        )
        return meth, dp["block_size"], dp["n_padded"]
    if meth in ("blocked", "staged", "fused", "recursive"):
        s = block_size or plan.auto_block_size(n)
        m = plan.padded_size(n, s)
        if (
            meth != "recursive"
            and not successors
            and hbm_budget is not None
            and batch * m * m * word > hbm_budget
        ):
            meth = "recursive"
        return meth, s, m
    return meth, None, n


def _coerce(w, semiring: Semiring, dtype=None):
    """np/jnp coercion + storage-dtype encoding of every engine input.

    * Dtype-pinned lowerings encode up front: int16 tropical clips weights
      into [I16_NINF, I16_INF] (so ±inf lands exactly on the sentinels and
      out-of-range weights saturate, never wrap); the packed or_and lowering
      requires pre-packed int32/uint32 bit-plane words (``pack_reachability``
      or ``solve(packed=True)``).
    * An explicit float ``dtype`` (bf16/f32/f64) is a plain cast — ±inf is
      representable, so no re-encoding is needed.
    * Otherwise, integer matrices cannot represent the ±inf identities of
      the tropical semirings: padding / missing edges would wrap on ⊗
      (INT_MAX + w < 0) and silently shorten paths.  Promote once, up front.
    """
    arr = np.asarray(w) if isinstance(w, (np.ndarray, list, tuple)) else w
    if arr.ndim not in (2, 3) or arr.shape[-1] != arr.shape[-2]:
        raise ValueError(f"w must be (n,n) or (B,n,n), got {arr.shape}")
    if semiring.packed:
        if not jnp.issubdtype(arr.dtype, jnp.integer):
            raise ValueError(
                f"semiring {semiring.name!r} takes int32 bit-plane words, "
                f"got {arr.dtype}; pack boolean graphs with "
                f"pack_reachability() or call solve(..., packed=True)"
            )
        if arr.dtype == np.uint32:
            # Bit-pattern reinterpret, not a value cast (bit 31 is graph 31).
            arr = (
                arr.view(np.int32) if isinstance(arr, np.ndarray)
                else jax.lax.bitcast_convert_type(arr, jnp.int32)
            )
        elif arr.dtype != np.int32:
            arr = arr.astype(jnp.int32)
        return arr
    if semiring.dtype == "int16":
        xp = np if isinstance(arr, np.ndarray) else jnp
        return xp.clip(arr, I16_NINF, I16_INF).astype(xp.int16)
    if dtype is not None:
        return jnp.asarray(arr).astype(dtype)
    if not jnp.issubdtype(arr.dtype, jnp.floating) and not (
        np.isfinite(semiring.zero) and np.isfinite(semiring.one)
    ):
        arr = arr.astype(np.float32)
    return arr


def _pad(w: jax.Array, m: int, semiring: Semiring) -> jax.Array:
    """Pad (…, n, n) to (…, m, m) with ⊕-identity edges, ⊗-identity diag.

    A numpy input pads on the host (the distributed solve then places it
    straight into its sharding, never whole on one device)."""
    n = w.shape[-1]
    if m == n:
        return w
    if isinstance(w, np.ndarray):
        out = np.full(w.shape[:-2] + (m, m), semiring.zero, w.dtype)
        out[..., :n, :n] = w
        idx = np.arange(n, m)
        out[..., idx, idx] = semiring.one
        return out
    widths = [(0, 0)] * (w.ndim - 2) + [(0, m - n), (0, m - n)]
    out = jnp.pad(w, widths, constant_values=semiring.zero)
    idx = jnp.arange(n, m)
    return out.at[..., idx, idx].set(jnp.asarray(semiring.one, out.dtype))


def _check_negative_cycles(dist, batched: bool) -> None:
    bad = np.asarray(negative_cycle_mask(dist))
    if bad.any():
        which = f"graphs {np.flatnonzero(bad).tolist()}" if batched else "graph"
        raise NegativeCycleError(f"negative cycle detected in {which}")


def _check_successor_args(meth: str, semiring: Semiring) -> None:
    if semiring is not MIN_PLUS:
        raise ValueError("successors=True requires the min_plus semiring")
    if meth not in SUCCESSOR_METHODS:
        raise ValueError(
            f"successors=True supports methods {SUCCESSOR_METHODS}, not {meth!r}"
        )


def _resolve_backend(backend: str, interpret: bool | None) -> str:
    """The solver's backend policy on top of ``compat.resolve_pallas_backend``.

    One historical wrinkle: an *explicit* ``interpret=`` under
    ``backend="auto"`` has always meant "run the TPU Pallas lowering with
    that interpret flag" (the tests drive the kernels that way on CPU), so
    auto only falls back to "ref" when interpret is left unset.
    """
    be = compat.resolve_pallas_backend(backend)
    if backend == "auto" and interpret is not None and be == "ref":
        be = "tpu"
    return be


@dataclasses.dataclass(frozen=True)
class PlanKey:
    """The executable-cache key: everything that changes the compiled code.

    ``mesh`` is the mesh signature for distributed entries — the
    ((axis, size), …) grid plus the row/col axis split — so the same
    engine can serve several meshes without executable collisions; None
    for single-device methods.  ``backend`` is the *resolved* round
    lowering ("tpu" | "gpu" | "ref", never "auto") — engines pinned to
    different backends never share executables.
    """

    n_padded: int
    batch: int
    dtype: str
    semiring: str
    method: str
    block_size: int | None
    bk: int
    batch_block: int | None
    successors: bool
    mesh: tuple | None = None
    edges: int = 0  # repair entries: the padded edge-batch bucket E
    leaf: int | None = None  # recursive entries: pivot-panel width
    oocore: bool = False     # recursive entries: host-resident panel store
    backend: str = "tpu"     # resolved round lowering (tpu | gpu | ref)


@dataclasses.dataclass
class ExecutablePlan:
    """A planned, compiled (on first use) batched solve.

    runner: padded (batch, m, m) → padded dist (or (dist, succ)).
    traces: number of times JAX actually traced the runner — stays at 1 for
            a warm cache entry (the no-recompile guarantee tests assert).
            A single-device solve runner shares its program, and so its
            trace and compile, with every engine of this process that
            plans the same one; its count is the program's.
    vmem_bytes / hbm_bytes_per_round: the plan-layer model for the fused
            round at this key (None for non-kernel methods).
    """

    key: PlanKey
    runner: Callable[[jax.Array], Any]
    vmem_bytes: int | None = None
    hbm_bytes_per_round: float | None = None
    traces: int = 0


@dataclasses.dataclass
class EngineStats:
    hits: int = 0
    misses: int = 0
    solves: int = 0
    graphs_solved: int = 0   # a packed engine counts 32 per word (lanes)
    repairs: int = 0         # rank-1 repair dispatches (ApspEngine.repair)
    edges_repaired: int = 0  # real (unpadded) edge updates absorbed by them
    repair_rejects: int = 0  # should_repair fast-rejects (edge worsenings)
    repair_dels: int = 0           # decremental sweeps (ApspEngine.repair_del)
    repair_del_rows: int = 0       # affected rows those sweeps re-relaxed
    repair_del_noops: int = 0      # empty affected set — no sweep dispatched
    repair_del_fallbacks: int = 0  # marked, then re-solved (cost/semiring)
    edges_deleted: int = 0         # real deletions absorbed (sweeps + noops)
    packs: int = 0          # edge-list packs (ApspEngine.pack_edges)
    graphs_packed: int = 0  # graphs those packs wrote, one per bit lane
    edges_packed: int = 0   # edge tuples they read, repeats included


class ApspEngine:
    """Session object owning the plan/executable cache for repeated solves.

        eng = ApspEngine()
        res = eng.solve(w)                    # same surface as apsp.solve
        results = eng.solve_many(graphs)      # ragged batch, auto-bucketed
        tables = eng.solve_many(graphs, successors=True)   # routing tables

    Construction pins the solve configuration (method, semiring, block
    dims); per-call shape/dtype variation is absorbed by the cache.
    """

    def __init__(
        self,
        *,
        method: str = "auto",
        semiring: Semiring | str = MIN_PLUS,
        dtype=None,
        packed: bool = False,
        block_size: int | None = None,
        bk: int = 32,
        batch_block: int | None = None,
        variant: str = "fori",
        validate: bool = True,
        backend: str = "auto",
        interpret: bool | None = None,
        vmem_budget: int | None = None,
        mesh=None,
        row_axes="data",
        col_axes="model",
        leaf: int | None = None,
        hbm_budget: int | None = None,
        devices=None,
    ):
        """method/semiring/block dims pin the solve configuration; per-call
        shape/dtype/batch variation is absorbed by the plan cache.

        dtype/packed pin a *storage lowering* at construction
        (``core.semiring.lower_semiring``): ``dtype=jnp.int16`` runs the
        saturating int16 tropical lowering, ``dtype=jnp.bfloat16`` casts
        weights to bf16, and ``packed=True`` (or_and only) serves the
        bit-packed int32 closure — engine inputs are then *pre-packed*
        bit-plane words (``pack_edges`` makes them from edge lists on the
        device, ``api.pack_reachability`` from dense graphs; the stateless
        ``solve(packed=True)`` owns pack/unpack, the engine stays in word
        space so cached plans see the physical shapes).  Plan keys carry
        the lowered semiring name + storage dtype, so an f32 and an int16
        engine never share executables.

        mesh/row_axes/col_axes: a ``jax.sharding.Mesh`` enables
        method="distributed" — every cached executable is then a
        shard-mapped batched solve over that mesh (plan keys carry the mesh
        signature), and ``solve_many`` buckets shard across devices without
        retracing.  Distributed solves do not track successors.

        backend pins the round lowering for the staged/fused methods —
        "auto" (resolve from the attached hardware), "tpu", "gpu" (the
        Triton round; interpreted when no GPU is attached), or "ref".  It
        is resolved once, here; ``_build`` maps the resolved value to
        ``fw_staged``'s ``lowering``.  The resolved value is part of every
        plan key, so engines on different backends never share
        executables and each backend keeps its own warm-cache no-retrace
        guarantee.

        leaf/hbm_budget/devices configure method="recursive" (the R-Kleene
        panel schedule of ``apsp.kleene``): ``hbm_budget`` also promotes
        the in-core tiled methods to recursive whenever the padded matrix
        would not fit the budget (weighed at the solve's batch and
        dtype); plan keys
        then carry (leaf, oocore), and the cached entry keeps ONE
        ``KleeneExecutor`` whose jit caches make warm solves retrace
        nothing.
        """
        if method not in METHODS:
            raise ValueError(f"unknown method {method!r}; have {METHODS}")
        if method == "distributed" and mesh is None:
            raise ValueError(
                "ApspEngine(method='distributed') requires a mesh= — "
                "construct one (e.g. launch.mesh.make_host_mesh) and pass it"
            )
        self.method = method
        self.semiring = lower_semiring(
            _resolve_semiring(semiring), dtype, packed=packed
        )
        self.dtype = dtype
        self.block_size = block_size
        self.bk = bk
        self.batch_block = batch_block
        self.variant = variant
        self.validate = validate
        self.interpret = interpret
        self.backend = backend
        self._backend = _resolve_backend(backend, interpret)
        self.vmem_budget = vmem_budget
        self.mesh = mesh
        self.row_axes = row_axes
        self.col_axes = col_axes
        self.leaf = leaf
        self.hbm_budget = hbm_budget
        self.devices = devices
        self.stats = EngineStats()
        self._cache: dict[PlanKey, ExecutablePlan] = {}

    @property
    def _mesh_sig(self) -> tuple | None:
        if self.mesh is None:
            return None
        row = self.row_axes if isinstance(self.row_axes, str) else tuple(self.row_axes)
        col = self.col_axes if isinstance(self.col_axes, str) else tuple(self.col_axes)
        return (tuple(self.mesh.shape.items()), row, col)

    # ------------------------------------------------------------- planning
    def clear_cache(self) -> None:
        self._cache.clear()

    @property
    def cache_size(self) -> int:
        return len(self._cache)

    def _resolve_shape(
        self, n: int, successors: bool, batch: int = 1, dtype=None,
    ) -> tuple[str, int | None, int]:
        """(method, block_size, n_padded) for an n-vertex graph — delegates
        to the module's ``_resolve_shape``, the ONE dispatch-and-padding
        policy, so the bucket key and the plan key can never drift.  The
        plan key weighs the hbm_budget promotion at its real batch and
        dtype; buckets form at batch=1 (a bucket's batch is unknown until
        formed), which is safe because the promotion never changes the
        padded shape."""
        if dtype is None:
            dtype = jnp.float32 if self.dtype is None else self.dtype
        return _resolve_shape(
            self.method, n, successors, self.block_size,
            mesh=self.mesh, row_axes=self.row_axes, col_axes=self.col_axes,
            hbm_budget=self.hbm_budget, batch=batch,
            word=jnp.dtype(dtype).itemsize,
        )

    def plan_for(
        self,
        n: int,
        batch: int = 1,
        *,
        dtype=jnp.float32,
        successors: bool = False,
    ) -> ExecutablePlan:
        """Resolve (and cache) the executable plan for an (n, batch) solve."""
        meth, s, m = self._resolve_shape(n, successors, batch, dtype)
        if successors:
            _check_successor_args(meth, self.semiring)
        if meth == "numpy" and self.semiring is not MIN_PLUS:
            raise ValueError("method='numpy' implements min_plus only")
        bb = None
        bk = self.bk
        dist_plan = None
        rec_plan = None
        if s is not None:
            if meth in ("staged", "fused", "distributed"):
                # The depth the round kernel runs: bk = s for the TPU
                # "fori" kernels, which do not read bk.
                bk = plan.kernel_bk(
                    s, bk, variant=self.variant, backend=self._backend
                )
            else:
                bk = min(bk, s)
            if meth == "recursive":
                # Planned ONCE here; _build consumes the same dict, so the
                # key's (leaf, oocore) and the executor's schedule cannot
                # diverge.
                rec_plan = plan.recursive_plan(
                    n, leaf=self.leaf, hbm_budget=self.hbm_budget,
                    block_size=s, batch=batch, dtype=dtype, bk=bk,
                    variant=self.variant,
                )
            elif meth in ("staged", "fused") and self._backend == "tpu":
                bb = self.batch_block or plan.auto_batch_block(
                    batch, m, s, bk=bk, variant=self.variant,
                    word=jnp.dtype(dtype).itemsize,
                    vmem_budget=self.vmem_budget, successors=successors,
                )
            elif meth in ("staged", "fused"):
                # The Triton round and the XLA twin keep no VMEM scratch:
                # the whole batch is one block.
                bb = self.batch_block or batch
            elif meth == "distributed":
                from repro.core.distributed import _axis_size

                R = _axis_size(self.mesh, self.row_axes)
                C = _axis_size(self.mesh, self.col_axes)
                # Planned ONCE here; _build consumes the same dict, so the
                # key's batch_block and the executable's VMEM model cannot
                # diverge.
                dist_plan = plan.distributed_plan(
                    m, R * C, grid=(R, C), block_size=s, batch=batch,
                    bk=bk, variant=self.variant,
                    word=jnp.dtype(dtype).itemsize,
                    vmem_budget=self.vmem_budget,
                )
                bb = self.batch_block or dist_plan["batch_block"]
        key = PlanKey(
            n_padded=m, batch=batch, dtype=str(jnp.dtype(dtype)),
            semiring=self.semiring.name, method=meth, block_size=s, bk=bk,
            batch_block=bb, successors=successors,
            mesh=self._mesh_sig if meth == "distributed" else None,
            leaf=rec_plan["leaf"] if rec_plan else None,
            oocore=rec_plan["out_of_core"] if rec_plan else False,
            backend=self._backend,
        )
        entry = self._cache.get(key)
        if entry is not None:
            self.stats.hits += 1
            return entry
        self.stats.misses += 1
        entry = self._build(key, dist_plan=dist_plan, rec_plan=rec_plan)
        self._cache[key] = entry
        return entry

    def _build(
        self, key: PlanKey, dist_plan: dict | None = None,
        rec_plan: dict | None = None,
    ) -> ExecutablePlan:
        """Construct the jitted batched runner for a cache key."""
        sr = self.semiring
        s, bk, bb = key.block_size, key.bk, key.batch_block
        interpret = self.interpret
        # The one choice of round lowering: the resolved backend names it.
        lowering = {"tpu": "pallas", "gpu": "gpu", "ref": "ref"}[key.backend]

        if key.method == "numpy":
            def runner(wp):
                return np.stack([fw_numpy(g) for g in np.asarray(wp)])

            return ExecutablePlan(key=key, runner=runner)

        if key.method == "distributed":
            # One shard-mapped batched solve over the engine's mesh: every
            # device runs the fused bordered round on its local tile set,
            # all rounds inside one jitted call.  The executable is keyed on
            # the mesh signature, so repeated (n, B, dtype) solves on the
            # same mesh never retrace.
            from repro.core.distributed import build_fw_shard_fn

            rounds = key.n_padded // s
            sharded, sharding = build_fw_shard_fn(
                self.mesh, key.n_padded, block_size=s,
                row_axes=self.row_axes, col_axes=self.col_axes,
                semiring=sr, backend="fused", bk=bk, variant=self.variant,
                batch_block=key.batch_block,  # resolved under OUR vmem budget
                # The bordered round has no Triton twin: off the TPU
                # lowering it runs its XLA twin.
                fused_lowering="pallas" if lowering == "pallas" else "ref",
                interpret=interpret, batched=True,
            )
            entry = ExecutablePlan(
                key=key, runner=None,
                vmem_bytes=dist_plan["vmem_bytes"] if dist_plan else None,
            )

            def apsp_solve(wl):
                entry.traces += 1
                return sharded(wl, jnp.int32(0), jnp.int32(rounds))

            jitted = jax.jit(apsp_solve)
            entry.runner = lambda wp: jitted(jax.device_put(wp, sharding))
            return entry

        if key.method == "recursive":
            # One KleeneExecutor per cache entry: its leaf/sweep jit caches
            # ARE the warm-cache guarantee (a second solve on the same key
            # re-enters the same compiled leaves and sweeps — ``traces``
            # stays put).  Each call gets a fresh panel store; the executor
            # holds no per-solve state.
            from repro.apsp.kleene import (
                DevicePanelStore,
                HostPanelStore,
                KleeneExecutor,
            )

            word = jnp.dtype(key.dtype).itemsize
            entry = ExecutablePlan(
                key=key,
                runner=None,
                vmem_bytes=plan.fused_round_vmem_bytes(
                    key.leaf, s, bk, word=word, variant=self.variant,
                ),
                hbm_bytes_per_round=(
                    rec_plan["hbm_bytes_total"] / rec_plan["rounds"]
                    if rec_plan else None
                ),
            )
            ex = KleeneExecutor(
                semiring=sr, block_size=s, leaf=key.leaf, bk=bk,
                variant=self.variant, interpret=interpret,
                devices=self.devices,
                on_trace=lambda: setattr(entry, "traces", entry.traces + 1),
            )
            oocore = key.oocore

            def runner(wp):
                store = (
                    HostPanelStore(np.asarray(wp)) if oocore
                    else DevicePanelStore(wp)
                )
                ex.run(store)
                return jnp.asarray(store.result())

            entry.runner = runner
            entry.executor = ex  # introspection: depth/steps/byte counters
            return entry

        entry = ExecutablePlan(key=key, runner=None)
        if key.method in ("staged", "fused"):
            scale = 2 if key.successors else 1
            word = jnp.dtype(key.dtype).itemsize
            if key.backend == "gpu":
                # Triton round: the on-chip model is the per-SM SMEM working
                # set, and the HBM model carries the band buffers' GMEM
                # round-trips (no VMEM scratch exists to charge).
                entry.vmem_bytes = scale * plan.gpu_round_smem_bytes(
                    s, bk, word=word, variant=self.variant,
                )
                entry.hbm_bytes_per_round = scale * plan.gpu_round_hbm_bytes(
                    key.n_padded, s, word=word, batch=key.batch,
                )
            else:
                # "tpu" — and "ref", whose XLA twin replays the fused
                # schedule, so the TPU models still describe the plan.
                entry.vmem_bytes = plan.fused_round_vmem_bytes(
                    key.n_padded, s, bk, word=word, variant=self.variant,
                    batch=bb or 1, successors=key.successors,
                )
                entry.hbm_bytes_per_round = scale * plan.fused_round_hbm_bytes(
                    key.n_padded, s, word=word, batch=key.batch,
                )

        entry.runner = _solve_runner(
            entry, SolveProgram(key, sr, self.variant, interpret, lowering)
        )
        return entry

    # -------------------------------------------------------------- solving
    def solve(self, w, *, successors: bool = False) -> APSPResult:
        """One graph or one uniform (B, n, n) batch through the cache."""
        with jax.profiler.TraceAnnotation("apsp.solve"):
            arr = _coerce(w, self.semiring, self.dtype)
            batched = arr.ndim == 3
            n = arr.shape[-1]
            B = arr.shape[0] if batched else 1
            entry = self.plan_for(
                n, B, dtype=arr.dtype, successors=successors
            )
            # The numpy rung runs on the host, and the distributed runner
            # places a host array straight into its sharding: neither puts
            # a numpy input whole on one device.
            host = isinstance(arr, np.ndarray) and entry.key.method in (
                "numpy", "distributed"
            )
            wb = arr if host else jnp.asarray(arr)
            if not batched:
                wb = wb[None]
            dist, succ = self._run(entry, wb, n)
            if not batched:
                dist = dist[0]
                succ = succ[0] if succ is not None else None
            if self.validate and _is_min_plus(self.semiring):
                _check_negative_cycles(dist, batched)
            self.stats.solves += 1
            self.stats.graphs_solved += B * self.semiring.lanes
            return self._result(entry, dist, succ, n)

    def solve_many(
        self, graphs: Sequence, *, successors: bool = False
    ) -> list[APSPResult]:
        """Ragged batch: bucket by padded shape, solve each bucket batched.

        graphs: sequence of (n_i, n_i) matrices (sizes may differ) or one
        (B, n, n) array.  Returns per-graph results in input order, bitwise
        equal to per-graph ``solve`` calls — bucketing never changes the
        per-element computation, only how many dispatches carry it.
        """
        with jax.profiler.TraceAnnotation("apsp.solve"):
            if hasattr(graphs, "ndim") and getattr(graphs, "ndim", 0) == 3:
                graphs = list(graphs)
            arrs = [_coerce(g, self.semiring, self.dtype) for g in graphs]
            for a in arrs:
                if a.ndim != 2:
                    raise ValueError(
                        f"solve_many expects (n,n) graphs, got {a.shape}"
                    )
            # ----- bucket by the shape the executable actually sees ------
            buckets: dict[tuple, list[int]] = {}
            metas = []
            for idx, a in enumerate(arrs):
                n = a.shape[-1]
                meth, s, m = self._resolve_shape(n, successors)
                bkey = (meth, m, s, str(jnp.dtype(a.dtype)))
                buckets.setdefault(bkey, []).append(idx)
                metas.append((n, meth, s, m))
            # ----- one batched solve per bucket --------------------------
            results: list[APSPResult | None] = [None] * len(arrs)
            for (meth, m, s, _dt), idxs in buckets.items():
                entry = self.plan_for(
                    arrs[idxs[0]].shape[-1], len(idxs),
                    dtype=arrs[idxs[0]].dtype, successors=successors,
                )
                wb = jnp.stack([
                    _pad(jnp.asarray(arrs[i]), m, self.semiring)
                    for i in idxs
                ])
                dist, succ = self._run(entry, wb, m)
                if self.validate and _is_min_plus(self.semiring):
                    bad = np.asarray(negative_cycle_mask_padded(dist, [
                        metas[i][0] for i in idxs
                    ]))
                    if bad.any():
                        which = [idxs[k] for k in np.flatnonzero(bad)]
                        raise NegativeCycleError(
                            f"negative cycle detected in graphs {which}"
                        )
                for k, i in enumerate(idxs):
                    n_i = metas[i][0]
                    d_i = dist[k, :n_i, :n_i]
                    s_i = succ[k, :n_i, :n_i] if succ is not None else None
                    results[i] = self._result(entry, d_i, s_i, n_i)
            self.stats.solves += len(buckets)
            self.stats.graphs_solved += len(arrs) * self.semiring.lanes
            return results  # type: ignore[return-value]

    def pack_edges(self, src, dst, n: int) -> jax.Array:
        """Edge lists → the packed engine's (G, n, n) int32 word table.

        src, dst: (B, E) int32 edge arrays of B ≤ 32·G graphs (or (E,) for
        one); see ``pack_edges`` for the layout.  The table is made on
        the device by the program ``apsp_pack`` (module ``jit_apsp_pack``)
        inside the host span ``apsp.pack``, ready for ``solve``.
        """
        if not self.semiring.packed:
            raise ValueError(
                f"pack_edges builds or_and_packed words; this engine runs "
                f"{self.semiring.name!r} (construct it with "
                f"semiring='or_and', packed=True)"
            )
        with jax.profiler.TraceAnnotation("apsp.pack"):
            words = apsp_pack(src, dst, n)
            shape = np.shape(src)
            self.stats.packs += 1
            self.stats.graphs_packed += 1 if len(shape) == 1 else shape[0]
            self.stats.edges_packed += int(np.prod(shape))
            return words

    # -------------------------------------------------------------- repair
    def repair(self, dist, updates, *, succ=None) -> APSPResult:
        """Absorb a batch of ⊕-improving edge updates into a closed matrix.

        dist: a (n, n) closure (a prior solve's output); updates: sequence
        of ``(u, v, w)`` where ``w`` is the ⊕-delta merged into edge
        (u, v) — the improved weight itself for the idempotent semirings,
        the additive delta for plus_mul; succ: the matching next-hop table
        to patch alongside (min-plus float only).

        One fused rank-1 dispatch (``kernels.fw_repair``; its bitwise XLA
        twin on CPU; a shard-mapped per-edge sweep on a mesh engine) —
        O(E·n²) against the full solve's O(n³).  The result equals a full
        re-solve of the updated graph exactly under the kernel's documented
        conditions: ⊕-improving updates, closure diagonal = ⊗-identity
        (lifted/restored automatically for plus_mul, whose FW convention
        keeps a 0 diagonal; exact there only on DAGs), no optimal path
        using one updated edge twice.  Edge *removals* / min-plus weight
        increases are structural — re-solve instead
        (``serve.registry`` classifies; ``should_repair`` is the cost
        policy).

        Edge batches pad to a power-of-two bucket with no-op edges
        (u = v = 0, w = ⊕-identity), so the plan cache holds one
        executable per (shape, bucket) rather than one per batch length.
        """
        sr = self.semiring
        arr = _coerce(dist, sr, self.dtype)
        packed_plane = "packed" in sr.name and arr.ndim == 3 and arr.shape[0] == 1
        if packed_plane:
            # A packed closure is (G, n, n) word planes; the rank-1 repair is
            # per-plane (w is then the int32 lane mask of graphs gaining the
            # edge).  Accept the common single-word case directly; multi-word
            # sets repair plane-by-plane at the call site.
            arr = arr[0]
        if arr.ndim != 2 or arr.shape[0] != arr.shape[1]:
            raise ValueError(f"repair expects a (n, n) closure, got {arr.shape}")
        n = arr.shape[-1]
        updates = list(updates)
        if not updates:
            raise ValueError("repair needs at least one (u, v, w) update")
        if succ is not None:
            if not _is_min_plus(sr):
                raise ValueError(
                    "successor repair is min_plus only (like every "
                    "successor path)"
                )
            if jnp.dtype(arr.dtype).kind != "f":
                raise ValueError(
                    "successor repair needs a float distance table "
                    "(the strict-< relaxation is not lowered for int16)"
                )
            if self.method == "distributed":
                raise ValueError(
                    "distributed repair is distance-only (like the "
                    "distributed solve)"
                )
        E = len(updates)
        E_pad = max(4, 1 << (E - 1).bit_length())
        u = np.zeros(E_pad, np.int32)
        v = np.zeros(E_pad, np.int32)
        w = np.full(E_pad, sr.zero, jnp.dtype(arr.dtype).name)
        for i, (ui, vi, wi) in enumerate(updates):
            u[i], v[i], w[i] = ui, vi, wi
        if self.method == "distributed":
            meth, s, m = self._resolve_shape(n, False)
        else:
            s = self.block_size or plan.auto_block_size(n)
            m = plan.padded_size(n, s)
        key = PlanKey(
            n_padded=m, batch=1, dtype=str(jnp.dtype(arr.dtype)),
            semiring=sr.name,
            method="repair_distributed" if self.method == "distributed"
            else "repair",
            block_size=s, bk=0, batch_block=None,
            successors=succ is not None,
            mesh=self._mesh_sig if self.method == "distributed" else None,
            edges=E_pad, backend=self._backend,
        )
        entry = self._cache.get(key)
        if entry is not None:
            self.stats.hits += 1
        else:
            self.stats.misses += 1
            entry = self._build_repair(key)
            self._cache[key] = entry
        dp = _pad(jnp.asarray(arr), m, sr)
        if succ is None:
            out = entry.runner(dp, u, v, w)
            d2, s2 = out[..., :n, :n], None
        else:
            sp = jnp.full((m, m), -1, jnp.int32)
            sp = sp.at[:n, :n].set(jnp.asarray(succ, jnp.int32))
            d2, s2 = entry.runner(dp, sp, u, v, w)
            d2, s2 = d2[..., :n, :n], s2[..., :n, :n]
        if self.validate and _is_min_plus(sr):
            _check_negative_cycles(d2, False)
        self.stats.repairs += 1
        self.stats.edges_repaired += E
        if packed_plane:
            d2 = d2[None]
        return self._result(entry, d2, s2, n)

    def repair_del(
        self, dist, w, deletions, *, succ=None, threshold: float = 0.5,
    ) -> APSPResult:
        """Absorb a batch of edge *deletions/worsenings* into a closed
        matrix — the structural events the rank-1 ``repair`` cannot touch.

        dist: a (n, n) closure (a prior solve's output); w: the **updated**
        weight matrix (deletions already applied — a deleted edge holds the
        ⊕-identity, a worsened one its new weight); deletions: sequence of
        ``(u, v, w_old)`` — endpoints plus the weight the edge carried
        *before* the deletion (for packed or_and, the old int32 word bits);
        succ: the matching next-hop table to repair alongside (min-plus
        float only).

        Two stages (``kernels.fw_repair_del``): mark the affected set —
        pairs whose shortest path is witnessed through a deleted edge, via
        the d[i,u] ⊗ w_old ⊗ d[v,j] == d[i,j] test, O(E·n²) — then
        re-relax only the affected rows with the restricted row sweep,
        O(T·(s + 2a)·n) traffic.  The result equals a full re-solve of w,
        bitwise on integer-valued weights (the kernel's exactness
        contract).  Falls back to ``self.solve(w)`` — counted in
        ``stats.repair_del_fallbacks`` — when the affected fraction fails
        ``plan.should_repair_del(threshold=...)`` or the semiring is
        plus_mul (non-idempotent ⊕ sums over all paths; no restricted
        recomputation is sound).  An *empty* affected set returns the
        closure untouched with no sweep dispatch (``repair_del_noops``;
        cached traces stay flat).

        Mesh engines run the same LOCAL sweep: the affected strip is too
        small to amortize a bordered round's collectives, and the
        distributed solve is bitwise-equal to single-device anyway, so the
        local result matches a mesh re-solve exactly.  Packed or_and
        accepts the (1, n, n) single-word plane like ``repair``; deletions
        are per-edge (the lanes that lost the edge are read from w itself).
        """
        sr = self.semiring
        arr = _coerce(dist, sr, self.dtype)
        wa = _coerce(w, sr, self.dtype)
        packed_plane = "packed" in sr.name and arr.ndim == 3 and arr.shape[0] == 1
        if packed_plane:
            arr, wa = arr[0], wa[0]
        if arr.ndim != 2 or arr.shape[0] != arr.shape[1]:
            raise ValueError(
                f"repair_del expects a (n, n) closure, got {arr.shape}"
            )
        if wa.shape != arr.shape:
            raise ValueError(
                f"weight matrix {wa.shape} does not match closure {arr.shape}"
            )
        n = arr.shape[-1]
        dels = [(int(u), int(v), wi) for (u, v, wi) in deletions]
        if succ is not None:
            if not _is_min_plus(sr):
                raise ValueError(
                    "successor repair_del is min_plus only (like every "
                    "successor path)"
                )
            if jnp.dtype(arr.dtype).kind != "f":
                raise ValueError(
                    "successor repair_del needs a float distance table "
                    "(the strict-< relaxation is not lowered for int16)"
                )
            if self.method == "distributed":
                raise ValueError(
                    "distributed repair_del is distance-only (like the "
                    "distributed solve)"
                )
        E = len(dels)
        if E == 0:
            self.stats.repair_del_noops += 1
            d0 = arr[None] if packed_plane else arr
            s0 = None if succ is None else jnp.asarray(succ, jnp.int32)
            return APSPResult(
                dist=d0, succ=s0, method="repair_del", semiring=sr.name,
                block_size=self.block_size, n=n, padded_n=n,
            )
        if "plus_mul" in sr.name:
            # Non-idempotent ⊕ sums over ALL paths: neither the one-witness
            # marking nor any restricted recomputation is sound — the only
            # correct decremental move is a full re-solve.
            self.stats.edges_deleted += E
            self.stats.repair_del_fallbacks += 1
            return self.solve(w, successors=succ is not None)
        s = self.block_size or plan.auto_block_size(n)
        m = plan.padded_size(n, s)
        E_pad = max(4, 1 << (E - 1).bit_length())
        u = np.zeros(E_pad, np.int32)
        v = np.zeros(E_pad, np.int32)
        # Padding edges carry the ⊕-identity weight: their witness absorbs
        # to 0̄ and can never meet a live closure entry (and the traced
        # live-count mask drops them anyway).
        wold = np.full(E_pad, sr.zero, jnp.dtype(arr.dtype).name)
        for i, (ui, vi, wi) in enumerate(dels):
            u[i], v[i] = ui, vi
            try:
                wold[i] = wi
            except (ValueError, OverflowError):
                # A non-finite old weight in an integer lowering: the edge
                # never existed there — the ⊕-identity witness is inert,
                # exactly right.
                pass
        dtype = str(jnp.dtype(arr.dtype))
        key1 = PlanKey(
            n_padded=m, batch=1, dtype=dtype, semiring=sr.name,
            method="repair_del_mark", block_size=s, bk=0, batch_block=None,
            successors=succ is not None, edges=E_pad, backend=self._backend,
        )
        entry1 = self._cache.get(key1)
        if entry1 is not None:
            self.stats.hits += 1
        else:
            self.stats.misses += 1
            entry1 = self._build_repair_del_mark(key1)
            self._cache[key1] = entry1
        dp = _pad(jnp.asarray(arr), m, sr)
        wp = _pad(jnp.asarray(wa), m, sr)
        s_init = None
        if succ is None:
            d_init, row_mask, _cnt = entry1.runner(
                dp, wp, u, v, wold, np.int32(E)
            )
        else:
            sp = jnp.full((m, m), -1, jnp.int32)
            sp = sp.at[:n, :n].set(jnp.asarray(succ, jnp.int32))
            d_init, s_init, row_mask, _cnt = entry1.runner(
                dp, sp, wp, u, v, wold, np.int32(E)
            )
        rows = np.flatnonzero(np.asarray(row_mask)[:n])
        a = int(rows.size)
        self.stats.edges_deleted += E
        if a == 0:
            # No shortest path was witnessed through any deleted edge: the
            # closure (and succ) is already the updated graph's — return it
            # untouched, no sweep dispatch, cached traces stay flat.
            self.stats.repair_del_noops += 1
            d0 = arr[None] if packed_plane else arr
            s0 = None if succ is None else jnp.asarray(succ, jnp.int32)
            return APSPResult(
                dist=d0, succ=s0, method="repair_del", semiring=sr.name,
                block_size=s, n=n, padded_n=m,
            )
        word = jnp.dtype(arr.dtype).itemsize
        if not plan.should_repair_del(
            n, a, block_size=s, word=word, edges=E,
            successors=succ is not None, threshold=threshold,
        ):
            self.stats.repair_del_fallbacks += 1
            return self.solve(w, successors=succ is not None)
        a_pad = min(max(8, 1 << (a - 1).bit_length()), m)
        rows_arr = np.full(a_pad, m, np.int32)
        rows_arr[:a] = rows
        key2 = PlanKey(
            n_padded=m, batch=1, dtype=dtype, semiring=sr.name,
            method="repair_del", block_size=s,
            bk=min(self.bk, s), batch_block=None,
            successors=succ is not None, edges=a_pad, backend=self._backend,
        )
        entry2 = self._cache.get(key2)
        if entry2 is not None:
            self.stats.hits += 1
        else:
            self.stats.misses += 1
            entry2 = self._build_repair_del_sweep(key2)
            self._cache[key2] = entry2
        if succ is None:
            d2 = entry2.runner(d_init, rows_arr)[:n, :n]
            s2 = None
        else:
            d2, s2 = entry2.runner(d_init, s_init, rows_arr)
            d2, s2 = d2[:n, :n], s2[:n, :n]
        if self.validate and _is_min_plus(sr):
            _check_negative_cycles(d2, False)
        self.stats.repair_dels += 1
        self.stats.repair_del_rows += a
        if packed_plane:
            d2 = d2[None]
        return self._result(entry2, d2, s2, n)

    def should_repair(
        self, n: int, pending_updates: int, *,
        successors: bool = False, dtype=None, threshold: float = 0.5,
        worsenings: int = 0,
    ) -> bool:
        """The staleness/accumulated-delta policy: is a rank-1 repair still
        cheaper than a full fused re-solve for this backlog?

        ``worsenings > 0`` fast-rejects regardless of cost: the rank-1
        repair only absorbs ⊕-*improvements* (its relaxation ⊕-merges the
        new edge into the closure), so a worsened edge — a min-plus weight
        increase, a removal, a failed link — invalidates committed paths no
        ⊕-merge can undo, and the only correct move is a full re-solve.
        Rejects are counted in ``stats.repair_rejects`` so serving metrics
        can tell "repair too expensive" from "repair would be wrong".

        Otherwise compares ``plan.repair_hbm_bytes`` for the accumulated
        edge count against ``threshold ×`` the full solve's modeled
        traffic — past the crossover (≈ threshold · n/s edges) the serving
        layer should fall back to ``solve``, which also resets exactness
        drift from any structural churn.
        """
        if worsenings > 0:
            self.stats.repair_rejects += 1
            return False
        if pending_updates < 1:
            return False
        s = self.block_size or plan.auto_block_size(n)
        word = jnp.dtype(
            dtype if dtype is not None else self.dtype or jnp.float32
        ).itemsize
        cost = plan.repair_hbm_bytes(
            n, s, word=word, edges=pending_updates, successors=successors
        )
        full = plan.fused_solve_hbm_bytes(n, s, word=word) * (
            2 if successors else 1
        )
        return cost <= threshold * full

    def _build_repair(self, key: PlanKey) -> ExecutablePlan:
        """Construct the jitted repair runner for a cache key."""
        sr = self.semiring
        s, E = key.block_size, key.edges
        interpret = self.interpret
        lift = "plus_mul" in key.semiring  # FW keeps a 0 (⊕-id) diagonal
        word = jnp.dtype(key.dtype).itemsize
        entry = ExecutablePlan(key=key, runner=None)
        entry.hbm_bytes_per_round = plan.repair_hbm_bytes(
            key.n_padded, s, word=word, edges=E, successors=key.successors,
        )

        def _set_diag(d, val):
            idx = jnp.arange(d.shape[-1])
            return d.at[..., idx, idx].set(jnp.asarray(val, d.dtype))

        if key.method == "repair_distributed":
            from repro.core.distributed import build_repair_shard_fn

            sharded, sharding = build_repair_shard_fn(
                self.mesh, key.n_padded,
                row_axes=self.row_axes, col_axes=self.col_axes,
                semiring=sr, edges=E,
            )

            def apsp_repair(dp, u, v, w):
                entry.traces += 1
                dg = jnp.diagonal(dp) if lift else None
                if lift:
                    dp = _set_diag(dp, sr.one)
                out = sharded(dp, u, v, w)
                if lift:
                    idx = jnp.arange(out.shape[-1])
                    out = out.at[..., idx, idx].set(dg)
                return out

            jitted = jax.jit(apsp_repair)
            entry.runner = lambda dp, u, v, w: jitted(
                jax.device_put(dp, sharding), u, v, w
            )
            return entry

        from repro.kernels.ops import default_interpret

        use_ref = interpret is None and default_interpret()
        if key.successors:
            if use_ref:
                from repro.kernels.ref import fw_repair_with_successors_ref

                fn = lambda d, sc, u, v, w: fw_repair_with_successors_ref(
                    d, sc, u, v, w
                )
            else:
                from repro.kernels.fw_repair import fw_repair_with_successors

                fn = lambda d, sc, u, v, w: fw_repair_with_successors(
                    d, sc, u, v, w, block_size=s, interpret=interpret
                )

            def apsp_repair(dp, sp, u, v, w):
                entry.traces += 1
                return fn(dp, sp, u, v, w)

            entry.runner = jax.jit(apsp_repair)
            return entry

        if use_ref:
            from repro.kernels.ref import fw_repair_ref

            fn = lambda d, u, v, w: fw_repair_ref(d, u, v, w, semiring=sr)
        else:
            from repro.kernels.fw_repair import fw_repair

            fn = lambda d, u, v, w: fw_repair(
                d, u, v, w, block_size=s, semiring=sr, interpret=interpret
            )

        def apsp_repair(dp, u, v, w):
            entry.traces += 1
            dg = jnp.diagonal(dp) if lift else None
            if lift:
                dp = _set_diag(dp, sr.one)
            out = fn(dp, u, v, w)
            if lift:
                idx = jnp.arange(out.shape[-1])
                out = out.at[..., idx, idx].set(dg)
            return out

        entry.runner = jax.jit(apsp_repair)
        return entry

    def _build_repair_del_mark(self, key: PlanKey) -> ExecutablePlan:
        """Stage-1 runner: padded (closure[, succ], weights, edge batch,
        live count) → (d_init[, s_init], affected-row mask, entry count).
        Pure XLA on every backend — the witness test is E outer-product
        compares, bandwidth-bound with nothing for a kernel to fuse."""
        sr = self.semiring
        entry = ExecutablePlan(key=key, runner=None)
        from repro.kernels.fw_repair_del import (
            mark_affected,
            mark_affected_with_successors,
        )

        if key.successors:

            def apsp_repair_del_mark(dp, sp, wp, u, v, wold, ecount):
                entry.traces += 1
                return mark_affected_with_successors(
                    dp, sp, wp, u, v, wold, ecount, semiring=sr
                )

            entry.runner = jax.jit(apsp_repair_del_mark)
            return entry

        def apsp_repair_del_mark(dp, wp, u, v, wold, ecount):
            entry.traces += 1
            return mark_affected(dp, wp, u, v, wold, ecount, semiring=sr)

        entry.runner = jax.jit(apsp_repair_del_mark)
        return entry

    def _build_repair_del_sweep(self, key: PlanKey) -> ExecutablePlan:
        """Stage-2 runner: (d_init[, s_init], padded affected rows) → the
        repaired closure.  key.edges carries the power-of-two affected-row
        bucket a_pad (the strip height), the same bucketing trick the
        rank-1 repair uses for its edge batches.  plus_mul never reaches
        here (repair_del falls back to solve), so no diagonal lift."""
        sr = self.semiring
        s = key.block_size
        interpret = self.interpret
        word = jnp.dtype(key.dtype).itemsize
        entry = ExecutablePlan(key=key, runner=None)
        entry.hbm_bytes_per_round = plan.repair_del_hbm_bytes(
            key.n_padded, s, affected_rows=key.edges, word=word,
            successors=key.successors,
        )
        if key.successors:
            # Successor sweeps run the XLA twin on every backend — next-hop
            # tables are a host-walked serving structure (see the kernel
            # module docstring); a Pallas variant is open headroom.
            from repro.kernels.fw_repair_del import (
                fw_repair_del_sweep_with_successors_ref,
            )

            def apsp_repair_del_sweep(d_init, s_init, rows):
                entry.traces += 1
                return fw_repair_del_sweep_with_successors_ref(
                    d_init, s_init, rows, block_size=s
                )

            entry.runner = jax.jit(apsp_repair_del_sweep)
            return entry

        from repro.kernels.ops import default_interpret

        use_ref = interpret is None and default_interpret()
        if use_ref:
            from repro.kernels.fw_repair_del import fw_repair_del_sweep_ref

            fn = lambda d, r: fw_repair_del_sweep_ref(
                d, r, block_size=s, bk=key.bk, variant=self.variant,
                semiring=sr,
            )
        else:
            from repro.kernels.fw_repair_del import fw_repair_del_sweep

            fn = lambda d, r: fw_repair_del_sweep(
                d, r, block_size=s, bk=key.bk, variant=self.variant,
                semiring=sr, interpret=interpret,
            )

        def apsp_repair_del_sweep(d_init, rows):
            entry.traces += 1
            return fn(d_init, rows)

        entry.runner = jax.jit(apsp_repair_del_sweep)
        return entry

    # -------------------------------------------------------------- helpers
    def _run(self, entry: ExecutablePlan, wb, n: int):
        """Pad to the plan shape, run the cached executable, unpad."""
        m = entry.key.n_padded
        wp = _pad(wb, m, self.semiring)
        out = entry.runner(wp)
        if entry.key.successors:
            dist, succ = out
            return dist[..., :n, :n], succ[..., :n, :n]
        return out[..., :n, :n], None

    def _result(self, entry: ExecutablePlan, dist, succ, n: int) -> APSPResult:
        key = entry.key
        return APSPResult(
            dist=dist, succ=succ, method=key.method, semiring=key.semiring,
            block_size=key.block_size, n=n, padded_n=key.n_padded,
            backend=key.backend if key.method in ("staged", "fused") else None,
        )


@dataclasses.dataclass(frozen=True)
class SolveProgram:
    """What the single-device solve runner of one plan key computes.

    It is a static argument of ``apsp_solve``, so engines that plan equal
    programs — a stateless ``api.solve`` call builds a fresh engine each
    time — share one trace and one compile through JAX's own cache.
    """

    key: PlanKey
    semiring: Semiring
    variant: str
    interpret: bool | None
    lowering: str  # core.staged.LOWERINGS, from key.backend

    def __call__(self, wp):
        key, sr, s = self.key, self.semiring, self.key.block_size
        if key.method == "naive":
            if key.successors:
                return jax.vmap(fw_with_successors)(wp)
            # fw_naive/fw_blocked batch natively over the leading dim.
            return fw_naive(wp, semiring=sr)
        if key.method == "blocked":
            if key.successors:
                return jax.vmap(
                    lambda x: fw_blocked_with_successors(x, block_size=s)
                )(wp)
            return fw_blocked(wp, block_size=s, semiring=sr)
        # staged / fused — the kernels' native batch grid
        if key.successors:
            return fw_staged_with_successors(
                wp, block_size=s, batch_block=key.batch_block,
                interpret=self.interpret, lowering=self.lowering,
            )
        return fw_staged(
            wp, block_size=s, bk=key.bk, batch_block=key.batch_block,
            variant=self.variant, semiring=sr, interpret=self.interpret,
            lowering=self.lowering,
        )


# Traces of each solve program in this process (``ExecutablePlan.traces``).
_SOLVE_TRACES: collections.Counter = collections.Counter()


@functools.partial(jax.jit, static_argnames="program")
def apsp_solve(wp, *, program: SolveProgram):
    """The single-device solve as one program, named for the device trace
    (module ``jit_apsp_solve``)."""
    _SOLVE_TRACES[program] += 1  # runs only while JAX traces
    return program(wp)


def _solve_runner(entry: ExecutablePlan, program: SolveProgram):
    """``entry``'s runner: ``apsp_solve`` of ``program``, with the
    program's trace count kept on the entry, and ``lower`` for
    ahead-of-time compiles."""

    def runner(wp):
        out = apsp_solve(wp, program=program)
        entry.traces = _SOLVE_TRACES[program]
        return out

    runner.lower = functools.partial(apsp_solve.lower, program=program)
    return runner


@functools.partial(jax.jit, static_argnames="n")
def apsp_pack(src, dst, n: int) -> jax.Array:
    """``pack_edges`` as one program, named for the device trace."""
    return pack_edges(src, dst, n)


def negative_cycle_mask_padded(dist, ns: Sequence[int]) -> np.ndarray:
    """Per-graph negative-cycle mask honoring each graph's true size.

    dist: (B, m, m) padded closures; ns: true vertex counts.  Padding
    vertices have a 0 (⊗-identity) diagonal, so restricting the check to
    the real diagonal is equivalent but keeps intent explicit.
    """
    d = np.asarray(jnp.diagonal(jnp.asarray(dist), axis1=-2, axis2=-1))
    return np.stack([bool((d[k, : ns[k]] < 0).any()) for k in range(len(ns))])
