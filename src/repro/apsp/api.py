"""Unified APSP front-end: ``solve`` owns padding, dispatch, and batching.

Every caller used to hand-roll the same steps: pad n to a tile multiple,
pick a method and block size, run, unpad, verify.  ``solve`` owns all of it:

  * **pad/unpad** — arbitrary n; padding vertices are ⊕-identity rows/cols
    with ⊗-identity diagonal, so they are unreachable under any semiring and
    the top-left n×n of the padded closure equals the closure of the input.
  * **dispatch** — ``method="auto"`` picks a sensible rung of the paper's
    implementation ladder for the input size and backend; explicit names
    ("numpy" | "naive" | "blocked" | "staged" | "fused" | "recursive" |
    "distributed") pin one ("fused" = staged with the single-dispatch fused
    round kernel; "recursive" = the R-Kleene panel schedule of
    ``apsp.kleene``, auto-selected whenever an ``hbm_budget`` is given and
    the padded matrix would not fit it).
  * **batching** — a (B, n, n) input runs all B graphs through the kernels'
    *native* batch grid (staged/fused: one dispatch per round for the whole
    batch; blocked/naive: one vmap-ed computation); results match per-graph
    solves bit-for-bit.
  * **successors** — ``successors=True`` tracks next-hop matrices natively
    through the fused round kernel (``fw_staged_with_successors``) or the
    blocked/naive paths; no more fused→blocked fallback.
  * **validation** — min-plus solves raise ``NegativeCycleError`` when the
    result certifies a negative cycle (a strictly negative diagonal entry).

``solve`` is stateless: every call re-plans and re-pads.  For repeated or
ragged-batch workloads use ``repro.apsp.engine.ApspEngine``, which caches
the plan/executable per (n_padded, B, dtype, semiring, method, block dims)
key and buckets ragged graph sets into padded batches.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.apsp import plan
from repro.apsp.kleene import fw_kleene
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
    backend: the lowering that ran the staged/fused round ("tpu" | "gpu" |
          "ref"); None for the other methods.
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


def pack_reachability(w) -> jax.Array:
    """Pack (B, n, n) or (n, n) boolean graphs into int32 bit planes.

    Graph ``g`` lands in word ``g // 32``, bit ``g % 32`` (LSB-first):
    ``out[g // 32, i, j] >> (g % 32) & 1`` is "edge i→j exists in graph g".
    Any nonzero entry counts as an edge.  B is padded up to a multiple of 32
    with empty graphs; output shape is (ceil(B/32), n, n) int32, ready for
    ``solve(..., semiring="or_and_packed")``.
    """
    arr = jnp.asarray(w)
    if arr.ndim == 2:
        arr = arr[None]
    if arr.ndim != 3 or arr.shape[-1] != arr.shape[-2]:
        raise ValueError(f"w must be (n,n) or (B,n,n), got {arr.shape}")
    B, n, _ = arr.shape
    G = -(-B // PACK_LANES)

    # One graph at a time, so nothing larger than the words is made.
    def fold(g, words):
        bit = (arr[g] != 0).astype(jnp.uint32) << (g % PACK_LANES)
        return words.at[g // PACK_LANES].set(words[g // PACK_LANES] | bit)

    words = jax.lax.fori_loop(0, B, fold, jnp.zeros((G, n, n), jnp.uint32))
    return jax.lax.bitcast_convert_type(words, jnp.int32)


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


def unpack_reachability(p, count: int | None = None, *, dtype=jnp.float32):
    """Inverse of ``pack_reachability``: (G, n, n) int32 words → (count, n, n)
    0/1 matrices of ``dtype`` (count defaults to all G·32 bit lanes)."""
    arr = jnp.asarray(p)
    if arr.ndim == 2:
        arr = arr[None]
    if arr.ndim != 3 or arr.shape[-1] != arr.shape[-2]:
        raise ValueError(f"p must be (n,n) or (G,n,n), got {arr.shape}")
    G, n, _ = arr.shape
    words = jax.lax.bitcast_convert_type(arr, jnp.uint32)
    shifts = jnp.arange(PACK_LANES, dtype=jnp.uint32)[None, :, None, None]
    bits = (words[:, None, :, :] >> shifts) & jnp.uint32(1)
    out = bits.reshape(G * PACK_LANES, n, n).astype(dtype)
    return out if count is None else out[:count]


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

    Shared by the stateless ``solve`` and the engine's plan/bucket keys so
    the two can never pad or dispatch differently for the same input.  For
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
    """np/jnp coercion + storage-dtype encoding shared by solve and the engine.

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


def solve(
    w,
    *,
    method: str = "auto",
    semiring: Semiring | str = MIN_PLUS,
    dtype=None,
    packed: bool = False,
    successors: bool = False,
    block_size: int | None = None,
    validate: bool = True,
    mesh=None,
    row_axes="data",
    col_axes="model",
    variant: str = "fori",
    backend: str = "auto",
    interpret: bool | None = None,
    leaf: int | None = None,
    hbm_budget: int | None = None,
    devices=None,
) -> APSPResult:
    """All-pairs shortest paths (semiring closure) of one or many graphs.

    w: (n, n) adjacency matrix, or (B, n, n) for a batch of graphs; missing
       edges are the semiring ⊕-identity (+inf for min-plus).  Any float
       dtype the kernels support (float32/bfloat16 are the tested pair);
       any n — the solver pads to the tile multiple and unpads the result.
       Integer matrices are promoted to float32 when the semiring
       identities are non-finite (min-plus & friends) — ints cannot encode
       +inf.
    method: "auto" | "numpy" | "naive" | "blocked" | "staged" | "fused" |
       "distributed".  "fused" pins the one-pallas_call-per-round kernel
       ("staged" defaults to it too and falls back per fw_staged);
       "distributed" shards W over a device mesh and runs the fused
       *bordered* round per device (``core.distributed``), auto-padding n
       to the mesh multiple via ``plan.distributed_plan`` — batched
       (B, n, n) input shards the trailing dims and is bitwise equal to B
       single-device fused solves.
    semiring: a ``core.semiring.Semiring`` or its name — "min_plus"
       (shortest paths), "max_plus" (critical paths), "or_and" (transitive
       closure on {0,1}), "max_min" (bottleneck paths), "plus_mul"
       (ordinary algebra).  ⊕-identity encodes "no edge", ⊗-identity the
       diagonal.  Storage lowerings resolve by name too ("or_and_packed"
       for pre-packed int32 bit planes, "min_plus_i16" & friends).
    dtype: storage dtype for the solve — the bandwidth axis.  None keeps
       the input dtype.  Float dtypes (bfloat16/float32/float64) are a
       plain cast: half the HBM bytes for bf16 at 8 mantissa bits of
       precision (distances round to ~3 significant decimal digits; exact
       for small-int weights with sums below 256).  int16 lowers tropical
       semirings to *saturating* arithmetic (``core.semiring``): weights
       clip into [-32768, 32767], +inf ↦ 32767, and relaxation saturates
       at the sentinels instead of wrapping.  plus_mul has no int16
       lowering.
    packed: bit-packed transitive closure (or_and only).  The input is
       (B, n, n) — or (n, n) for B=1 — boolean graphs (any dtype, nonzero
       = edge); solve packs 32 graphs per int32 lane
       (``pack_reachability``), runs ONE closure over the packed words
       with bitwise OR/AND (~32× fewer HBM bytes per graph than unpacked
       f32), and unpacks back to the input's shape and dtype.
    successors: also return next-hop matrices (min-plus only; native in the
       fused/staged round kernel as well as the blocked/naive paths).
       succ[..., i, j] = first hop of the shortest i→j path, -1 = no path
       (int32).
    block_size: pivot-tile size for blocked/staged/distributed (None = auto).
    validate: raise ``NegativeCycleError`` on a negative diagonal (min-plus
       only; forces a host sync).
    mesh/row_axes/col_axes: device mesh for method="distributed".
    variant/interpret: staged-kernel lowering knobs (passed through).
    backend: which Pallas lowering runs the staged/fused round — "auto"
       (default: resolve from ``jax.default_backend()`` — TPU Pallas on
       TPU, the Triton round on GPU, the bitwise XLA ref twin elsewhere),
       or pin "tpu" | "gpu" | "ref" explicitly.  All three produce bitwise
       identical closures; pinning "gpu" (or "tpu") off-hardware runs that
       lowering under the Pallas interpreter.  Threaded through
       ``ApspEngine``'s plan key and ``plan.fw_candidates(backend=)``.
    leaf: pivot-panel width for method="recursive" (multiple of block_size;
       None = ``plan.recursive_plan``'s pick — budget-fattest power of two
       when out of core, 4·block_size in core).
    hbm_budget: device-memory budget in bytes.  When the padded matrix
       (batch · m² · word) exceeds it, any in-core tiled method — including
       "auto" — is promoted to "recursive" and the solve streams panels
       from a host-side backing store (``apsp.kleene.HostPanelStore``),
       keeping only the pivot cross + factors resident.  Bitwise equal to
       the in-core fused solve on every semiring lowering.
    devices: optional device list round-robining recursive sweep tiles.

    Returns an ``APSPResult``: ``dist`` (same leading shape/dtype as the
    input, unpadded), ``succ`` (int32 or None), plus the resolved method /
    semiring / block_size / padded size for introspection.
    """
    sr = _resolve_semiring(semiring)
    if packed:
        # Pack → closure over int32 bit planes → unpack.  The inner solve is
        # an ordinary or_and_packed solve; each bit lane is an independent
        # graph, so the unpacked planes are bitwise equal to B unpacked
        # solves (tests/test_fw_round.py guards 1..32).
        if successors:
            raise ValueError(
                "successors=True requires min_plus; packed=True is the "
                "or_and transitive-closure lowering"
            )
        sr = lower_semiring(sr, dtype, packed=True)
        arr = jnp.asarray(w)
        in_batched = arr.ndim == 3
        count = arr.shape[0] if in_batched else 1
        words = pack_reachability(arr)
        if words.shape[0] == 1:
            words = words[0]  # keep the single-word case on the 2-D path
        inner = solve(
            words, method=method, semiring=sr, block_size=block_size,
            validate=False, mesh=mesh, row_axes=row_axes, col_axes=col_axes,
            variant=variant, backend=backend, interpret=interpret,
        )
        dist = unpack_reachability(inner.dist, count=count, dtype=arr.dtype)
        if not in_batched:
            dist = dist[0]
        return dataclasses.replace(inner, dist=dist, n=arr.shape[-1])
    sr = lower_semiring(sr, dtype)
    arr = _coerce(w, sr, dtype)
    batched = arr.ndim == 3
    n = arr.shape[-1]
    meth, s, m = _resolve_shape(
        method, n, successors, block_size,
        mesh=mesh, row_axes=row_axes, col_axes=col_axes,
        hbm_budget=hbm_budget, batch=arr.shape[0] if batched else 1,
        word=np.dtype(arr.dtype).itemsize,
    )

    if successors:
        _check_successor_args(meth, sr)
    # Validate eagerly even on paths (blocked/numpy/...) that never reach
    # the staged round, so a typo'd backend= fails loudly.
    compat.resolve_pallas_backend(backend)
    if meth == "distributed" and mesh is None:
        raise ValueError("method='distributed' requires a mesh")
    if meth == "numpy" and sr is not MIN_PLUS:
        raise ValueError("method='numpy' implements min_plus only")

    # --- run ------------------------------------------------------------
    succ = None
    be = None
    if meth == "numpy":
        dist = (
            np.stack([fw_numpy(g) for g in arr]) if batched else fw_numpy(arr)
        )
    elif meth == "naive":
        wj = jnp.asarray(arr)
        if successors:
            run = fw_with_successors
            dist, succ = jax.vmap(run)(wj) if batched else run(wj)
        else:
            # Batch-rank-agnostic: the (B, n, n) case runs the same fori
            # loop with a leading batch dim — no vmap wrapper.
            dist = fw_naive(wj, semiring=sr)
    else:
        if meth == "distributed" and isinstance(arr, np.ndarray):
            wp = _pad(arr, m, sr)  # host-side: fw_distributed shards it
        else:
            wp = _pad(jnp.asarray(arr), m, sr)
        if meth == "blocked":
            if successors:
                run = lambda x: fw_blocked_with_successors(x, block_size=s)
                out = jax.vmap(run)(wp) if batched else run(wp)
                dist, succ = out
            else:
                # Natively batched: fw_blocked slices the (B, m, m) array
                # directly (leading batch dim), one round loop for all B.
                dist = fw_blocked(wp, block_size=s, semiring=sr)
        elif meth in ("staged", "fused"):
            # Natively batched: a (B, m, m) input threads the kernels'
            # leading batch grid dimension — one dispatch per round for the
            # whole batch, not a vmap that replays rounds per graph.  The
            # resolved backend picks the round lowering: TPU Pallas, the
            # Triton round, or the bitwise XLA ref twin (what auto lands on
            # for CPU, where the Pallas interpreter's grid emulation would
            # dominate wall-clock) — same op chains either way.
            be = _resolve_backend(backend, interpret)
            if successors:
                dist, succ = fw_staged_with_successors(
                    wp, block_size=s, interpret=interpret,
                    lowering={"tpu": "pallas", "gpu": "gpu", "ref": "ref"}[be],
                )
            else:
                # "staged" leaves the round lowering to fw_staged (fused by
                # default); "fused" pins the single-dispatch round kernel.
                dist = fw_staged(
                    wp, block_size=s, semiring=sr, variant=variant,
                    interpret=interpret,
                    fused={"ref": "ref", "gpu": "gpu"}.get(
                        be, True if meth == "fused" else None
                    ),
                )
        elif meth == "recursive":
            # R-Kleene panel schedule: plan picks the leaf and decides
            # in-core (device store) vs out-of-core (host store + streamed
            # panels); either way the schedule replays the fused round's
            # op chains exactly, so the closure is bitwise-equal to
            # method="fused" at the same block size.
            rp = plan.recursive_plan(
                n, leaf=leaf, hbm_budget=hbm_budget, block_size=s,
                batch=arr.shape[0] if batched else 1, dtype=wp.dtype,
                variant=variant,
            )
            dist = fw_kleene(
                wp, semiring=sr, block_size=s, leaf=rp["leaf"],
                variant=variant, out_of_core=rp["out_of_core"],
                interpret=interpret, devices=devices,
            )
        else:  # distributed — the fused bordered round, one dispatch/device
            from repro.core.distributed import fw_distributed

            # The result stays sharded over the mesh.
            dist = fw_distributed(
                wp, mesh, block_size=s, row_axes=row_axes, col_axes=col_axes,
                semiring=sr, variant=variant, interpret=interpret,
                fused_lowering="auto" if interpret is None else "pallas",
            )
        dist = dist[..., :n, :n]
        if succ is not None:
            succ = succ[..., :n, :n]

    if validate and _is_min_plus(sr):
        _check_negative_cycles(dist, batched)

    return APSPResult(
        dist=dist, succ=succ, method=meth, semiring=sr.name,
        block_size=s, n=n, padded_n=m, backend=be,
    )
