"""Unified APSP front-end: ``solve`` is one call over the engine.

Every caller used to hand-roll the same steps: pad n to a tile multiple,
pick a method and block size, run, unpad, verify.  ``solve`` does all of it
in one call by building an ``engine.ApspEngine`` from its options and
running ``ApspEngine.solve`` once, so a stateless solve runs exactly the
program an engine runs:

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
    blocked/naive paths.
  * **validation** — min-plus solves raise ``NegativeCycleError`` when the
    result certifies a negative cycle (a strictly negative diagonal entry).
  * **packing** — ``packed=True`` wraps the solve in pack → closure of the
    int32 bit planes → unpack (``pack_reachability``/``unpack_reachability``).

``solve`` keeps no state: every call builds a fresh engine.  For repeated
or ragged-batch workloads keep one ``ApspEngine``, which caches the
plan/executable per key and buckets ragged graph sets into padded batches.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.apsp.engine import (  # noqa: F401  (re-exported public names)
    METHODS,
    SUCCESSOR_METHODS,
    APSPResult,
    ApspEngine,
    NegativeCycleError,
    negative_cycle_mask,
    pack_edges,
)
from repro.core.semiring import MIN_PLUS, PACK_LANES, Semiring


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
       "distributed".  "fused" and "staged" both run the
       one-pallas_call-per-round kernel (or its backend's twin);
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
    if packed and successors:
        raise ValueError(
            "successors=True requires min_plus; packed=True is the "
            "or_and transitive-closure lowering"
        )
    engine = ApspEngine(
        method=method, semiring=semiring, dtype=dtype, packed=packed,
        block_size=block_size, variant=variant, validate=validate,
        backend=backend, interpret=interpret, mesh=mesh, row_axes=row_axes,
        col_axes=col_axes, leaf=leaf, hbm_budget=hbm_budget, devices=devices,
    )
    if not packed:
        return engine.solve(w, successors=successors)
    # Pack → closure over int32 bit planes → unpack.  The engine runs an
    # ordinary or_and_packed solve; each bit lane is an independent graph,
    # so the unpacked planes are bitwise equal to B unpacked solves
    # (tests/test_fw_round.py guards 1..32).
    arr = jnp.asarray(w)
    in_batched = arr.ndim == 3
    count = arr.shape[0] if in_batched else 1
    words = pack_reachability(arr)
    if words.shape[0] == 1:
        words = words[0]  # keep the single-word case on the 2-D path
    inner = engine.solve(words)
    dist = unpack_reachability(inner.dist, count=count, dtype=arr.dtype)
    if not in_batched:
        dist = dist[0]
    return dataclasses.replace(inner, dist=dist, n=arr.shape[-1])
