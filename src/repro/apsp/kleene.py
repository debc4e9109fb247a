"""Recursive (R-Kleene) Floyd-Warshall: stream panels past HBM.

Everything else in the stack assumes the padded distance matrix is resident
on-device as one array, so the largest solvable graph is capped by HBM even
though the fused round (kernels/fw_round.py) is bandwidth-optimal within
that limit.  This module removes the cap: the solve is decomposed into a
binary R-Kleene recursion over pivot-round ranges (``plan.kleene_ranges``)
whose leaves hold a *pivot cross* — the (m, P) column band and (P, m) row
band of one P-wide run of pivot rounds — on device while every tile outside
the cross lives in a host-side backing store and streams through exactly
once per leaf.

**Why not the textbook R-Kleene product schedule.**  The classical
formulation (``A11 ← FW(A11); A12 ← A11⊗A12; …; A22 ⊕= A21⊗A12``) multiplies
by *final* sub-closures.  Blocked FW's phase 3 instead consumes each round's
phase-2-closed band state — a value later rounds keep improving — so the
product schedule evaluates a different ⊕-chain per element: harmless for the
idempotent lattices, visibly different for plus_mul (non-idempotent ⊕) and
for last-ulp float ties.  This repo's contract is *bitwise* equality across
every lowering (tests/test_fw_round.py), so the leaves here replay the exact
fused-round dataflow instead:

  * Per round r inside a leaf, the kernel-identical phase 1/2 recurrences
    close the pivot tile and bands (same ``fori_loop`` op chains as
    ``kernels.ref.fw_round_ref``), and the *factor snapshot* — the closed
    (s, m) row band and (m, s) column band, i.e. exactly the operands the
    fused kernel's phase 3 reads from scratch — is appended to the leaf's
    factor panels.
  * Phase 3 applies immediately to the resident cross only (the same
    ``_stage_compute`` bk-chunk sequence, restricted to the cross rows and
    columns).
  * After the leaf's R rounds, every outside tile receives ALL R deferred
    phase-3 updates in ONE factor matmul: ``tile ⊕= colf ⊗ rowf`` over the
    concatenated (m, P)/(P, m) factors, chunked by the same bk.  Because the
    fori/unroll variants are a left fold over ascending k, one P-deep
    contraction is per-element identical to R sequential s-deep phase-3
    applications in round order — for every semiring, by construction, not
    just the idempotent ones.  (The "broadcast" variant ⊕-reduces per chunk;
    bk divides s, so chunk boundaries coincide with the fused round's and
    the chains still match.)

The (P, P) diagonal overlap is materialized in both resident bands; each
round applies the identical splice/relaxation ops to both copies, so they
cannot diverge and the write-back order is immaterial.

**Out-of-core layer.**  ``HostPanelStore`` keeps the matrix in host (NumPy)
memory and counts every h2d/d2h byte — the measured side of the
``plan.recursive_transfer_bytes`` model (the 15% acceptance check in
launch/fw_oocore.py).  The sweep is double-buffered: tile i+1's host→device
transfer is issued before tile i's matmul is dispatched, and tile i−1's
write-back (the only host sync) lands while both are in flight.
``DevicePanelStore`` is the in-core twin (zero transfer) used when the plan
says the matrix fits — and by CI, where the whole schedule runs on CPU via
the XLA ref twins.  A ``devices=`` list round-robins sweep tiles across
local devices (factors replicate once per leaf), composing with the mesh
path: a distributed shard bigger than one device's budget can recurse
locally through the same executor.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.apsp.plan import kleene_ranges
from repro.core.semiring import MIN_PLUS, Semiring
from repro.kernels.minplus_matmul import (
    _fit_block,
    _stage_compute,
    semiring_matmul,
)
from repro.kernels.ops import default_interpret
from repro.kernels.ref import _dyn_slice, _dyn_update


# ---------------------------------------------------------------- stores
class PanelStore:
    """Backing store for a padded (…, m, m) matrix, addressed by 2-D panel.

    ``get``/``put`` move rectangular (h, w) panels of the trailing two dims
    (leading batch dims ride along whole).  Byte counters are the measured
    side of the transfer model; the in-core store keeps them at zero.
    """

    h2d_bytes: int = 0
    d2h_bytes: int = 0
    gets: int = 0
    puts: int = 0

    @property
    def shape(self) -> tuple[int, ...]:
        raise NotImplementedError

    @property
    def dtype(self):
        raise NotImplementedError

    def get(self, r0: int, c0: int, h: int, w: int, device=None) -> jax.Array:
        raise NotImplementedError

    def put(self, r0: int, c0: int, arr) -> None:
        raise NotImplementedError

    def result(self):
        """The full closed matrix (host or device resident)."""
        raise NotImplementedError

    def _panel_bytes(self, h: int, w: int) -> int:
        lead = int(np.prod(self.shape[:-2], dtype=np.int64)) if len(
            self.shape
        ) > 2 else 1
        return lead * h * w * np.dtype(self.dtype).itemsize


class DevicePanelStore(PanelStore):
    """In-core store: the matrix stays one device array, panels are slices.

    Functional updates (``dynamic_update_slice``) keep the executor's store
    protocol identical to the streaming path; transfer counters stay zero —
    this is what ``solve(method="recursive")`` uses when the plan says the
    matrix fits the budget (and what CI runs on CPU).
    """

    def __init__(self, w):
        self.h2d_bytes = self.d2h_bytes = self.gets = self.puts = 0
        self._w = jnp.asarray(w)

    @property
    def shape(self):
        return self._w.shape

    @property
    def dtype(self):
        return self._w.dtype

    def get(self, r0, c0, h, w, device=None):
        self.gets += 1
        return self._w[..., r0:r0 + h, c0:c0 + w]

    def put(self, r0, c0, arr):
        self.puts += 1
        self._w = _dyn_update(self._w, jnp.asarray(arr, self._w.dtype), r0, c0)

    def result(self):
        return self._w


class HostPanelStore(PanelStore):
    """Out-of-core store: host (NumPy) truth, panels DMA'd on demand.

    ``get`` copies the host slice and hands it to ``jax.device_put`` — an
    async dispatch, so a prefetch issued one tile ahead overlaps the
    current tile's compute (the double buffer in ``KleeneExecutor.run``).
    ``put`` materializes the device result back into the backing array and
    is the only host sync.  Counters tally exact panel bytes each way; on
    a CPU container the "transfer" is a memcpy, but the byte accounting is
    identical to what a PCIe-attached device would move, which is what the
    model check measures.
    """

    def __init__(self, w):
        self.h2d_bytes = self.d2h_bytes = self.gets = self.puts = 0
        arr = np.array(w)  # own, writable copy — the solve mutates it
        if arr.ndim < 2 or arr.shape[-1] != arr.shape[-2]:
            raise ValueError(f"store needs (…, m, m), got {arr.shape}")
        self._w = arr

    @property
    def shape(self):
        return self._w.shape

    @property
    def dtype(self):
        return self._w.dtype

    def get(self, r0, c0, h, w, device=None):
        self.gets += 1
        self.h2d_bytes += self._panel_bytes(h, w)
        panel = np.ascontiguousarray(self._w[..., r0:r0 + h, c0:c0 + w])
        return jax.device_put(panel, device)

    def put(self, r0, c0, arr):
        self.puts += 1
        self.d2h_bytes += self._panel_bytes(arr.shape[-2], arr.shape[-1])
        self._w[..., r0:r0 + arr.shape[-2], c0:c0 + arr.shape[-1]] = (
            np.asarray(arr)
        )

    def result(self):
        return self._w


# -------------------------------------------------------------- executor
class KleeneExecutor:
    """The recursive schedule, compiled once per shape family.

    Two jit units:

      * ``leaf`` — closes one P-wide pivot cross (R kernel-identical fused
        rounds restricted to the resident bands) and returns the
        concatenated factor panels.  The panel's round offset is a traced
        scalar, so every full-width leaf of a solve — and of every later
        solve at the same shapes — shares one trace.
      * ``sweep`` — applies one leaf's deferred phase-3 factor product to
        one outside tile (traced row/col offsets slice the factors).  On
        TPU this dispatches ``kernels.minplus_matmul.semiring_matmul`` (the
        paper-derived staged Pallas kernel); elsewhere the execution-grade
        XLA ``_stage_compute`` chunk loop — identical per-element chains
        either way.

    ``traces`` counts actual retraces (the engine's warm-cache guarantee);
    ``leaf_calls``/``sweep_calls`` count dispatches (the plan's steps
    model).
    """

    def __init__(
        self,
        *,
        semiring: Semiring = MIN_PLUS,
        block_size: int,
        leaf: int,
        bk: int = 32,
        variant: str = "fori",
        interpret: bool | None = None,
        devices: Sequence | None = None,
        on_trace: Callable[[], None] | None = None,
    ):
        if leaf % block_size:
            raise ValueError(
                f"leaf ({leaf}) must be a multiple of block_size "
                f"({block_size}) — leaves replay whole fused pivot rounds"
            )
        self.semiring = semiring
        self.s = block_size
        self.leaf = leaf
        self.bk = _fit_block(block_size, bk)
        self.variant = variant
        self.devices = list(devices) if devices else None
        self.on_trace = on_trace
        # Same lowering policy as solve/engine: Pallas natively on TPU, the
        # bitwise XLA chunk loop everywhere else (never the interpreter).
        self._pallas_sweep = not (
            default_interpret() if interpret is None else interpret
        )
        self.traces = 0
        self.leaf_calls = 0
        self.sweep_calls = 0
        self.depth = 0
        self._leaf = jax.jit(self._leaf_impl, static_argnames=("R",))
        self._sweep = jax.jit(self._sweep_impl)

    # ---- jitted bodies ---------------------------------------------------
    def _traced(self):
        self.traces += 1
        if self.on_trace is not None:
            self.on_trace()

    def _leaf_impl(self, colband, rowband, lo, *, R):
        """Close one pivot cross: R fused rounds on the resident bands.

        colband (…, m, P), rowband (…, P, m), lo = first pivot-round index
        (traced).  Per round, phases 1/2 are the op-for-op recurrences of
        ``kernels.ref.fw_round_ref``; phase 3 applies to the cross only,
        with the closed bands spliced in first (the kernel's scratch read).
        Returns the updated bands plus the concatenated factor panels —
        the per-round phase-3 operands the outside sweep replays.
        """
        self._traced()
        sr, s, bk, variant = self.semiring, self.s, self.bk, self.variant
        m = rowband.shape[-1]
        P = R * s
        LO = lo * s
        rowfs, colfs = [], []
        for r in range(R):
            q = r * s
            o = LO + q
            diag = _dyn_slice(rowband, q, o, s, s)

            def p1(k, t):
                return sr.add(
                    t, sr.mul(t[..., :, k, None], t[..., k, None, :])
                )

            diag = jax.lax.fori_loop(0, s, p1, diag)
            row = _dyn_slice(rowband, q, 0, s, m)

            def p2r(k, p):
                return sr.add(
                    p, sr.mul(diag[..., :, k, None], p[..., k, None, :])
                )

            row = jax.lax.fori_loop(0, s, p2r, row)
            row = _dyn_update(row, diag, 0, o)
            col = _dyn_slice(colband, 0, q, m, s)

            def p2c(k, p):
                return sr.add(
                    p, sr.mul(p[..., :, k, None], diag[..., k, None, :])
                )

            col = jax.lax.fori_loop(0, s, p2c, col)
            col = _dyn_update(col, diag, o, 0)
            rowfs.append(row)
            colfs.append(col)
            # Phase 3 on the cross: bands take their closed values first
            # (both copies of the (P, P) overlap see identical splices),
            # then the same bk-chunk relaxation the fused kernel runs.
            col_cross = _dyn_slice(col, LO, 0, P, s)
            row_cross = _dyn_slice(row, 0, LO, s, P)
            rowband = _dyn_update(rowband, row, q, 0)
            rowband = _dyn_update(rowband, col_cross, 0, o)
            colband = _dyn_update(colband, col, 0, q)
            colband = _dyn_update(colband, row_cross, o, 0)
            for k0 in range(0, s, bk):
                rowband = _stage_compute(
                    rowband, col_cross[..., :, k0:k0 + bk],
                    row[..., k0:k0 + bk, :], sr, variant,
                )
                colband = _stage_compute(
                    colband, col[..., :, k0:k0 + bk],
                    row_cross[..., k0:k0 + bk, :], sr, variant,
                )
        rowf = jnp.concatenate(rowfs, axis=-2) if R > 1 else rowfs[0]
        colf = jnp.concatenate(colfs, axis=-1) if R > 1 else colfs[0]
        return colband, rowband, colf, rowf

    def _sweep_impl(self, tile, colf, rowf, r0, c0):
        """tile ⊕= colf[r0:r0+h] ⊗ rowf[:, c0:c0+w] — R rounds of deferred
        phase 3 as one ascending-k contraction (bitwise per the left-fold
        argument in the module docstring)."""
        self._traced()
        sr, bk, variant = self.semiring, self.bk, self.variant
        h, wd = tile.shape[-2:]
        P = rowf.shape[-2]
        a = _dyn_slice(colf, r0, 0, h, P)
        b = _dyn_slice(rowf, 0, c0, P, wd)
        if self._pallas_sweep:
            return semiring_matmul(
                a, b, tile, semiring=sr, bk=bk, variant=variant
            )
        for k0 in range(0, P, bk):
            tile = _stage_compute(
                tile, a[..., :, k0:k0 + bk], b[..., k0:k0 + bk, :],
                sr, variant,
            )
        return tile

    # ---- driver ----------------------------------------------------------
    def _device(self, i: int):
        if not self.devices:
            return None
        return self.devices[i % len(self.devices)]

    def run(self, store: PanelStore) -> PanelStore:
        """Close the store's matrix in place (returns the store).

        Panels execute in round order — the depth-first traversal of the
        binary recursion — which is exactly what preserves per-element
        ⊕-accumulation order against the flat fused schedule.
        """
        m = store.shape[-1]
        s = self.s
        if m % s:
            raise ValueError(f"matrix size {m} not a multiple of s={s}")
        leaf = min(self.leaf, m)
        ranges, self.depth = kleene_ranges(m // s, leaf // s)
        for p_idx, (lo, hi) in enumerate(ranges):
            R = hi - lo
            LO, HI = lo * s, hi * s
            P = R * s
            colband = store.get(0, LO, m, P)
            rowband = store.get(LO, 0, P, m)
            colband, rowband, colf, rowf = self._leaf(
                colband, rowband, jnp.int32(lo), R=R
            )
            self.leaf_calls += 1
            store.put(0, LO, colband)
            store.put(LO, 0, rowband)
            # Outside sweep over the leaf grid (every tile excluding the
            # cross), double-buffered: prefetch tile i+1, dispatch tile i,
            # then sync tile i−1's write-back while both are in flight.
            tiles = []
            for i, (rlo, rhi) in enumerate(ranges):
                if i == p_idx:
                    continue
                for j, (clo, chi) in enumerate(ranges):
                    if j == p_idx:
                        continue
                    tiles.append(
                        (rlo * s, clo * s, (rhi - rlo) * s, (chi - clo) * s)
                    )
            if not tiles:
                continue
            facs = {}
            for i in range(len(tiles)):
                dev = self._device(i)
                if dev not in facs:
                    facs[dev] = (
                        (colf, rowf) if dev is None
                        else (jax.device_put(colf, dev),
                              jax.device_put(rowf, dev))
                    )
            pending = None
            nxt = store.get(*tiles[0][:2], *tiles[0][2:],
                            device=self._device(0))
            for i, (r0, c0, h, wd) in enumerate(tiles):
                cur = nxt
                if i + 1 < len(tiles):
                    t2 = tiles[i + 1]
                    nxt = store.get(*t2[:2], *t2[2:],
                                    device=self._device(i + 1))
                cf, rf = facs[self._device(i)]
                out = self._sweep(cur, cf, rf, jnp.int32(r0), jnp.int32(c0))
                self.sweep_calls += 1
                if pending is not None:
                    store.put(pending[0], pending[1], pending[2])
                pending = (r0, c0, out)
            store.put(pending[0], pending[1], pending[2])
        return store


# --------------------------------------------------------------- frontend
def fw_kleene(
    w,
    *,
    semiring: Semiring = MIN_PLUS,
    block_size: int,
    leaf: int | None = None,
    bk: int = 32,
    variant: str = "fori",
    out_of_core: bool = False,
    interpret: bool | None = None,
    devices: Sequence | None = None,
    store: PanelStore | None = None,
) -> jax.Array:
    """Recursive-schedule closure of a padded (…, m, m) matrix.

    m must be a multiple of ``block_size`` (``apsp.solve`` owns padding,
    like the other backends).  ``leaf`` defaults to min(m, 4·block_size);
    ``out_of_core=True`` routes through a ``HostPanelStore`` (host-resident
    matrix, streamed panels) instead of the in-core device store.  Pass an
    explicit ``store`` to keep it — its h2d/d2h byte counters are the
    measured side of the ``plan.recursive_transfer_bytes`` model.  Bitwise
    equal to ``fw_staged`` at the same block size, whatever its round
    lowering, on every semiring lowering (tests/test_kleene.py).
    """
    m = w.shape[-1]
    if leaf is None:
        leaf = min(m, 4 * block_size)
    ex = KleeneExecutor(
        semiring=semiring, block_size=block_size, leaf=min(leaf, m), bk=bk,
        variant=variant, interpret=interpret, devices=devices,
    )
    if store is None:
        store = (
            HostPanelStore(np.asarray(w)) if out_of_core
            else DevicePanelStore(jnp.asarray(w))
        )
    ex.run(store)
    return jnp.asarray(store.result())
