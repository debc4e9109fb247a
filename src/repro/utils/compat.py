"""Backend plumbing shared by the whole library.

  * ``shard_map`` — the one spelling of ``jax.shard_map`` the library uses
    (replication checks off: the kernels' outputs are per-device blocks).
  * **backend resolution** for the Pallas kernels: ``resolve_pallas_backend``
    maps a user-facing ``backend=`` argument ("auto" | "tpu" | "gpu" |
    "ref") to the lowering the solver threads through ``PlanKey`` and
    ``fw_staged(fused=)``, and ``pallas_tpu`` is the ONE lazy
    ``jax.experimental.pallas.tpu`` import the kernels route through.
  * **the device's budgets**: ``vmem_limit_bytes`` (the scoped-VMEM limit
    every TPU kernel compiles with, which is also the planning budget) and
    ``min_block_size`` (the tile floor on TPU).
  * ``enable_compile_cache`` — the persistent compilation cache, turned on
    by entry points (never at import).
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Sequence

import jax

# The lowerings a Pallas-backed round can resolve to.  "ref" is the bitwise
# XLA twin in kernels/ref.py — execution-grade on any backend.
PALLAS_BACKENDS = ("tpu", "gpu", "ref")

# jax.default_backend() spellings that mean "a real GPU is attached".
_GPU_PLATFORMS = ("gpu", "cuda", "rocm")

# Scoped VMEM one Pallas TPU kernel may use, by ``device_kind``.  A v5e core
# has 128 MiB of VMEM, but Mosaic grants a kernel 16 MiB unless asked: the
# fused round's (s, n) + (n, s) scratch bands outgrow that past n = 8192 in
# f32.  The headroom under 128 MiB is left to Mosaic's internal scratch.
# A TPU device kind missing here is an error, never a default.
_VMEM_LIMIT_BYTES = {"TPU v5 lite": 100 << 20}
# Off a TPU the TPU kernels only run interpreted, as rehearsals of this
# device kind, so they plan as for it.
_REHEARSED_KIND = "TPU v5 lite"

# Mosaic tiles the last two block dims by (8, 128): on TPU every (s, s)
# block is a lane-tile multiple and smaller graphs pad up to it.
_TPU_MIN_BLOCK = 128
_MIN_BLOCK = 16

_REPO = Path(__file__).resolve().parents[3]


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checks off."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def resolve_pallas_backend(backend: str = "auto") -> str:
    """Resolve a user-facing ``backend=`` to a concrete round lowering.

    "auto" reads ``jax.default_backend()``: "tpu" on a TPU, "gpu" when a
    CUDA/ROCm device is attached, and "ref" (the bitwise XLA twin)
    everywhere else.  Explicit values are validated and passed through:
    ``backend="gpu"`` on a CPU host still runs the GPU lowering, in Pallas
    interpret mode (``kernels.ops.default_gpu_interpret``), which is how the
    bitwise test suite and CI exercise it without hardware.
    """
    if backend == "auto":
        plat = jax.default_backend()
        if plat == "tpu":
            return "tpu"
        if plat in _GPU_PLATFORMS:
            return "gpu"
        return "ref"
    if backend not in PALLAS_BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}; have "
            f"{('auto',) + PALLAS_BACKENDS}"
        )
    return backend


def pallas_tpu(need: str = "pallas TPU scratch + scalar prefetch") -> Any:
    """The lazy ``jax.experimental.pallas.tpu`` import, shared by every
    TPU kernel.

    Raises ``NotImplementedError`` (naming what the caller ``need``-ed)
    when the module is absent — GPU-only / CPU-only jax builds — so the
    kernels stay importable everywhere and only *calling* a TPU lowering
    without the TPU pallas module fails.
    """
    try:
        from jax.experimental.pallas import tpu as pltpu

        return pltpu
    except NotImplementedError:
        raise
    except Exception as e:  # pragma: no cover - pallas TPU module absent
        raise NotImplementedError(f"{need} unavailable in this jax") from e


def vmem_limit_bytes() -> int:
    """Scoped-VMEM budget of ``jax.devices()[0]`` — the limit every TPU
    kernel compiles with and the budget ``apsp.plan`` fits TPU scratch
    into.  Only the TPU lowerings read it; the Triton round and the XLA
    twins keep no VMEM scratch."""
    dev = jax.devices()[0]
    kind = dev.device_kind if dev.platform == "tpu" else _REHEARSED_KIND
    try:
        return _VMEM_LIMIT_BYTES[kind]
    except KeyError:
        raise ValueError(
            f"no VMEM budget for device kind {kind!r}; add it to "
            f"repro.utils.compat._VMEM_LIMIT_BYTES"
        ) from None


def min_block_size() -> int:
    """Smallest pivot tile the attached backend runs: one lane tile (128)
    on TPU, 16 elsewhere (interpret mode and the XLA twins)."""
    return _TPU_MIN_BLOCK if jax.default_backend() == "tpu" else _MIN_BLOCK


def check_tpu_lowering(
    dtype, interpret: bool, block_size: int | None = None
) -> None:
    """Refuse, with the reason, a kernel launch Mosaic cannot compile for
    the chip — instead of an opaque Mosaic error or a silent fallback.

    int16 storage: v5e's Mosaic has no 16-bit integer compare or min.
    Tiles: (s, s) pivot blocks must be lane-tile (128) multiples.
    """
    if interpret:
        return
    if jax.numpy.dtype(dtype) == jax.numpy.dtype(jax.numpy.int16):
        raise NotImplementedError(
            "the int16 storage lowering does not compile for TPU: Mosaic "
            "has no 16-bit integer compare/min on this chip; use float32 "
            "or bfloat16 storage"
        )
    if block_size is not None and block_size % _TPU_MIN_BLOCK:
        raise ValueError(
            f"block_size={block_size} on TPU must be a multiple of "
            f"{_TPU_MIN_BLOCK} (Mosaic's lane tile)"
        )


def tpu_compiler_params(*, dimension_semantics: Sequence[str]) -> Any:
    """Pallas TPU CompilerParams: grid semantics + the device VMEM limit."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics),
        vmem_limit_bytes=vmem_limit_bytes(),
    )


def gpu_compiler_params(*, num_warps: int, num_stages: int) -> Any:
    """Pallas Triton CompilerParams (occupancy hints)."""
    from jax.experimental.pallas import triton as pltriton

    return pltriton.CompilerParams(num_warps=num_warps, num_stages=num_stages)


def vmem_scratch(shape: tuple[int, ...], dtype) -> Any:
    """A ``pltpu.VMEM`` scratch allocation spec."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory;
    otherwise it is ``<repo>/.jax_cache`` — a fixed path, because the path
    is part of what a later run must find again.  Every compiled program is
    kept, however quick its compile.  Call from entry points only.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        _REPO / ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
