"""Compile the main-path kernels for a described TPU v5e, at real sizes.

No chip is attached: the TPU compiler builds for a ``v5e:2x2`` topology it
is only told about (``jax.experimental.topologies``).  Nothing runs, so
these tests say nothing about results or times; they catch what interpret
mode cannot — a kernel Mosaic refuses (gathers, unaligned blocks, 16-bit
ops), a scratch band over the scoped-VMEM limit, order arrays over SMEM.
Each test asserts the Pallas kernel (``tpu_custom_call``) is in the
compiled program.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.  The persistent compilation cache is off while these compile —
an entry written for a described chip cannot be read back without one.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

import repro.core  # noqa: F401  (import order: core before the kernels)
from repro.core.semiring import MIN_PLUS, MIN_PLUS_I16, OR_AND_PACKED
from repro.kernels.fw_repair import fw_repair, fw_repair_with_successors
from repro.kernels.fw_repair_del import fw_repair_del_sweep
from repro.kernels.fw_round import fw_round, fw_round_with_successors
from repro.kernels.minplus_matmul import semiring_matmul

N = 16384


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # pragma: no cover - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n", [16384, 32768])
def test_fused_round_compiles_f32(one_chip, n):
    text = _compiled_text(
        lambda w, b: fw_round(w, b, interpret=False),
        _spec((n, n), jnp.float32, one_chip),
        _spec((), jnp.int32, one_chip),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "dtype,semiring",
    [(jnp.bfloat16, MIN_PLUS), (jnp.int32, OR_AND_PACKED)],
    ids=["bf16", "or_and_packed"],
)
def test_fused_round_compiles_storage_lowerings(one_chip, dtype, semiring):
    text = _compiled_text(
        lambda w, b: fw_round(w, b, semiring=semiring, interpret=False),
        _spec((N, N), dtype, one_chip),
        _spec((), jnp.int32, one_chip),
    )
    assert "tpu_custom_call" in text


def test_fused_round_int16_refused_with_reason(one_chip):
    with pytest.raises(NotImplementedError, match="int16"):
        _compiled_text(
            lambda w, b: fw_round(w, b, semiring=MIN_PLUS_I16,
                                  interpret=False),
            _spec((N, N), jnp.int16, one_chip),
            _spec((), jnp.int32, one_chip),
        )


def test_fused_round_sub_lane_tile_refused(one_chip):
    with pytest.raises(ValueError, match="multiple of 128"):
        _compiled_text(
            lambda w, b: fw_round(w, b, block_size=32, interpret=False),
            _spec((1024, 1024), jnp.float32, one_chip),
            _spec((), jnp.int32, one_chip),
        )


def test_successor_round_compiles(one_chip):
    text = _compiled_text(
        lambda w, sc, b: fw_round_with_successors(w, sc, b, interpret=False),
        _spec((N, N), jnp.float32, one_chip),
        _spec((N, N), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip),
    )
    assert "tpu_custom_call" in text


def test_batched_successor_round_compiles(one_chip):
    # The routing refresh: 8 graphs of n=2048 through one batch grid.
    text = _compiled_text(
        lambda w, sc, b: fw_round_with_successors(w, sc, b, interpret=False),
        _spec((8, 2048, 2048), jnp.float32, one_chip),
        _spec((8, 2048, 2048), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("successors", [False, True], ids=["dist", "succ"])
def test_repair_compiles(one_chip, successors):
    E = 4
    d = _spec((N, N), jnp.float32, one_chip)
    uvw = (_spec((E,), jnp.int32, one_chip), _spec((E,), jnp.int32, one_chip),
           _spec((E,), jnp.float32, one_chip))
    if successors:
        text = _compiled_text(
            lambda d, sc, u, v, w: fw_repair_with_successors(
                d, sc, u, v, w, interpret=False),
            d, _spec((N, N), jnp.int32, one_chip), *uvw,
        )
    else:
        text = _compiled_text(
            lambda d, u, v, w: fw_repair(d, u, v, w, interpret=False),
            d, *uvw,
        )
    assert "tpu_custom_call" in text


def test_repair_del_sweep_compiles(one_chip):
    text = _compiled_text(
        lambda d, rows: fw_repair_del_sweep(
            d, rows, block_size=128, interpret=False),
        _spec((N, N), jnp.float32, one_chip),
        _spec((64,), jnp.int32, one_chip),
    )
    assert "tpu_custom_call" in text


def test_kleene_contraction_compiles(one_chip):
    # semiring_matmul as the R-Kleene sweep runs it: a P=512-deep factor
    # contraction into an (n, n) tile.
    text = _compiled_text(
        lambda a, b, c: semiring_matmul(a, b, c, interpret=False),
        _spec((N, 512), jnp.float32, one_chip),
        _spec((512, N), jnp.float32, one_chip),
        _spec((N, N), jnp.float32, one_chip),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fused", [None, False],
                         ids=["fused_round", "four_dispatch"])
def test_solve_program_compiles(one_chip, fused):
    # The whole program solve(method="auto") runs on a TPU at n=16384, and
    # the seed's phase-kernel round (fw_phase1/fw_phase2 + the matmul).
    from repro.core.staged import fw_staged

    text = _compiled_text(
        lambda w: fw_staged(w, block_size=128, fused=fused, interpret=False),
        _spec((N, N), jnp.float32, one_chip),
    )
    assert "tpu_custom_call" in text


def test_bordered_round_compiles_on_4_chip_mesh(topo):
    # The distributed solve's shard-mapped rounds on the described 2x2
    # mesh: one fused bordered round per chip, plus the collectives.
    from jax.sharding import Mesh
    import numpy as np

    from repro.core.distributed import build_fw_shard_fn

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    n = 2 * N
    sharded, sharding = build_fw_shard_fn(
        mesh, n, block_size=128, semiring=MIN_PLUS, interpret=False,
        fused_lowering="pallas",
    )
    rep = NamedSharding(mesh, P())
    text = _compiled_text(
        sharded,
        _spec((n, n), jnp.float32, sharding),
        _spec((), jnp.int32, rep),
        _spec((), jnp.int32, rep),
    )
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
