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
import re

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


@pytest.mark.parametrize("B,n,bb", [(16, 2048, 8), (4, 16384, 4)])
def test_batched_round_compiles_at_auto_batch_block(one_chip, B, n, bb):
    # The batch block auto_batch_block plans, lane cache included, is one
    # the v5e's scoped VMEM holds.
    from repro.apsp import plan

    assert plan.auto_batch_block(B, n, 128) == bb
    text = _compiled_text(
        lambda w, b: fw_round(w, b, interpret=False),
        _spec((B, n, n), jnp.float32, one_chip),
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


@pytest.mark.parametrize("lowering", ["pallas", "phases"],
                         ids=["fused_round", "four_dispatch"])
def test_solve_program_compiles(one_chip, lowering):
    # The whole program solve(method="auto") runs on a TPU at n=16384, and
    # the seed's phase-kernel round (fw_phase1/fw_phase2 + the matmul).
    from repro.core.staged import fw_staged

    text = _compiled_text(
        lambda w: fw_staged(w, block_size=128, lowering=lowering,
                            interpret=False),
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


# ------------------------------------------------------------- trace names
# The benchmark reads the device trace by name: the solve program's module
# (``jit_apsp_solve``), the round kernels' HLO ops (``%fw_round.<i>``,
# ``%fw_round_with_successors.<i>``) and the per-round ``copy`` ops inside
# the solve's ``while`` loop.  A rename here would silently null a metric.
SMALL = 2048


def _computations(text: str) -> dict[str, list[str]]:
    """Compiled HLO text -> {computation name: its instruction lines};
    the entry computation is also under "ENTRY"."""
    comps: dict[str, list[str]] = {}
    body = None
    for line in text.splitlines():
        if body is None and line.endswith("{") and not line[:1].isspace():
            head = line.split()
            entry = head[0] == "ENTRY"
            name = (head[1] if entry else head[0]).lstrip("%")
            if name == "HloModule":
                continue
            body = comps.setdefault(name, [])
            if entry:
                comps["ENTRY"] = body
        elif body is not None and line == "}":
            body = None
        elif body is not None:
            body.append(line.strip())
    return comps


def _table_copies(lines, n: int) -> list[str]:
    """The ``copy`` ops in ``lines`` whose result is a whole (1, n, n)
    table."""
    pat = re.compile(rf"^%copy\.\d+ = \w+\[1,{n},{n}\]\S* copy\(")
    return [ln for ln in lines if pat.match(ln)]


def _module(text: str) -> str:
    return text.split(None, 2)[1].rstrip(",")


@pytest.mark.parametrize("successors", [False, True], ids=["dist", "succ"])
def test_engine_solve_program_names(one_chip, successors):
    from repro.apsp import ApspEngine

    eng = ApspEngine(method="fused", backend="tpu", interpret=False,
                     block_size=128)
    entry = eng.plan_for(SMALL, 1, successors=successors)
    text = entry.runner.lower(
        _spec((1, SMALL, SMALL), jnp.float32, one_chip)).compile().as_text()
    assert _module(text) == "jit_apsp_solve"
    kernel = "fw_round_with_successors" if successors else "fw_round"
    other = "fw_round" if successors else "fw_round_with_successors"
    assert re.search(rf"^\s*(?:ROOT )?%{kernel}\.\d+ = ", text, re.M)
    assert not re.search(rf"^\s*(?:ROOT )?%{other}\.\d+ = ", text, re.M)
    comps = _computations(text)
    loops = [re.search(r"body=%([\w.\-]+)", ln).group(1)
             for ln in comps["ENTRY"] if " while(" in ln]
    assert len(loops) == 1
    body = comps[loops[0]]
    # One whole-table copy per round: the distances, and the next hops.
    copies = _table_copies(body, SMALL)
    assert len(copies) == (2 if successors else 1), copies
    assert any(" f32[" in c for c in copies)
    if successors:
        assert any(" s32[" in c for c in copies)
        # The successor program's one copy of the input lies outside the
        # loop, so the per-round reading does not count it.
        assert _table_copies(comps["ENTRY"], SMALL)


def _kernel_case(name, one_chip):
    from repro.kernels.fw_round import fw_round_bordered

    n, E = 1024, 4
    f32 = _spec((n, n), jnp.float32, one_chip)
    i32 = _spec((n, n), jnp.int32, one_chip)
    b = _spec((), jnp.int32, one_chip)
    uvw = (_spec((E,), jnp.int32, one_chip), _spec((E,), jnp.int32, one_chip),
           _spec((E,), jnp.float32, one_chip))
    return {
        "fw_round": (lambda w, b: fw_round(w, b, interpret=False), f32, b),
        "fw_round_with_successors": (
            lambda w, sc, b: fw_round_with_successors(w, sc, b,
                                                      interpret=False),
            f32, i32, b),
        "fw_round_bordered": (
            lambda w: fw_round_bordered(w, interpret=False), f32),
        "fw_repair": (
            lambda d, u, v, w: fw_repair(d, u, v, w, interpret=False),
            f32, *uvw),
        "fw_repair_with_successors": (
            lambda d, sc, u, v, w: fw_repair_with_successors(
                d, sc, u, v, w, interpret=False),
            f32, i32, *uvw),
        "fw_repair_del_sweep": (
            lambda d, rows: fw_repair_del_sweep(d, rows, block_size=128,
                                                interpret=False),
            f32, _spec((64,), jnp.int32, one_chip)),
        "semiring_matmul": (
            lambda a, b, c: semiring_matmul(a, b, c, interpret=False),
            _spec((n, 512), jnp.float32, one_chip),
            _spec((512, n), jnp.float32, one_chip), f32),
    }[name]


@pytest.mark.parametrize("name", [
    "fw_round", "fw_round_with_successors", "fw_round_bordered", "fw_repair",
    "fw_repair_with_successors", "fw_repair_del_sweep", "semiring_matmul"])
def test_kernel_op_names(one_chip, name):
    fn, *args = _kernel_case(name, one_chip)
    text = _compiled_text(fn, *args)
    ops = re.findall(r'^\s*(?:ROOT )?%([\w.\-]+) = .*'
                     r'custom_call_target="tpu_custom_call"', text, re.M)
    assert ops and all(re.fullmatch(rf"{name}\.\d+", op) for op in ops), ops


@pytest.mark.parametrize("method,successors", [
    ("repair", False), ("repair", True),
    ("repair_del_mark", False), ("repair_del_mark", True),
    ("repair_del", False), ("repair_del", True)])
def test_engine_repair_program_names(one_chip, method, successors):
    from repro.apsp import ApspEngine
    from repro.apsp.engine import PlanKey

    eng = ApspEngine(backend="tpu", interpret=False, block_size=128)
    m, E, a_pad = SMALL, 4, 64
    key = PlanKey(n_padded=m, batch=1, dtype="float32",
                  semiring=eng.semiring.name, method=method, block_size=128,
                  bk=32 if method == "repair_del" else 0, batch_block=None,
                  successors=successors,
                  edges=a_pad if method == "repair_del" else E,
                  backend="tpu")
    build = {"repair": eng._build_repair,
             "repair_del_mark": eng._build_repair_del_mark,
             "repair_del": eng._build_repair_del_sweep}[method]
    d = _spec((m, m), jnp.float32, one_chip)
    sc = [_spec((m, m), jnp.int32, one_chip)] if successors else []
    edge = [_spec((E,), jnp.int32, one_chip)] * 2 + [
        _spec((E,), jnp.float32, one_chip)]
    args = {
        "repair": [d, *sc, *edge],
        "repair_del_mark": [d, *sc, d, *edge, _spec((), jnp.int32, one_chip)],
        "repair_del": [d, *sc, _spec((a_pad,), jnp.int32, one_chip)],
    }[method]
    text = build(key).runner.lower(*args).compile().as_text()
    assert _module(text) == {"repair": "jit_apsp_repair",
                             "repair_del_mark": "jit_apsp_repair_del_mark",
                             "repair_del": "jit_apsp_repair_del_sweep"}[method]


def test_engine_packed_solve_program(one_chip):
    # The packed reachability closure as the engine plans it on a TPU at
    # n=16384: one int32 word table of 32 graphs, the or_and_packed round.
    from repro.apsp import ApspEngine

    eng = ApspEngine(semiring="or_and", packed=True, method="fused",
                     backend="tpu", interpret=False)
    entry = eng.plan_for(N, 1, dtype=jnp.int32)
    text = entry.runner.lower(
        _spec((1, N, N), jnp.int32, one_chip)).compile().as_text()
    assert _module(text) == "jit_apsp_solve"
    assert re.search(r"^\s*(?:ROOT )?%fw_round\.\d+ = s32\[", text, re.M)


def test_edge_list_packer_program(one_chip):
    # The packer at the reach16k deployment: 32 graphs of 16 * n edges.
    from repro.apsp.engine import apsp_pack

    E = 16 * N
    text = apsp_pack.lower(
        _spec((32, E), jnp.int32, one_chip),
        _spec((32, E), jnp.int32, one_chip), n=N).compile().as_text()
    assert _module(text) == "jit_apsp_pack"
    assert re.search(rf"^\s*ROOT .* = s32\[1,{N},{N}\]", text, re.M)
