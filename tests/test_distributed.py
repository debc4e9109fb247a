"""Distributed solve == single-device fused solve, BITWISE.

The tentpole guarantee of the mesh-native path: ``fw_distributed`` /
``solve(method="distributed")`` / ``ApspEngine(mesh=...)`` run the fused
bordered round per device (``kernels.fw_round_bordered``), whose owner-echo
splices make every per-element ⊕/⊗ chain identical to the single-device
fused kernel's — so the sharded result must equal the unsharded one bit for
bit on ALL five semirings and both dtypes, not merely allclose.  n=96 on an
8-device (4×2) mesh also exercises ``plan.distributed_plan``'s auto-padding
(96 → 128) on every run.

Subprocesses because the XLA host-device count is locked at first jax init
(the main pytest process must keep seeing 1 device); each check compares
distributed vs single-device *inside* one subprocess.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SEMIRINGS = ("min_plus", "max_plus", "max_min", "or_and", "plus_mul")
DTYPES = ("float32", "bfloat16")


def run_check(*args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"  # virtual host devices
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.fw_dist_check", *args],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return res.stdout


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("semiring", SEMIRINGS)
def test_solve_distributed_bitwise_vs_fused(semiring, dtype):
    """solve(method="distributed") == solve(method="fused"), bitwise, with
    non-divisible n (96 → padded 128 on the 4×2 grid)."""
    out = run_check(
        "--devices", "8", "--n", "96", "--bs", "32", "--method", "solve",
        "--bitwise", "--semiring", semiring, "--dtype", dtype,
    )
    assert "OK bitwise" in out and "padded=128" in out


def test_fw_distributed_direct_bitwise():
    """The raw fw_distributed entry point (no solve padding) bit-matches."""
    out = run_check("--devices", "8", "--n", "128", "--bs", "16", "--bitwise")
    assert "OK bitwise" in out


@pytest.mark.parametrize("semiring", ["min_plus", "plus_mul"])
def test_distributed_lane_cache_rows_bitwise(semiring):
    """A 2×2 mesh with 16-wide tiles gives each device a bordered block of
    five tile rows, each relaxed against its own lane cache, for a batch of
    two graphs: the sharded solve still bit-matches the fused one."""
    out = run_check(
        "--devices", "4", "--n", "128", "--bs", "16", "--method", "solve",
        "--bitwise", "--batch", "2", "--semiring", semiring,
    )
    assert "OK bitwise" in out


def test_solve_distributed_batched_bitwise():
    """(B, n, n) input shards the trailing dims; every graph bit-matches
    its single-device fused solve through one sharded batch."""
    out = run_check(
        "--devices", "8", "--n", "96", "--bs", "32", "--method", "solve",
        "--bitwise", "--batch", "3",
    )
    assert "OK bitwise" in out


def test_engine_mesh_ragged_no_retrace():
    """ApspEngine(mesh=...): ragged solve_many buckets shard across devices,
    bit-match single-device solves, and the warm cache retraces nothing."""
    out = run_check("--devices", "8", "--n", "96", "--bs", "16",
                    "--method", "engine")
    assert "OK engine" in out and "cache=2" in out


def test_bench_metrics_comm_model_matches_hlo():
    """--bench: the collective bytes in the compiled per-round HLO must
    match plan.dist_round_comm_bytes exactly — the comm model is checked
    against a measured (compiled) run, not just asserted."""
    import json

    out = run_check("--devices", "8", "--n", "256", "--bs", "32", "--bench")
    line = next(l for l in out.splitlines() if l.startswith("METRICS "))
    m = json.loads(line[len("METRICS "):])
    assert m["comm_measured_bytes"] == m["comm_model_bytes"], m
    assert 0 < m["comm_efficiency_measured"] <= 1.0
    assert m["round_ms"] > 0


def test_distributed_plan_auto_padding():
    """Host-side planner arithmetic (no devices needed)."""
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.apsp.plan import distributed_plan

    p = distributed_plan(96, 8, block_size=32)
    assert (p["R"], p["C"]) == (4, 2)
    assert p["n_padded"] == 128 and p["rounds"] == 4
    assert p["tile"] == (32, 64) and p["bordered"] == (64, 96)
    assert 0 < p["comm_model_efficiency"] <= 1.0
    # pinning an existing mesh grid overrides the factorization
    p2 = distributed_plan(96, 8, grid=(2, 4), block_size=32)
    assert (p2["R"], p2["C"]) == (2, 4)
    with pytest.raises(ValueError):
        distributed_plan(96, 8, grid=(3, 2))
