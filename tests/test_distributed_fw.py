"""Distributed FW correctness on multi-device host meshes.

Runs in subprocesses because XLA device count is locked at first jax init
(the main pytest process must keep seeing 1 device).
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def test_2d_mesh_jnp():
    # Pin the per-phase jnp lowering explicitly (the default backend is the
    # fused bordered round now — covered bitwise in test_distributed.py).
    out = run_check("--devices", "8", "--n", "256", "--bs", "32",
                    "--backend", "jnp")
    assert "OK" in out


def test_2d_mesh_pallas_backend():
    out = run_check("--devices", "8", "--n", "256", "--bs", "32", "--backend", "pallas")
    assert "OK" in out


def test_multipod_mesh_chunked_checkpoints():
    out = run_check("--devices", "8", "--n", "256", "--bs", "64", "--pods", "2", "--chunked")
    assert "OK" in out


def test_tall_blocks():
    out = run_check("--devices", "4", "--n", "512", "--bs", "128", "--chunked")
    assert "OK" in out
