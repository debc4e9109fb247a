"""Production mesh construction.

A FUNCTION (not a module constant) so importing this module never touches
jax device state — required because dryrun.py must set XLA_FLAGS before any
jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.apsp.plan import mesh_factorization


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(n_devices: int | None = None, *, pods: int = 1):
    """Mesh over the first ``n_devices`` devices (all by default): forced
    host devices in tests/examples, the real chips on an accelerator.

    Uses the same (R, C) factorization as launch.fw_dist_check
    (repro.apsp.plan.mesh_factorization).
    """
    n = n_devices or len(jax.devices())
    R, C = mesh_factorization(n, pods)
    if pods > 1:
        return _make_mesh((pods, R // pods, C), ("pod", "data", "model"))
    return _make_mesh((R, C), ("data", "model"))
