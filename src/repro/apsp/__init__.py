"""repro.apsp — the unified APSP solver front-end and execution engine.

    from repro.apsp import solve, ApspEngine
    res = solve(w)                       # any n, any method, auto-padded
    res = solve(w_batch, method="fused") # native batch grid, one dispatch/round

    eng = ApspEngine()                   # serving sessions: repeated solves
    results = eng.solve_many(graphs)     # ragged sizes, bucketed + cached

``engine.ApspEngine`` runs every solve over the paper's implementation
ladder (numpy / naive / blocked / staged / fused / recursive /
distributed) and owns the plan/executable cache and ragged-batch
bucketing for repeated solves (mesh-keyed for distributed meshes);
``api.solve`` is the stateless entry point, one ``ApspEngine.solve`` per
call; ``plan`` holds the shared block-size / padding / roofline /
autotune / mesh arithmetic (batch-aware).  ``autotune_fw``,
``distributed_plan``, and ``recursive_plan`` are re-exported from ``plan``
as the planner entry points users reach for directly; ``kleene`` holds the
out-of-core R-Kleene schedule behind method="recursive" (``fw_kleene`` is
its direct entry point on pre-padded matrices).
"""
from repro.apsp import plan
from repro.apsp.api import (
    METHODS,
    SUCCESSOR_METHODS,
    APSPResult,
    NegativeCycleError,
    negative_cycle_mask,
    pack_edges,
    pack_reachability,
    solve,
    unpack_reachability,
)
from repro.apsp.engine import ApspEngine, EngineStats, ExecutablePlan, PlanKey
from repro.apsp.kleene import (
    DevicePanelStore,
    HostPanelStore,
    KleeneExecutor,
    fw_kleene,
)
from repro.apsp.plan import autotune_fw, distributed_plan, recursive_plan

__all__ = [
    "APSPResult",
    "ApspEngine",
    "DevicePanelStore",
    "EngineStats",
    "ExecutablePlan",
    "HostPanelStore",
    "KleeneExecutor",
    "METHODS",
    "SUCCESSOR_METHODS",
    "NegativeCycleError",
    "PlanKey",
    "autotune_fw",
    "distributed_plan",
    "fw_kleene",
    "negative_cycle_mask",
    "pack_edges",
    "pack_reachability",
    "plan",
    "recursive_plan",
    "solve",
    "unpack_reachability",
]
