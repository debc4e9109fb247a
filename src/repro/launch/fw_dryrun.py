import os
os.environ["XLA_FLAGS"] = os.environ.get(
    "REPRO_XLA_FLAGS", "--xla_force_host_platform_device_count=512"
)
# ^ MUST precede every other import: jax locks the device count on first init.
"""Dry-run + roofline for the paper's own workload: distributed blocked FW.

    PYTHONPATH=src python -m repro.launch.fw_dryrun --n 65536 --mesh both

Unlike the LM cells, FW's (min,+) inner loop cannot use the MXU, so the
compute roofline is the VPU:
    VPU ops/s/chip ≈ 8 sublanes × 128 lanes × 2 ALU ops × 1.59 GHz ≈ 3.26e12
(documented estimate — v5e's vector unit; the MXU's 197 TFLOP/s bf16 is
unreachable for tropical semirings, DESIGN.md §2).

USEFUL_OPS = 2·n³ (one add + one min per relaxation task).
Comm lower bound (SUMMA): n²(1/R + 1/C) words over n/s rounds.
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.apsp import plan
from repro.core.distributed import build_fw_shard_fn
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh

VPU_OPS = 8 * 128 * 2 * 1.59e9  # ≈3.26e12 elementwise ops/s/chip (estimate)


def run(n: int, block_size: int, multi_pod: bool, backend: str,
        lookahead: bool = False, phase2_shard: bool = False) -> dict:
    # Counting mode: unroll the k-loops inside the round body so
    # cost_analysis sees true trip counts (nested fori bodies are otherwise
    # counted once); the ROUND loop correction stays explicit (× rounds).
    import repro.core.distributed as dist

    dist._UNROLL_INNER = True
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 1
    for v in mesh.shape.values():
        chips *= v
    row_axes = ("pod", "data") if multi_pod else ("data",)
    # Counting always lowers the jnp backend: the Pallas kernel performs the
    # *identical* semiring arithmetic and pmins (tests/test_kernels.py), but
    # its interpret-mode lowering hides trip counts from cost_analysis.  The
    # pallas record keeps the measured compute/collective terms and swaps in
    # the BlockSpec-derived memory term below.
    sharded, sharding = build_fw_shard_fn(
        mesh, n, block_size=block_size, row_axes=row_axes, col_axes="model",
        backend="jnp", interpret=True, lookahead=lookahead,
        phase2_shard=phase2_shard,
    )
    rounds = plan.round_count(n, block_size)
    fn = jax.jit(sharded, donate_argnums=(0,))
    w_s = jax.ShapeDtypeStruct((n, n), jnp.float32)

    t0 = time.time()
    with mesh:
        lowered = fn.lower(
            jax.device_put(w_s, sharding) if False else w_s,
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
        )
        compiled = lowered.compile()
    compile_s = time.time() - t0
    ma = compiled.memory_analysis()
    print(compiled.memory_analysis())

    # cost_analysis counts the fori_loop round body ONCE → multiply by the
    # round count (the body is round-invariant: same slicing, same pmins).
    flops_once, bytes_once = rl.cost_flops_bytes(compiled)
    coll_once = rl.parse_collective_bytes(compiled.as_text())
    flops = flops_once * rounds
    byts = bytes_once * rounds
    coll = {k: v * rounds for k, v in coll_once.items()}
    coll_total = sum(coll.values())

    if backend == "pallas":
        # Mosaic cannot compile on CPU, so the Pallas phase-3 memory term is
        # derived from BlockSpec arithmetic (the VMEM contract is explicit;
        # model and derivation live in repro.apsp.plan / EXPERIMENTS.md).
        # The compute term is the same op count as the jnp backend (kept
        # from the measured lowering); collectives identical (same pmins).
        n_r = n // (chips // mesh.shape["model"])
        n_c = n // mesh.shape["model"]
        byts = plan.staged_hbm_bytes_per_round(n_r, n_c, block_size) * rounds

    useful_ops = 2.0 * n ** 3
    t_compute = flops / VPU_OPS  # FW is a VPU workload
    t_memory = byts / rl.HBM_BW
    t_coll = coll_total / rl.ICI_LINK_BW
    t_max = max(t_compute, t_memory, t_coll)
    frac = (useful_ops / chips / t_max) / VPU_OPS if t_max else 0.0
    # SUMMA comm lower bound per chip (f32 words).
    R = chips // mesh.shape["model"]
    C = mesh.shape["model"]
    comm_bound = plan.summa_comm_bound_bytes(n, R, C)

    rec = {
        "workload": "distributed_fw",
        "n": n,
        "block_size": block_size,
        "backend": backend,
        "lookahead": lookahead,
        "phase2_shard": phase2_shard,
        "mesh": "pod2x16x16" if multi_pod else "pod16x16",
        "rounds": rounds,
        "compile_s": round(compile_s, 1),
        "argument_bytes_per_dev": ma.argument_size_in_bytes,
        "temp_bytes_per_dev": ma.temp_size_in_bytes,
        "fits_v5e_16gb": (ma.argument_size_in_bytes + ma.temp_size_in_bytes)
        < 16 * 2 ** 30,
        "flops_per_chip": flops,
        "bytes_per_chip": byts,
        "coll_bytes_per_chip": coll_total,
        "coll_detail": coll,
        "useful_ops": useful_ops,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "bottleneck": max(
            {"compute": t_compute, "memory": t_memory, "collective": t_coll},
            key=lambda k: {"compute": t_compute, "memory": t_memory,
                           "collective": t_coll}[k],
        ),
        "roofline_fraction_vpu": frac,
        "summa_comm_bound_bytes": comm_bound,
        "comm_efficiency": comm_bound / coll_total if coll_total else 0.0,
    }
    return rec


def main():
    from repro.utils.compat import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=65536)
    ap.add_argument("--block-size", type=int, default=128)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--backend", default="jnp", choices=["jnp", "pallas"])
    ap.add_argument("--lookahead", action="store_true")
    ap.add_argument("--phase2-shard", action="store_true")
    ap.add_argument("--out", default="experiments/fw_dryrun")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    for multi in meshes:
        tag = (
            f"fw_n{args.n}_s{args.block_size}_{args.backend}"
            f"{'_look' if args.lookahead else ''}"
            f"{'_p2s' if args.phase2_shard else ''}_{'multi' if multi else 'single'}"
        )
        rec = run(args.n, args.block_size, multi, args.backend, args.lookahead,
                  args.phase2_shard)
        with open(os.path.join(args.out, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
        print(
            f"[ok] {tag} bottleneck={rec['bottleneck']} "
            f"frac={rec['roofline_fraction_vpu']:.3f} "
            f"t=(c {rec['t_compute_s']:.2f}s, m {rec['t_memory_s']:.2f}s, "
            f"x {rec['t_collective_s']:.2f}s) comm_eff={rec['comm_efficiency']:.2f}"
        )


if __name__ == "__main__":
    main()
