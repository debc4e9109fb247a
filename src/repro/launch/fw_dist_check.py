"""Multi-device distributed-FW check + bench probe (run in a subprocess).

Usage: python -m repro.launch.fw_dist_check [--devices 8] [--n 256] [--bs 32]

With ``JAX_PLATFORMS=cpu`` it sets XLA_FLAGS *before* importing jax to get
``--devices`` virtual host devices; on an accelerator it builds the mesh
from the real devices.  It then verifies the distributed solve.  Exit code
0 on success.  Modes:

  (default)        fw_distributed == fw_naive (allclose) — the legacy check.
  --bitwise        distributed == the single-device fused solve, BITWISE —
                   exercised per --semiring and --dtype (the owner-echo
                   guarantee of kernels.fw_round_bordered).
  --method solve   route through apsp.solve(method="distributed") — also
                   exercises the auto-padding of plan.distributed_plan for
                   non-divisible n (e.g. --n 96).
  --method engine  route a ragged batch through ApspEngine(mesh=...).
                   solve_many + assert the warm cache retraces nothing.
  --repair         distributed ApspEngine.repair (the shard-mapped rank-1
                   per-edge sweep) == single-device repair == full re-solve,
                   bitwise, per --semiring/--dtype (+ --packed lanes);
                   warm repair cache must not retrace.
  --repair-del     distributed ApspEngine.repair_del (batched edge-deletion
                   mark + restricted row sweep) == single-device repair_del
                   == full re-solve, bitwise; warm cache must not retrace.
  --bench          time the per-round dispatch and measure the collective
                   bytes in the compiled per-round HLO against the SUMMA
                   model (plan.dist_round_comm_bytes /
                   plan.summa_comm_bound_bytes); prints a ``METRICS {json}``
                   line benchmarks.run parses into BENCH_fw.json.

tests/test_distributed.py drives the bitwise matrix (5 semirings × 2
dtypes); .github/workflows/ci.yml runs the 8-virtual-device smoke.
"""
import argparse
import json
import os
import sys
import time


def collective_bytes(hlo: str) -> float:
    """Sum the per-device collective operand bytes in an HLO dump.

    The "measured" side of the comm-efficiency number: what the compiled
    program actually moves per call, vs what the SUMMA model says it
    should.  Delegates to ``launch.roofline.parse_collective_bytes`` (the
    one HLO collective parser in the repo — operand-based, so async
    -start/-done pairs count once).
    """
    from repro.launch import roofline

    return sum(roofline.parse_collective_bytes(hlo).values())


def _graph_for(semiring: str, n: int, seed: int = 0):
    """Per-semiring test input: ⊗ must not overflow under closure."""
    import numpy as np

    rng = np.random.default_rng(seed)
    if semiring == "plus_mul":
        # Non-idempotent ⊕ sums products over every path; tiny weights with
        # no unit self-loops keep the closure finite (a 1.0 diagonal makes
        # path counts — and the values — blow up to inf within a few
        # rounds), so bitwise comparisons compare numbers, not inf/NaN.
        return rng.uniform(1e-3, 1e-2, (n, n)).astype(np.float32)
    if semiring == "or_and":
        w = (rng.uniform(0, 1, (n, n)) < 0.05).astype(np.float32)
        np.fill_diagonal(w, 1.0)
        return w
    from repro.core.graph import random_digraph

    return random_digraph(n, density=0.3, seed=seed)


def bench_metrics(mesh, w, sr, *, bs: int, backend: str = "fused",
                  row_axes="data", pods: int = 1) -> dict:
    """Per-round and whole-solve time of the distributed solve of ``w`` on
    ``mesh``, plus the collective bytes of the compiled per-round program
    against the SUMMA model.  Runs in the calling process, on whatever
    devices the mesh holds (``benchmarks.run`` calls it in-process on a
    chip, through this CLI's subprocess on virtual host devices)."""
    import jax
    import jax.numpy as jnp

    from repro.apsp import plan
    from repro.apsp.api import _pad
    from repro.core.distributed import build_fw_shard_fn

    n = w.shape[-1]
    ndev = mesh.devices.size
    R, C = plan.mesh_factorization(ndev, pods)
    dp = plan.distributed_plan(n, ndev, grid=(R, C), block_size=bs,
                               pods=pods, word=jnp.dtype(w.dtype).itemsize)
    s, m = dp["block_size"], dp["n_padded"]
    wp = _pad(w, m, sr)
    sharded, sharding = build_fw_shard_fn(
        mesh, m, block_size=s, row_axes=row_axes, col_axes="model",
        semiring=sr, backend=backend,
    )
    step = jax.jit(sharded)
    wl = jax.device_put(wp, sharding)
    # One AOT compile serves both the HLO dump and the timed calls (a
    # plain step() afterwards would recompile — the jit dispatch cache
    # is not populated by lower().compile()).
    compiled = step.lower(wl, jnp.int32(0), jnp.int32(1)).compile()
    measured = collective_bytes(compiled.as_text())
    rounds = dp["rounds"]
    out = compiled(wl, jnp.int32(0), jnp.int32(1))  # warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    cur = wl
    for b in range(rounds):
        cur = compiled(cur, jnp.int32(b), jnp.int32(1))
    jax.block_until_ready(cur)
    round_ms = (time.perf_counter() - t0) / rounds * 1e3
    # Whole solve measured as ONE jitted all-rounds call (what
    # fw_distributed/ApspEngine actually dispatch) — not rounds ×
    # round_ms, which would double-count per-call overhead.
    full = step.lower(wl, jnp.int32(0), jnp.int32(rounds)).compile()
    jax.block_until_ready(full(wl, jnp.int32(0), jnp.int32(rounds)))
    t0 = time.perf_counter()
    jax.block_until_ready(full(wl, jnp.int32(0), jnp.int32(rounds)))
    solve_ms = (time.perf_counter() - t0) * 1e3
    bound_round = dp["summa_bound_bytes"] / rounds
    return dict(
        ndev=ndev, R=R, C=C, n=n, n_padded=m, bs=s,
        backend=backend, rounds=rounds, round_ms=round_ms,
        solve_ms=solve_ms,
        comm_measured_bytes=measured,
        comm_model_bytes=dp["comm_bytes_per_round"],
        summa_bound_bytes_per_round=bound_round,
        comm_efficiency_measured=(bound_round / measured) if measured else None,
        comm_efficiency_model=dp["comm_model_efficiency"],
    )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--bs", type=int, default=32)
    ap.add_argument("--backend", default="fused",
                    choices=["fused", "jnp", "pallas"])
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--semiring", default="min_plus")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16", "int16"])
    ap.add_argument("--method", default="direct",
                    choices=["direct", "solve", "engine"])
    ap.add_argument("--batch", type=int, default=1,
                    help="solve mode: close B graphs through one sharded batch")
    ap.add_argument("--bitwise", action="store_true",
                    help="compare against the single-device fused solve, bitwise")
    ap.add_argument("--repair", action="store_true",
                    help="distributed ApspEngine.repair == single-device "
                         "repair == full re-solve, bitwise")
    ap.add_argument("--repair-del", action="store_true", dest="repair_del",
                    help="distributed ApspEngine.repair_del (batched edge "
                         "deletion) == single-device repair_del == full "
                         "re-solve, bitwise")
    ap.add_argument("--packed", action="store_true",
                    help="repair mode: bit-packed or_and int32 lanes")
    ap.add_argument("--bench", action="store_true",
                    help="emit METRICS json (per-round ms + comm bytes)")
    ap.add_argument("--chunked", action="store_true", help="exercise checkpoint chunking")
    ap.add_argument("--phase2-shard", action="store_true")
    args = ap.parse_args()

    # CPU runs get --devices virtual host devices; on an accelerator the
    # mesh is built from the real devices and --devices is ignored.
    host = os.environ.get("JAX_PLATFORMS") == "cpu"
    if host:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", "")
        )
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.apsp import ApspEngine, plan, solve
    from repro.core import fw_naive
    from repro.core.distributed import build_fw_shard_fn, fw_distributed
    from repro.core.semiring import SEMIRINGS
    from repro.launch.mesh import make_host_mesh
    from repro.utils.compat import enable_compile_cache

    enable_compile_cache()
    ndev = len(jax.devices())
    if host:
        assert ndev == args.devices, (ndev, args.devices)
    args.devices = ndev
    # make_host_mesh builds from apsp.plan.mesh_factorization — the same
    # (R, C) grid benchmarks use to derive the SUMMA comm bound.
    mesh = make_host_mesh(args.devices, pods=args.pods)
    row_axes = ("pod", "data") if args.pods > 1 else "data"
    sr = SEMIRINGS[args.semiring]
    dtype = jnp.dtype(args.dtype)
    R, C = plan.mesh_factorization(args.devices, args.pods)

    if not (args.repair or args.repair_del):
        # repair modes build their own per-scenario inputs
        w = jnp.asarray(_graph_for(args.semiring, args.n, seed=0), dtype)
    if args.batch > 1:
        # (--bitwise too: the naive oracle of the default mode is not
        # batch-aware, so the only meaningful batched check is the bitwise
        # diff against the batched single-device fused solve.)
        assert args.method == "solve" and args.bitwise, \
            "--batch needs --method solve --bitwise"
        w = jnp.stack([
            jnp.asarray(_graph_for(args.semiring, args.n, seed=i), dtype)
            for i in range(args.batch)
        ])

    if args.repair:
        # Distributed rank-1 repair (core.distributed.build_repair_shard_fn,
        # a shard-mapped per-edge ⊕-broadcast sweep) must reproduce BOTH the
        # single-device repair and a full re-solve of the updated graph,
        # bitwise — per semiring, storage lowering, and the packed planes.
        from repro.apsp import pack_reachability
        from repro.core.semiring import I16_INF
        from repro.launch.fw_serve import _apply_updates, repair_scenario

        if args.packed:
            rng = np.random.default_rng(9)
            Bs = rng.uniform(size=(2, args.n, args.n)) < 0.05
            Bs[:, np.arange(args.n), np.arange(args.n)] = True
            w0 = np.asarray(pack_reachability(Bs.astype(np.float32)))
            upd = [(3, 7, 1 << 0), (args.n - 8, 9, 0b11)]
            B1 = Bs.copy()
            B1[0, 3, 7] = True
            B1[:, args.n - 8, 9] = True
            w1 = np.asarray(pack_reachability(B1.astype(np.float32)))
            kw = dict(semiring="or_and", packed=True, validate=False)
            baseline = "fused"
        elif args.dtype == "int16":
            assert args.semiring == "min_plus", "int16 repair: min_plus only"
            rng = np.random.default_rng(1)
            w0 = rng.integers(1, 997, (args.n, args.n)).astype(np.int16)
            w0[rng.uniform(size=(args.n, args.n)) > 0.4] = I16_INF
            np.fill_diagonal(w0, 0)
            upd = [(3, 7, 1), (10, 2, 2)]
            w1 = w0.copy()
            for u_, v_, d_ in upd:
                w1[u_, v_] = min(int(w1[u_, v_]), d_)
            # dtype pins the saturating int16 lowering at construction —
            # without it the engine promotes int inputs to f32.
            kw = dict(semiring=sr, dtype=jnp.int16, validate=False)
            baseline = "fused"
        else:
            w0, upd, baseline = repair_scenario(args.semiring, args.n)
            w1 = _apply_updates(w0, upd, args.semiring)
            kw = dict(semiring=sr, validate=False)
        single = ApspEngine(method=baseline, **kw)
        dist = ApspEngine(method="distributed", mesh=mesh, row_axes=row_axes,
                          **kw)
        r0 = single.solve(w0)
        rs = np.asarray(single.repair(r0.dist, upd).dist)
        rd = np.asarray(dist.repair(r0.dist, upd).dist)
        want = np.asarray(single.solve(w1).dist)
        if not np.array_equal(rd, rs, equal_nan=True):
            print("FAIL distributed repair != single-device repair",
                  file=sys.stderr)
            return 1
        if not np.array_equal(rs, want, equal_nan=True):
            print("FAIL repair != full re-solve", file=sys.stderr)
            return 1
        dist.repair(r0.dist, upd)  # warm pass: no retrace
        traces = [e.traces for e in dist._cache.values()]
        assert all(t == 1 for t in traces), f"repair cache retraced: {traces}"
        print(f"OK repair devices={ndev} mesh={dict(mesh.shape)} n={args.n} "
              f"semiring={args.semiring} dtype={args.dtype} "
              f"packed={args.packed} edges={len(upd)}")
        return 0

    if args.repair_del:
        # Decremental (edge-deletion) repair under a device mesh.  The
        # distributed engine's repair_del runs the mark + restricted row
        # sweep locally (the strip is too small to amortize collectives);
        # what the mesh guarantees is that the *baseline closure* it starts
        # from — the distributed solve — is bitwise-identical to the
        # single-device one, so mesh repair_del == single-device repair_del
        # == a full distributed re-solve of the deleted graph, bitwise.
        from repro.launch.fw_serve import pick_deletions, repair_scenario

        w0, _, baseline = repair_scenario(args.semiring, args.n)
        w0 = np.asarray(w0, dtype)
        kw = dict(semiring=sr, validate=False)
        single = ApspEngine(method=baseline, **kw)
        dist = ApspEngine(method="distributed", mesh=mesh, row_axes=row_axes,
                          **kw)
        r0s = single.solve(w0)
        if args.semiring != "plus_mul":
            # for plus_mul the baseline is method="naive" (the only closure
            # a non-idempotent ⊕ admits) and the blocked distributed solve
            # legitimately differs — repairs start from the baseline
            # closure either way, exactly like the --repair mode.
            r0d = dist.solve(w0)
            if not np.array_equal(np.asarray(r0d.dist),
                                  np.asarray(r0s.dist), equal_nan=True):
                print("FAIL distributed solve != single-device solve",
                      file=sys.stderr)
                return 1
        dels, w1 = pick_deletions(w0, r0s.dist, args.semiring)
        if not dels:
            # plus_mul: the path-sum closure rarely equals any single edge,
            # so no on-path pick exists — any deleted edge exercises the
            # fallback arm just as well.
            for u_, v_ in np.argwhere(w0 != sr.zero):
                if u_ != v_:
                    dels = [(int(u_), int(v_), float(w0[u_, v_]))]
                    w1 = np.array(w0, copy=True)
                    w1[u_, v_] = sr.zero
                    break
        # threshold forced high: at smoke sizes a deletion touches most
        # rows, and the byte model would (correctly) pick the re-solve arm;
        # the parity check wants the sweep arm exercised.
        rd = np.asarray(dist.repair_del(r0s.dist, w1, dels,
                                        threshold=100.0).dist)
        rs = np.asarray(single.repair_del(r0s.dist, w1, dels,
                                          threshold=100.0).dist)
        want = np.asarray(single.solve(w1).dist)
        if args.semiring == "plus_mul":
            # non-idempotent ⊕: repair_del's documented full-solve fallback
            # re-solves with the engine's OWN method (naive baseline vs the
            # blocked distributed solve, which legitimately differ for a
            # path-sum ⊕) — the guarantee is repair_del == that engine's
            # own full re-solve of the deleted graph.
            assert dist.stats.repair_del_fallbacks >= 1, "fallback not taken"
            if not np.array_equal(rd, np.asarray(dist.solve(w1).dist),
                                  equal_nan=True):
                print("FAIL distributed repair_del != distributed re-solve",
                      file=sys.stderr)
                return 1
            if not np.array_equal(rs, want, equal_nan=True):
                print("FAIL repair_del != full re-solve", file=sys.stderr)
                return 1
        else:
            if not np.array_equal(rd, rs, equal_nan=True):
                print("FAIL distributed repair_del != single-device "
                      "repair_del", file=sys.stderr)
                return 1
            if not np.array_equal(rs, want, equal_nan=True):
                print("FAIL repair_del != full re-solve", file=sys.stderr)
                return 1
            assert dist.stats.repair_dels >= 1, "sweep arm was not taken"
            dist.repair_del(r0s.dist, w1, dels,
                            threshold=100.0)  # warm: no retrace
            traces = [e.traces for e in dist._cache.values()
                      if e.key.method.startswith("repair_del")]
            assert traces and all(t == 1 for t in traces), \
                f"repair_del cache retraced: {traces}"
        print(f"OK repair_del devices={ndev} mesh={dict(mesh.shape)} "
              f"n={args.n} semiring={args.semiring} dtype={args.dtype} "
              f"edges={len(dels)}")
        return 0

    if args.bench:
        metrics = bench_metrics(
            mesh, w, sr, bs=args.bs, backend=args.backend,
            row_axes=row_axes, pods=args.pods,
        )
        print("METRICS " + json.dumps(metrics))
        print(f"OK bench ndev={ndev} n={args.n} bs={metrics['bs']} "
              f"backend={args.backend}")
        return 0

    if args.method == "engine":
        # Ragged batch through the mesh-keyed plan cache; every graph must
        # bit-match its single-device fused solve, and a second pass must
        # hit the warm cache without retracing.
        eng = ApspEngine(method="distributed", mesh=mesh, row_axes=row_axes,
                         semiring=sr, block_size=args.bs, validate=False)
        sizes = [args.n, max(args.n // 2, 2 * args.bs), args.n]
        graphs = [
            jnp.asarray(_graph_for(args.semiring, nn, seed=i), dtype)
            for i, nn in enumerate(sizes)
        ]
        results = eng.solve_many(graphs)
        for g, r in zip(graphs, results):
            single = solve(g, method="fused", block_size=r.block_size,
                           semiring=sr, validate=False)
            ok = np.array_equal(np.asarray(r.dist), np.asarray(single.dist),
                                equal_nan=True)
            assert ok, f"engine dist != single fused at n={g.shape[-1]}"
        eng.solve_many(graphs)
        traces = [e.traces for e in eng._cache.values()]
        assert all(t == 1 for t in traces), f"warm cache retraced: {traces}"
        print(f"OK engine devices={ndev} mesh={dict(mesh.shape)} "
              f"sizes={sizes} semiring={args.semiring} dtype={args.dtype} "
              f"cache={eng.cache_size} hits={eng.stats.hits}")
        return 0

    if args.method == "solve":
        res = solve(w, method="distributed", mesh=mesh, row_axes=row_axes,
                    semiring=sr, block_size=args.bs, validate=False)
        got = np.asarray(res.dist)
        s_used, m = res.block_size, res.padded_n
    else:  # direct fw_distributed (requires mesh-divisible n)
        ckpts = []
        cb = (lambda b, wl: ckpts.append(b)) if args.chunked else None
        out = fw_distributed(
            w, mesh, block_size=args.bs, row_axes=row_axes, col_axes="model",
            semiring=sr, backend=args.backend,
            rounds_per_call=2 if args.chunked else None,
            checkpoint_cb=cb,
            phase2_shard=args.phase2_shard,
        )
        got = np.asarray(jax.device_get(out))
        s_used, m = args.bs, args.n
        if args.chunked:
            assert ckpts and ckpts[-1] == args.n // args.bs, ckpts

    if args.bitwise:
        single = solve(w, method="fused", block_size=s_used, semiring=sr,
                       validate=False)
        want = np.asarray(single.dist)
        if args.method == "direct":
            want = np.asarray(_pad_like(want, m, sr, jnp))
        if not np.array_equal(got, want, equal_nan=True):
            bad = np.flatnonzero(got != want)
            print(f"FAIL bitwise: {bad.size} mismatching elements", file=sys.stderr)
            return 1
        print(f"OK bitwise devices={ndev} mesh={dict(mesh.shape)} n={args.n} "
              f"bs={s_used} method={args.method} backend={args.backend} "
              f"semiring={args.semiring} dtype={args.dtype} padded={m}")
        return 0

    want = np.asarray(fw_naive(w, semiring=sr))
    np.testing.assert_allclose(
        got[: args.n, : args.n], want, rtol=2e-5, atol=2e-5
    )
    print(f"OK devices={ndev} mesh={dict(mesh.shape)} n={args.n} bs={args.bs} "
          f"backend={args.backend} p2shard={args.phase2_shard} "
          f"chunks={len(ckpts) if args.chunked else 0}")
    return 0


def _pad_like(want, m, sr, jnp):
    """Pad the single-device oracle to the distributed padded size for a
    direct-mode bitwise diff (solve-mode results are already unpadded)."""
    from repro.apsp.api import _pad

    return _pad(jnp.asarray(want), m, sr)


if __name__ == "__main__":
    sys.exit(main())
