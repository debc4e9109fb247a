"""Benchmark entrypoint: one function per paper table/figure.

Prints ``name,params,us_per_call,derived`` CSV rows and writes the same
numbers to ``BENCH_fw.json`` (name[params] → us_per_call) so the perf
trajectory is machine-trackable across PRs.

  fw_table1        — the paper's Table 1 implementation ladder
  fw_scaling       — the paper's Figure 7 growth curve (time vs n³ fit)
  fw_batched       — batched solve() ladder (many small graphs at once):
                     sequential loop vs natively batched blocked FW vs the
                     fused round's native batch grid vs a warm ApspEngine
                     cache
  fw_dist          — distributed FW ladder (in-process on the real devices;
                     a subprocess on 8 virtual host devices on the CPU):
                     per-round ms for the fused bordered round vs the
                     per-phase lowering, whole-solve wall, and the
                     measured-vs-model SUMMA comm efficiency (collective
                     bytes parsed from the compiled HLO)
  kernel_sweep     — staged phase-3 kernel parameter sweep (interpret
                     correctness + VMEM-footprint arithmetic; see
                     EXPERIMENTS.md §Perf for the roofline-side analysis)
  fw_fused         — the fused one-dispatch-per-round kernel at the Table-1
                     sizes (+ achieved-bandwidth, int16/bf16 dtype rows, and
                     backend=gpu_interp rows running the Triton lowering
                     through the Pallas interpreter), plus the
                     plan.autotune_fw measured sweep over
                     (block_size, bm, bn, bk) round configs
  fw_packed        — bit-packed or_and transitive closure (32 graphs per
                     int32 lane) vs unpacked f32 or_and at n=1024
  fw_repair        — rank-1 incremental repair (ApspEngine.repair) vs the
                     full fused re-solve at n=1024 (single-edge and batched
                     16-edge dispatches; acceptance bar: repair ≥ 5×)
  serve_qps        — mixed query/update load through the layered serving
                     stack (serve/routing.py): per-query p50/p99 + QPS,
                     repair-vs-resolve refresh split in the derived column
  fw_oocore        — out-of-core recursive (R-Kleene) ladder: in-core
                     recursive vs fused at n∈{512,1024}, a capped-budget
                     streamed solve whose matrix exceeds the configured
                     HBM budget, and transfer_efficiency_pct = modeled /
                     measured host↔device stream bytes (×100)

Every run stamps a ``_meta`` entry (JAX backend + device kind) into
BENCH_fw.json so wall-clock and bandwidth numbers are always read against
the platform that produced them.

Run: PYTHONPATH=src python -m benchmarks.run [table ...]
     PYTHONPATH=src python -m benchmarks.run --smoke
       (CI guard: tiny interpret-mode correctness smoke + BENCH_fw.json
        key diff against the expected-key manifest, so a missing or
        renamed benchmark entry fails fast instead of rotting silently)
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import fw_table1
from repro.apsp import plan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSON = os.path.join(REPO, "BENCH_fw.json")


def bench_fw_table1():
    rows = []
    for name, n, sec, tps in fw_table1.run():
        rows.append((name, f"n={n}", sec * 1e6, f"{tps/1e9:.3f}Gtasks/s"))
    return rows


def bench_fw_scaling():
    """Fit t = c·n³ (the paper reports c ≈ 1.2e-11 s for its CPU)."""
    rows = []
    ns, ts = [], []
    for n in (256, 512, 1024):
        w = fw_table1.random_digraph(n, seed=n)
        t = fw_table1._time(fw_table1._rung, "blocked", w,
                            block_size=min(128, n))
        ns.append(n)
        ts.append(t)
        rows.append(("fw_scaling/blocked", f"n={n}", t * 1e6, f"{n**3/t/1e9:.2f}Gtasks/s"))
    # Least-squares fit of t = c·n³ (c = Σ n³t / Σ n⁶), recorded in
    # PICOSECONDS per task: the old row put c (seconds/task, ~1e-9 on this
    # host) through the µs column's round(·, 1) and serialized 0.0 forever.
    # Units are in the key so the number is self-describing; see
    # EXPERIMENTS.md §Scaling fit units.
    n3 = np.asarray(ns, np.float64) ** 3
    c = float(np.dot(n3, ts) / np.dot(n3, n3))
    rows.append(("fw_scaling/implied_constant", "t=c*n^3,ps", c * 1e12,
                 f"c={c:.3e}s/task"))
    return rows


def bench_fw_batched():
    """Batched solve() over B small graphs: the many-users-many-graphs cell.

    Four rungs of the same workload (B=16 routing-sized graphs):

      sequential     — B separate solve() calls (the pre-batching serving
                       loop)
      blocked_native — ONE batched blocked solve: fw_blocked's round loop
                       runs all B graphs with a leading batch dim (replaced
                       the old vmap-around-the-loop rung; the vmap wrapper
                       batched every dynamic slice individually and its
                       "regression" vs sequential was within CPU timing
                       noise — EXPERIMENTS.md §Batched)
      fused          — the round kernel's native batch grid: the batch dim
                       lives INSIDE the kernel schedule (one dispatch per
                       round for all B graphs); block 25 divides n=100 →
                       zero padding, variant="unroll" (the paper's loop
                       unrolling)
      engine_warm    — the same through a warm ApspEngine plan/executable
                       cache (the serving steady state: no re-plan, no
                       re-trace)

    The acceptance bar for the batched engine: fused ≥ 2× over sequential.
    """
    from repro.apsp import ApspEngine, solve
    from repro.core.graph import random_digraph

    rows = []
    b, n = 16, 100
    wb = np.stack([random_digraph(n, density=0.5, seed=i) for i in range(b)])
    t_batch = fw_table1._time(
        lambda: solve(wb, method="blocked", block_size=32, validate=False).dist
    )
    t_seq = fw_table1._time(
        lambda: [solve(wb[i], method="blocked", block_size=32,
                       validate=False).dist for i in range(b)][-1]
    )
    t_fused = fw_table1._time(
        lambda: solve(wb, method="fused", block_size=25, variant="unroll",
                      validate=False).dist
    )
    eng = ApspEngine(method="fused", block_size=25, variant="unroll",
                     validate=False)
    eng.solve(wb)  # plan + compile once; the steady state is all cache hits
    t_eng = fw_table1._time(lambda: eng.solve(wb).dist)
    rows.append(("fw_batched/blocked_native", f"B={b},n={n}", t_batch * 1e6,
                 f"{b*n**3/t_batch/1e9:.2f}Gtasks/s"))
    rows.append(("fw_batched/sequential", f"B={b},n={n}", t_seq * 1e6,
                 f"speedup={t_seq/t_batch:.1f}x_vs_blocked_native"))
    rows.append(("fw_batched/fused", f"B={b},n={n}", t_fused * 1e6,
                 f"speedup={t_seq/t_fused:.1f}x_vs_sequential"))
    rows.append(("fw_batched/engine_warm", f"B={b},n={n}", t_eng * 1e6,
                 f"speedup={t_seq/t_eng:.1f}x_vs_sequential,"
                 f"hits={eng.stats.hits}"))
    return rows


DIST_NDEV, DIST_N, DIST_BS = 8, 512, 64


def _dist_metrics(backend: str) -> dict:
    """``fw_dist_check.bench_metrics`` for the distributed ladder.

    On an accelerator it runs in this process on the real devices (one
    process holds the chips).  On the CPU it runs ``fw_dist_check --bench``
    in a subprocess on DIST_NDEV virtual host devices, because the host
    device count is locked at first jax init and this process keeps one.
    """
    if jax.default_backend() != "cpu":
        from repro.core.semiring import MIN_PLUS
        from repro.launch.fw_dist_check import _graph_for, bench_metrics
        from repro.launch.mesh import make_host_mesh

        w = jnp.asarray(_graph_for("min_plus", DIST_N, seed=0))
        return bench_metrics(make_host_mesh(), w, MIN_PLUS, bs=DIST_BS,
                             backend=backend)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.fw_dist_check",
         "--devices", str(DIST_NDEV), "--n", str(DIST_N),
         "--bs", str(DIST_BS), "--backend", backend, "--bench"],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
    )
    if res.returncode != 0:
        raise RuntimeError(
            f"fw_dist_check --bench ({backend}) failed:\n{res.stdout}\n{res.stderr}"
        )
    for line in res.stdout.splitlines():
        if line.startswith("METRICS "):
            return json.loads(line[len("METRICS "):])
    raise RuntimeError(f"no METRICS line in fw_dist_check output:\n{res.stdout}")


def bench_fw_dist():
    """Distributed FW ladder on 8 host devices: per-round time + comm check.

    Replaces the old bare ``dist_fw/OK`` success flag with numbers the perf
    trajectory can track:

      round_ms_fused  — per-round wall time, fused bordered round/device
      round_ms_phases — per-round wall time, per-phase jnp lowering
      solve           — whole-solve wall time, fused path, measured as ONE
                        jitted all-rounds call (what solve/engine dispatch)
      comm_efficiency_pct — SUMMA lower bound / collective bytes actually
                        found in the compiled per-round HLO (×100; the
                        measured-vs-model check of plan.dist_round_comm_bytes
                        — derived column shows both byte counts)

    Absolute times are host-CPU (collectives are memcpys); the comm bytes
    and the fused-vs-phases ratio are the portable signals.
    """
    rows = []
    fused = _dist_metrics("fused")
    phases = _dist_metrics("jnp")
    params = f"ndev={fused['ndev']},n={DIST_N},bs={DIST_BS}"
    rows.append((f"fw_dist/round_ms_fused", params, fused["round_ms"] * 1e3,
                 f"{fused['rounds']}rounds,1disp/round"))
    rows.append((f"fw_dist/round_ms_phases", params, phases["round_ms"] * 1e3,
                 f"{phases['rounds']}rounds,"
                 f"speedup={phases['round_ms']/fused['round_ms']:.2f}x_fused"))
    rows.append((f"fw_dist/solve", params, fused["solve_ms"] * 1e3,
                 f"{DIST_N**3/(fused['solve_ms']*1e-3)/1e9:.2f}Gtasks/s"))
    eff = fused["comm_efficiency_measured"]
    rows.append((f"fw_dist/comm_efficiency_pct", params,
                 (eff or 0.0) * 100.0,
                 f"measured={fused['comm_measured_bytes']}B,"
                 f"model={fused['comm_model_bytes']:.0f}B,"
                 f"bound={fused['summa_bound_bytes_per_round']:.0f}B/round"))
    return rows


def bench_kernel_sweep():
    """Staged kernel: correctness across staging depths + VMEM footprint."""
    from repro.kernels.minplus_matmul import semiring_matmul
    from repro.kernels.ref import semiring_matmul_ref

    rows = []
    n = 256
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.uniform(0, 10, (n, n)).astype(np.float32))
    b = jnp.asarray(rng.uniform(0, 10, (n, n)).astype(np.float32))
    want = np.asarray(semiring_matmul_ref(a, b))
    for bk in (8, 16, 32, 64, 128):
        t0 = time.perf_counter()
        got = semiring_matmul(a, b, bm=128, bn=128, bk=bk, interpret=True)
        jax.block_until_ready(got)
        dt = time.perf_counter() - t0
        ok = np.allclose(np.asarray(got), want)
        vmem = plan.phase3_vmem_bytes(128, 128, bk)
        rows.append((f"kernel_sweep/bk{bk}_{'ok' if ok else 'MISMATCH'}",
                     f"bm=bn=128,bk={bk}", dt * 1e6, f"vmem={vmem/1024:.0f}KB"))
    return rows


FUSED_SIZES = (256, 512, 1024)
SWEEP_N = 256
# Narrow-dtype ladder: the bandwidth-lean lowerings at the small and large
# Table-1 sizes (ISSUE 6 — bytes-per-round as a planning axis).
DTYPE_SIZES = (256, 1024)
DTYPES = ("int16", "bfloat16")
# Backend-parity ladder (ISSUE 9): the same fused solve through the Triton
# round in Pallas interpret mode — what a GPU-less container can execute.
# The wall number tracks the interpreter; the bitwise gpu==ref guard lives
# in --smoke and tests/test_fw_round_gpu.py.
GPU_INTERP_SIZES = (256, 512)


def _sweep_cfgs():
    """Deterministic autotune-sweep configs (the key manifest derives from
    this, so a changed sweep shows up as a key diff, not silent drift)."""
    cands = plan.fw_candidates(SWEEP_N, block_sizes=(64, 128), bks=(16, 32))
    return [c for c in cands
            if c["impl"] == "fused" or c["bm"] == c["block_size"]]


def _cfg_key(c) -> str:
    return (f"fw_fused/sweep_{c['impl']}_s{c['block_size']}"
            f"_bm{c['bm']}_bk{c['bk']}[n={SWEEP_N}]")


def bench_fw_fused():
    """Fused round kernel: Table-1 sizes + achieved bandwidth + the
    narrow-dtype ladder + the autotune sweep.

    Wall-times are interpret-mode on CPU (XLA-compiled trace of the kernel,
    not Mosaic) — comparable across rungs here, but the TPU numbers are the
    ones the paper's 5× claim lives on.  Derived column: dispatches/round.

    ``hbm_gbps`` rows turn "the round is bandwidth-bound" into a number:
    modeled solve bytes (``plan.fused_solve_hbm_bytes``) over measured wall
    time.  The dtype rows run the same fused solve through the int16
    (saturating tropical) and bf16 storage lowerings — on hardware, half
    the bytes per round; here the wall numbers track the CPU ref lowering.
    """
    from repro.apsp import solve
    from repro.core.graph import random_digraph
    from repro.core.staged import fw_staged

    rows = []
    for n in FUSED_SIZES:
        w = random_digraph(n, density=1.0, seed=n)
        s = min(128, n)
        # min over 2 reps at n=1024: the first warm interpret-mode call pays
        # one-off XLA CPU autotuning/paging (~2× the steady state).
        reps = 2 if n >= 1024 else 3
        t = fw_table1._time(fw_table1._rung, "fused", w,
                            block_size=s, reps=reps)
        rows.append(("fw_fused/solve", f"n={n}", t * 1e6,
                     f"{n**3/t/1e9:.2f}Gtasks/s,1disp/round"))
        # Bandwidth rows carry the backend that produced them: on the CPU
        # container these are XLA-ref wall-clocks, NOT a TPU HBM roofline —
        # the _meta stamp in BENCH_fw.json says the same on the JSON side.
        rows.append(("fw_fused/hbm_gbps", f"n={n}",
                     plan.achieved_hbm_gbps(n, s, t),
                     f"model={plan.fused_solve_hbm_bytes(n, s)/1e6:.0f}"
                     f"MB/solve,f32,backend={jax.default_backend()}"))
        if n in DTYPE_SIZES:
            for dname in DTYPES:
                dt = {"int16": jnp.int16, "bfloat16": jnp.bfloat16}[dname]
                td = fw_table1._time(
                    lambda w=w, s=s, dt=dt: solve(
                        w, method="fused", block_size=s, dtype=dt,
                        validate=False,
                    ).dist,
                    reps=reps,
                )
                rows.append((
                    "fw_fused/solve", f"n={n},dtype={dname}", td * 1e6,
                    f"{n**3/td/1e9:.2f}Gtasks/s,word="
                    f"{plan.word_for(dname)}B",
                ))

    # Backend-parity rows: the Triton lowering of the fused round, run
    # through the Pallas interpreter (no GPU attached here).  Keyed by
    # backend= so the TPU/GPU rows never collide in BENCH_fw.json.
    for n in GPU_INTERP_SIZES:
        w = random_digraph(n, density=1.0, seed=n)
        s = min(128, n)
        tg = fw_table1._time(
            lambda w=w, s=s: solve(
                w, method="fused", block_size=s, backend="gpu",
                validate=False,
            ).dist,
        )
        rows.append(("fw_fused/solve", f"backend=gpu_interp,n={n}", tg * 1e6,
                     f"{n**3/tg/1e9:.2f}Gtasks/s,triton_interpret"))

    # plan.autotune_fw measured sweep: both round lowerings, ranked.
    w = jnp.asarray(random_digraph(SWEEP_N, density=1.0, seed=SWEEP_N))

    def _measure(c):
        return fw_table1._time(
            lambda: fw_staged(
                w, block_size=c["block_size"], bm=c["bm"], bn=c["bn"],
                bk=c["bk"], fused=c["impl"] == "fused",
                interpret=True,
            ),
        )

    cfgs = _sweep_cfgs()
    for c in cfgs:
        c["us"] = _measure(c) * 1e6
    best = min(cfgs, key=lambda c: c["us"])
    for c in cfgs:
        flag = "best," if c is best else ""
        rows.append((_cfg_key(c).split("[")[0], f"n={SWEEP_N}", c["us"],
                     f"{flag}{c['dispatches_per_round']}disp,"
                     f"vmem={c['vmem_bytes']/1024:.0f}KB,"
                     f"backend={c['backend']}"))
    return rows


PACKED_N, PACKED_B = 1024, 32


def bench_fw_packed():
    """Bit-packed or_and closure vs unpacked f32 or_and at n=1024.

    The tentpole number of ISSUE 6: one packed int32 solve closes 32
    independent reachability graphs in the SAME matrix footprint (and byte
    traffic) an unpacked f32 solve spends on one.  Rows:

      unpacked_f32      — one graph, or_and on {0,1} f32 (the old mode)
      packed_i32        — 32 graphs via solve(packed=True): pack → one
                          bitwise fused closure → unpack, timed end-to-end
      per_graph_speedup — unpacked time / (packed time / 32); the
                          acceptance bar is ≥8×, the byte model says ~32×
                          minus pack/unpack overhead
    """
    from repro.apsp import solve

    rows = []
    rng = np.random.default_rng(7)
    # Sparse enough that the closure is non-trivial, dense enough that the
    # giant component spans — representative transitive-closure work.
    g1 = (rng.uniform(size=(PACKED_N, PACKED_N)) < 0.005).astype(np.float32)
    gb = (rng.uniform(size=(PACKED_B, PACKED_N, PACKED_N)) < 0.005).astype(
        np.float32
    )
    t_un = fw_table1._time(
        lambda: solve(g1, method="fused", block_size=128, semiring="or_and",
                      validate=False).dist, reps=2,
    )
    t_pk = fw_table1._time(
        lambda: solve(gb, method="fused", block_size=128, semiring="or_and",
                      packed=True, validate=False).dist, reps=2,
    )
    speedup = t_un / (t_pk / PACKED_B)
    rows.append(("fw_packed/unpacked_f32", f"B=1,n={PACKED_N}", t_un * 1e6,
                 f"{PACKED_N**3/t_un/1e9:.2f}Gtasks/s"))
    rows.append(("fw_packed/packed_i32", f"B={PACKED_B},n={PACKED_N}",
                 t_pk * 1e6,
                 f"{PACKED_B*PACKED_N**3/t_pk/1e9:.2f}Gtasks/s,32lanes/word"))
    rows.append(("fw_packed/per_graph_speedup", f"n={PACKED_N}", speedup,
                 f"target>=8x,packed_per_graph={t_pk/PACKED_B*1e6:.0f}us"))
    return rows


REPAIR_N = 1024


def bench_fw_repair():
    """Rank-1 incremental repair vs full fused re-solve at n=1024.

    The serving fast path of ISSUE 7: absorbing E ⊕-improving edge updates
    into an existing closure is O(E·n²) HBM traffic against the full
    solve's O(n³/s·n²)-ish rounds.  Rows:

      full_resolve — the fused one-dispatch-per-round solve (the refresh
                     cost a repair avoids)
      repair_e1    — one warm single-edge repair dispatch
      repair_e16   — a batched 16-edge update set through one dispatch
      speedup      — full_resolve / repair_e1; acceptance bar ≥ 5×, the
                     byte model (plan.repair_hbm_bytes vs
                     plan.fused_solve_hbm_bytes) predicts ~n/(2s)·rounds
    """
    from repro.apsp import ApspEngine
    from repro.core.graph import random_digraph

    rows = []
    n = REPAIR_N
    w = random_digraph(n, density=1.0, seed=n)
    eng = ApspEngine(method="fused", validate=False)
    r0 = eng.solve(w)
    t_solve = fw_table1._time(lambda: eng.solve(w).dist, reps=2)
    upd1 = [(3, 7, 1e-3)]
    upd16 = [(i, (i * 37 + 11) % n, 1e-3 + i * 1e-6) for i in range(16)]
    eng.repair(r0.dist, upd1)  # compile once; steady state is cache hits
    t_e1 = fw_table1._time(lambda: eng.repair(r0.dist, upd1).dist, reps=3)
    eng.repair(r0.dist, upd16)
    t_e16 = fw_table1._time(lambda: eng.repair(r0.dist, upd16).dist, reps=3)
    s = r0.block_size
    rows.append(("fw_repair/full_resolve", f"n={n}", t_solve * 1e6,
                 f"{n**3/t_solve/1e9:.2f}Gtasks/s"))
    rows.append(("fw_repair/repair_e1", f"n={n}", t_e1 * 1e6,
                 f"model={plan.repair_hbm_bytes(n, s, edges=1)/1e6:.1f}MB"))
    rows.append(("fw_repair/repair_e16", f"n={n}", t_e16 * 1e6,
                 f"model={plan.repair_hbm_bytes(n, s, edges=16)/1e6:.1f}MB"))
    rows.append(("fw_repair/speedup", f"n={n}", t_solve / t_e1,
                 f"target>=5x,e16={t_solve/t_e16:.1f}x"))
    return rows


def bench_fw_repair_del():
    """Decremental (edge-deletion) repair vs full fused re-solve at n=1024.

    The ISSUE 10 fast path: after deleting an edge that only a small
    fraction of shortest paths route through, the two-stage repair (mark
    the affected rows, then re-relax just that row strip through the
    restricted fused sweep) beats re-running the full solve.  The edge is
    chosen by sampling on-shortest-path candidates (``w[u,v] == dist[u,v]``)
    and keeping the one whose witness count is smallest but nonzero, so the
    measured point sits squarely in the regime the byte model
    (plan.repair_del_hbm_bytes vs plan.fused_solve_hbm_bytes) says repair
    should win.  Rows:

      full_resolve      — the fused one-dispatch-per-round solve
      repair            — warm two-stage repair_del (mark + row sweep)
      affected_fraction — share of (i,j) pairs the deletion touched
      speedup           — full_resolve / repair; acceptance bar ≥ 5× with
                          ≤ 5% of pairs affected
    """
    from repro.apsp import ApspEngine
    from repro.core.graph import random_digraph

    rows = []
    n = REPAIR_N
    w = random_digraph(n, density=1.0, seed=n)
    eng = ApspEngine(method="fused", validate=False)
    r0 = eng.solve(w)
    t_solve = fw_table1._time(lambda: eng.solve(w).dist, reps=2)
    d0 = np.asarray(r0.dist)
    w0 = np.asarray(w, dtype=d0.dtype)
    # Sample on-path edges; keep the smallest nonzero affected-pair count.
    on_path = np.argwhere(
        (w0 == d0) & np.isfinite(w0)
        & (np.arange(n)[:, None] != np.arange(n)[None, :]))
    rng = np.random.default_rng(n)
    picks = on_path[rng.choice(len(on_path), size=min(64, len(on_path)),
                               replace=False)]
    best, best_pairs = None, n * n + 1
    for u, v in picks:
        wit = d0[:, u, None] + w0[u, v] + d0[None, v, :]
        pairs = int(np.count_nonzero((wit == d0) & np.isfinite(d0)))
        if 0 < pairs < best_pairs:
            best, best_pairs = (int(u), int(v)), pairs
    u, v = best
    frac = best_pairs / (n * n)
    w1 = w0.copy()
    w1[u, v] = np.inf
    dels = [(u, v, float(w0[u, v]))]
    eng.repair_del(r0.dist, w1, dels, threshold=1.0)  # compile once
    t_rep = fw_table1._time(
        lambda: eng.repair_del(r0.dist, w1, dels, threshold=1.0).dist, reps=3)
    s = r0.block_size
    a = int(eng.stats.repair_del_rows / max(eng.stats.repair_dels, 1))
    rows.append(("fw_repair_del/full_resolve", f"n={n}", t_solve * 1e6,
                 f"{n**3/t_solve/1e9:.2f}Gtasks/s"))
    rows.append(("fw_repair_del/repair", f"n={n}", t_rep * 1e6,
                 f"model={plan.repair_del_hbm_bytes(n, s, affected_rows=a)/1e6:.1f}MB,rows={a}"))
    rows.append(("fw_repair_del/affected_fraction", f"n={n}", frac * 100,
                 f"target<=5pct,pairs={best_pairs},edge=({u},{v})"))
    rows.append(("fw_repair_del/speedup", f"n={n}", t_solve / t_rep,
                 "target>=5x"))
    return rows


SERVE_G, SERVE_N, SERVE_Q = 8, 256, 1200


def bench_serve_qps():
    """Mixed query/update serving load through the layered RoutingEngine.

    One warm registry of G graphs; a load of path queries (a quarter via
    the micro-batching scheduler) with an ⊕-improving edge update every 50
    ops, so refreshes alternate between the rank-1 repair fast path and
    full re-solves.  Rows are per-query latency percentiles (inline-query
    wall time; scheduler-batched queries amortize and are excluded from
    the percentiles) and sustained QPS; the derived column carries the
    repair/solve refresh split.  Queries mid-refresh read the previous
    published snapshot — consistency is asserted by the serve-smoke guard
    (launch/fw_serve.py --smoke), this table records the speed.
    """
    from repro.launch.fw_serve import run_load

    m = run_load(graphs=SERVE_G, n=SERVE_N, queries=SERVE_Q,
                 update_every=50, method="auto", seed=0)
    params = f"G={SERVE_G},n={SERVE_N}"
    split = (f"repairs={m['repair_refreshes']},"
             f"solves={m['solve_refreshes']},"
             f"flushes={m['batched_flushes']}")
    return [
        ("serve_qps/qps", params, m["qps"],
         f"{m['queries']}queries,{m['updates']}updates"),
        ("serve_qps/p50_us", params, m["p50_us"], split),
        ("serve_qps/p99_us", params, m["p99_us"],
         f"max_batch_seen={m['max_seen_batch']}"),
    ]


OOCORE_SIZES = (512, 1024)
OOCORE_BUDGET_N = 1024
# 2.5 MiB device budget vs the 4 MiB n=1024 f32 matrix: recursive_plan
# floors the leaf at one 128-block panel (resident ≈ 2.3 MiB) and the
# solve must genuinely stream panels through the host backing store.
OOCORE_BUDGET = 5 << 19


def bench_fw_oocore():
    """Out-of-core recursive (R-Kleene) ladder (ISSUE 8).

    Rows:

      solve_fused      — the in-core fused one-dispatch-per-round baseline
      solve_recursive  — the same solve through the R-Kleene driver
                         (leaf panels via the fused-round dataflow, outside
                         tiles via factor-snapshot min-plus contractions);
                         bitwise-equal by construction, the derived column
                         carries the overhead ratio the sweep dispatches add
      streamed         — a capped-budget solve (OOCORE_BUDGET < matrix) on
                         the host-resident backing store: panels h2d/d2h
                         through the double-buffered streamer
      transfer_efficiency_pct — modeled stream bytes / measured ×100 (the
                         schedule makes them exact; 15% is the CI band)

    Wall numbers are CPU-container refs like every other table; the byte
    counters and the recursive/fused ratio are the portable signals.
    """
    from repro.apsp import solve
    from repro.core.graph import random_digraph
    from repro.launch.fw_oocore import stream_once

    rows = []
    for n in OOCORE_SIZES:
        w = random_digraph(n, density=1.0, seed=n)
        s = min(128, n)
        rp = plan.recursive_plan(n, block_size=s)
        reps = 2
        t_f = fw_table1._time(
            lambda w=w, s=s: solve(w, method="fused", block_size=s,
                                   validate=False).dist, reps=reps)
        t_r = fw_table1._time(
            lambda w=w, s=s: solve(w, method="recursive", block_size=s,
                                   validate=False).dist, reps=reps)
        rows.append(("fw_oocore/solve_fused", f"n={n}", t_f * 1e6,
                     f"{n**3/t_f/1e9:.2f}Gtasks/s,in_core_baseline"))
        rows.append(("fw_oocore/solve_recursive", f"n={n}", t_r * 1e6,
                     f"leaf={rp['leaf']},{rp['sweep_calls']}sweeps,"
                     f"ratio={t_r/t_f:.2f}x_fused"))
    # bitwise vs fused is guarded by --smoke and tests/test_kleene.py;
    # check=False keeps the big-n bench from paying a third full solve.
    m = stream_once(OOCORE_BUDGET_N, budget=OOCORE_BUDGET, block_size=128,
                    check=False)
    rows.append((
        "fw_oocore/streamed", f"n={OOCORE_BUDGET_N},budget=2.5MB",
        m["streamed_s"] * 1e6,
        f"leaf={m['leaf']},resident={m['hbm_resident_bytes']/1e6:.1f}MB,"
        f"matrix={m['matrix_bytes']/1e6:.1f}MB"))
    model = m["model_h2d_bytes"] + m["model_d2h_bytes"]
    measured = m["measured_h2d_bytes"] + m["measured_d2h_bytes"]
    rows.append((
        "fw_oocore/transfer_efficiency_pct", f"n={OOCORE_BUDGET_N}",
        m["transfer_efficiency_pct"] or 0.0,
        f"model={model/1e6:.1f}MB,measured={measured/1e6:.1f}MB"))
    return rows


TABLES = {
    "fw_table1": bench_fw_table1,
    "fw_scaling": bench_fw_scaling,
    "fw_batched": bench_fw_batched,
    "fw_dist": bench_fw_dist,
    "kernel_sweep": bench_kernel_sweep,
    "fw_fused": bench_fw_fused,
    "fw_packed": bench_fw_packed,
    "fw_repair": bench_fw_repair,
    "fw_repair_del": bench_fw_repair_del,
    "serve_qps": bench_serve_qps,
    "fw_oocore": bench_fw_oocore,
}


def expected_keys() -> dict[str, list[str]]:
    """The key manifest: every BENCH_fw.json entry each table must produce.

    ``--smoke`` diffs this against the committed file; a benchmark that is
    renamed, dropped, or silently stops emitting a size fails CI instead of
    leaving a stale number behind.
    """
    return {
        "fw_table1": (
            [f"fw_table1/cpu_numpy[n={n}]" for n in (256, 512)]
            + [f"fw_table1/naive_harish_narayanan[n={n}]" for n in (256, 512, 1024)]
            + [f"fw_table1/blocked_katz_kider[n={n}]" for n in (256, 512, 1024)]
        ),
        "fw_scaling": (
            [f"fw_scaling/blocked[n={n}]" for n in (256, 512, 1024)]
            + ["fw_scaling/implied_constant[t=c*n^3,ps]"]
        ),
        "fw_batched": ["fw_batched/blocked_native[B=16,n=100]",
                       "fw_batched/sequential[B=16,n=100]",
                       "fw_batched/fused[B=16,n=100]",
                       "fw_batched/engine_warm[B=16,n=100]"],
        "fw_dist": [
            f"fw_dist/{k}[ndev={DIST_NDEV},n={DIST_N},bs={DIST_BS}]"
            for k in ("round_ms_fused", "round_ms_phases", "solve",
                      "comm_efficiency_pct")
        ],
        "kernel_sweep": [f"kernel_sweep/bk{bk}_ok[bm=bn=128,bk={bk}]"
                         for bk in (8, 16, 32, 64, 128)],
        "fw_fused": (
            [f"fw_fused/solve[n={n}]" for n in FUSED_SIZES]
            + [f"fw_fused/hbm_gbps[n={n}]" for n in FUSED_SIZES]
            + [f"fw_fused/solve[n={n},dtype={d}]"
               for n in DTYPE_SIZES for d in DTYPES]
            + [f"fw_fused/solve[backend=gpu_interp,n={n}]"
               for n in GPU_INTERP_SIZES]
            + [_cfg_key(c) for c in _sweep_cfgs()]
        ),
        "fw_packed": [
            f"fw_packed/unpacked_f32[B=1,n={PACKED_N}]",
            f"fw_packed/packed_i32[B={PACKED_B},n={PACKED_N}]",
            f"fw_packed/per_graph_speedup[n={PACKED_N}]",
        ],
        "fw_repair": [
            f"fw_repair/full_resolve[n={REPAIR_N}]",
            f"fw_repair/repair_e1[n={REPAIR_N}]",
            f"fw_repair/repair_e16[n={REPAIR_N}]",
            f"fw_repair/speedup[n={REPAIR_N}]",
        ],
        "fw_repair_del": [
            f"fw_repair_del/full_resolve[n={REPAIR_N}]",
            f"fw_repair_del/repair[n={REPAIR_N}]",
            f"fw_repair_del/affected_fraction[n={REPAIR_N}]",
            f"fw_repair_del/speedup[n={REPAIR_N}]",
        ],
        "serve_qps": [
            f"serve_qps/{k}[G={SERVE_G},n={SERVE_N}]"
            for k in ("qps", "p50_us", "p99_us")
        ],
        "fw_oocore": (
            [f"fw_oocore/solve_fused[n={n}]" for n in OOCORE_SIZES]
            + [f"fw_oocore/solve_recursive[n={n}]" for n in OOCORE_SIZES]
            + [f"fw_oocore/streamed[n={OOCORE_BUDGET_N},budget=2.5MB]",
               f"fw_oocore/transfer_efficiency_pct[n={OOCORE_BUDGET_N}]"]
        ),
    }


def smoke() -> None:
    """CI guard: interpret-mode correctness smoke + BENCH key diff."""
    from repro.apsp import solve
    from repro.core.floyd_warshall import fw_naive
    from repro.core.graph import random_digraph

    w = random_digraph(48, density=0.4, seed=3)  # pads 48 → 64 at s=32
    res = solve(w, method="fused", block_size=32, validate=False)
    want = np.asarray(fw_naive(jnp.asarray(w)))
    np.testing.assert_allclose(np.asarray(res.dist), want, rtol=1e-5, atol=1e-5)
    print("smoke: fused solve matches naive oracle (n=48, padded)")

    # The backend-parity guard (ISSUE 9): the Triton lowering of the fused
    # round (interpret mode here — no GPU) must reproduce the ref lowering
    # bitwise, distances and successors.
    gpu = solve(w, method="fused", block_size=32, backend="gpu",
                validate=False)
    if not np.array_equal(np.asarray(gpu.dist), np.asarray(res.dist)):
        sys.exit("smoke: Triton fused round diverges from the ref lowering")
    gs = solve(w, method="fused", block_size=32, backend="gpu",
               successors=True, validate=False)
    rs = solve(w, method="fused", block_size=32, backend="ref",
               successors=True, validate=False)
    if not (np.array_equal(np.asarray(gs.dist), np.asarray(rs.dist))
            and np.array_equal(np.asarray(gs.succ), np.asarray(rs.succ))):
        sys.exit("smoke: Triton successor round diverges from the ref "
                 "lowering")
    print("smoke: Triton fused round == ref lowering "
          "(dist AND succ, bitwise, interpret)")

    # The fw_batched guard: the fused batch grid must reproduce B separate
    # fused solves BITWISE (batching is scheduling, never numerics) and the
    # naive oracle up to tolerance.
    wb = np.stack([random_digraph(40, density=0.5, seed=i) for i in range(3)])
    batched = solve(wb, method="fused", block_size=20, validate=False)
    for i in range(wb.shape[0]):
        single = solve(wb[i], method="fused", block_size=20, validate=False)
        if not np.array_equal(np.asarray(batched.dist[i]),
                              np.asarray(single.dist)):
            sys.exit(f"smoke: batched fused solve diverges from the "
                     f"sequential per-graph solve on graph {i}")
        np.testing.assert_allclose(
            np.asarray(batched.dist[i]),
            np.asarray(fw_naive(jnp.asarray(wb[i]))), rtol=1e-5, atol=1e-5)
    print("smoke: batched fused == sequential per-graph solves (B=3, bitwise)")

    # The fw_packed guard: pack → bitwise closure → unpack must reproduce
    # per-graph unpacked or_and solves BITWISE, at a graph count that is not
    # a multiple of 32 (exercises the empty pad lanes).
    gs = np.stack([
        (np.random.default_rng(i).uniform(size=(40, 40)) < 0.1)
        .astype(np.float32) for i in range(5)
    ])
    pk = solve(gs, semiring="or_and", packed=True, method="fused",
               block_size=20, validate=False)
    for i in range(gs.shape[0]):
        up = solve(gs[i], semiring="or_and", method="fused", block_size=20,
                   validate=False)
        if not np.array_equal(np.asarray(pk.dist[i]), np.asarray(up.dist)):
            sys.exit(f"smoke: packed or_and closure diverges from the "
                     f"unpacked per-graph solve on graph {i}")
    print("smoke: packed or_and closure == unpacked per-graph solves "
          "(B=5, bitwise)")

    # The fw_repair guard: one rank-1 repair dispatch must reproduce the
    # full re-solve of the updated graph bitwise (distances AND successors;
    # the deeper per-semiring matrix lives in fw_serve --smoke and
    # tests/test_fw_repair.py).
    from repro.apsp import ApspEngine
    from repro.launch.fw_serve import _apply_updates, repair_scenario

    wr, upd, _ = repair_scenario("min_plus", 48, seed=4)
    eng = ApspEngine(method="fused", validate=False)
    r0 = eng.solve(wr, successors=True)
    rep = eng.repair(r0.dist, upd, succ=r0.succ)
    r1 = eng.solve(_apply_updates(wr, upd, "min_plus"), successors=True)
    if not (np.array_equal(np.asarray(rep.dist), np.asarray(r1.dist),
                           equal_nan=True)
            and np.array_equal(np.asarray(rep.succ), np.asarray(r1.succ))):
        sys.exit("smoke: rank-1 repair diverges from the full re-solve")
    print("smoke: rank-1 repair == full re-solve (dist AND succ, bitwise)")

    # The fw_oocore guard (ISSUE 8): the recursive (R-Kleene) schedule must
    # reproduce the fused solve bitwise, and a capped hbm_budget must
    # actually stream panels host↔device with traffic on the plan's
    # transfer-byte model (the deeper per-lowering matrix lives in
    # fw_oocore --smoke and tests/test_kleene.py).
    rec = solve(w, method="recursive", block_size=32, leaf=32, validate=False)
    if not np.array_equal(np.asarray(rec.dist), np.asarray(res.dist)):
        sys.exit("smoke: recursive solve diverges from the fused solve")
    from repro.launch.fw_oocore import stream_once

    sm = stream_once(256, budget=(256 * 256 * 4) * 6 // 10, block_size=32)
    model = sm["model_h2d_bytes"] + sm["model_d2h_bytes"]
    measured = sm["measured_h2d_bytes"] + sm["measured_d2h_bytes"]
    if not sm["out_of_core"] or measured <= 0:
        sys.exit("smoke: capped-budget solve did not stream panels")
    if abs(measured - model) > 0.15 * model:
        sys.exit(f"smoke: streamed {measured}B vs model {model}B outside 15%")
    print(f"smoke: recursive == fused (bitwise); capped budget streams "
          f"{measured}B vs model {model}B")

    if not os.path.exists(BENCH_JSON):
        sys.exit(f"smoke: {BENCH_JSON} missing — run the benchmarks first")
    with open(BENCH_JSON) as f:
        data = json.load(f)
    # The platform stamp: every committed number must say what backend
    # produced it (CPU-container refs are not a TPU roofline).
    meta = data.get("_meta")
    if not (isinstance(meta, dict) and meta.get("backend")):
        sys.exit("smoke: BENCH_fw.json lacks a _meta backend stamp — "
                 "rerun the benchmarks")
    print(f"smoke: BENCH_fw.json stamped backend={meta['backend']} "
          f"device={meta.get('device')}")
    have = {k for k in data if not k.startswith("_")}
    want_keys = {k for keys in expected_keys().values() for k in keys}
    missing = sorted(want_keys - have)
    # Every key in the file is table-produced, so anything outside the
    # manifest is stale — including leftovers of a dropped/renamed table.
    stale = sorted(have - want_keys)
    for k in missing:
        print(f"smoke: MISSING benchmark entry {k!r}", file=sys.stderr)
    for k in stale:
        print(f"smoke: STALE benchmark entry {k!r} (renamed/dropped?)",
              file=sys.stderr)
    if missing or stale:
        sys.exit(1)
    print(f"smoke: BENCH_fw.json keys match the manifest ({len(have)} entries)")


def main() -> None:
    from repro.utils.compat import enable_compile_cache

    enable_compile_cache()
    if "--smoke" in sys.argv[1:]:
        smoke()
        return
    which = sys.argv[1:] or list(TABLES)
    unknown = [t for t in which if t not in TABLES]
    if unknown:
        sys.exit(f"unknown table(s) {unknown}; have {sorted(TABLES)}")
    record: dict[str, float] = {}
    if os.path.exists(BENCH_JSON):  # partial runs refresh, not clobber
        with open(BENCH_JSON) as f:
            record = json.load(f)
        # Drop every entry of a table being rerun: row names embed status
        # (dist_fw/OK vs /FAIL), so merging without this would keep a stale
        # entry under the opposite status forever.
        record = {k: v for k, v in record.items()
                  if k.split("/", 1)[0] not in which}
    fresh = 0
    print("name,params,us_per_call,derived")
    for t in which:
        for name, params, us, derived in TABLES[t]():
            print(f"{name},{params},{us:.1f},{derived}")
            record[f"{name}[{params}]"] = round(us, 1)
            fresh += 1
    # Platform stamp: "_meta" has no "/" so partial reruns never drop it via
    # the table filter above; every run refreshes it to the live backend.
    dev = jax.devices()[0]
    record["_meta"] = {
        "backend": jax.default_backend(),
        "device": dev.device_kind,
        "device_count": jax.device_count(),
        "note": "wall-clock and hbm_gbps measured on this backend; "
                "cpu-container numbers are interpret-mode XLA refs, "
                "not a TPU roofline",
    }
    with open(BENCH_JSON, "w") as f:
        json.dump(record, f, indent=1, sort_keys=True)
    print(f"# wrote {fresh}/{len(record)} entries to {BENCH_JSON}", file=sys.stderr)


if __name__ == "__main__":
    main()
