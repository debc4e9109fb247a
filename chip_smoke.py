"""Smoke test of the main path on a TPU: the fused solve and the router.

Run from the repository root, one process per chip set:

    python3 chip_smoke.py [--seed 0]          # one TPU v5e chip
    python3 chip_smoke.py --four-chips        # four chips (2x2 mesh)

One chip, two phases, each through the entry points users call:

  solve  ``repro.apsp.solve`` of a dense random f32 min-plus graph with
         n=16384 (a 1 GiB matrix), ``method="auto"``.  The round must run
         as the Pallas kernel (backend "tpu", ``tpu_custom_call`` in the
         compiled program); the closure must equal the plain jnp
         ``fw_blocked`` rung, run on the chip, bitwise; 4 sampled source
         rows must match a host Dijkstra within ``SOLVE_RTOL``.
  serve  ``repro.serve.routing.RoutingEngine`` holding 8 graphs of
         n ~ 2048 (a 45x45 road-like grid and seeded random digraphs with
         integer weights): one ``refresh``, ~200 queries checked against a
         host Dijkstra, then one improving ``update_edge`` and one
         on-path ``fail_link``, which must be served by the rank-1 repair
         and the decremental repair (the engine's stats say which) and
         equal a fresh re-solve bitwise.

``--four-chips`` runs only ``solve(method="distributed")`` at n=32768 f32
on a 2x2 mesh of the four chips, compared bitwise with the single-device
fused solve of the same matrix.

Any failed check or exception exits non-zero.  On success the last line
of standard output is ``{"ok": true, "device": {...}}``; times printed
before it are information, not measurements of record.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax

SOLVE_N = 16384
SOLVE_ROWS = 4
# FW and Dijkstra add a path's weights in different orders; f32 paths of a
# few hops agree to a few ulps.
SOLVE_RTOL = 1e-5
SERVE_SIZES = (2048, 2040, 2001, 1990, 1950, 1937, 1925)  # + the 45x45 grid
SERVE_GRID_SIDE = 45
SERVE_DENSITY = 0.01
SERVE_QUERIES = 200
FOUR_CHIP_N = 32768


class CheckFailed(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)
    print(f"  ok: {what}", flush=True)


def dijkstra(w, src: int):
    """Dense O(n²) Dijkstra on the host (float64): distances from src."""
    import numpy as np

    n = w.shape[0]
    dist = np.full(n, np.inf)
    dist[src] = 0.0
    done = np.zeros(n, bool)
    for _ in range(n):
        cand = np.where(done, np.inf, dist)
        u = int(np.argmin(cand))
        if not np.isfinite(cand[u]):
            break
        done[u] = True
        np.minimum(dist, dist[u] + w[u].astype(np.float64), out=dist)
    return dist


def solve_phase(n: int, seed: int, *, expect_backend: str = "tpu") -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.apsp import solve
    from repro.core.floyd_warshall import fw_blocked
    from repro.core.graph import random_digraph
    from repro.core.semiring import MIN_PLUS
    from repro.core.staged import fw_staged

    print(f"[solve] n={n} dense f32 min-plus, seed={seed}", flush=True)
    w = random_digraph(n, seed=seed)

    t0 = time.perf_counter()
    res = solve(w, method="auto")
    dist = np.asarray(res.dist)
    first = time.perf_counter() - t0
    check(res.backend == expect_backend,
          f"solve(method='auto') ran the {res.backend!r} round "
          f"(method={res.method}, block_size={res.block_size})")
    wd = jnp.asarray(w)
    if expect_backend == "tpu":
        # The staged program the solve ran, lowered on its own.
        t0 = time.perf_counter()
        text = fw_staged.lower(
            wd, block_size=res.block_size, semiring=MIN_PLUS, variant="fori",
            interpret=None, lowering="pallas",
        ).compile().as_text()
        print(f"  compile (cached program) {time.perf_counter() - t0:.2f}s")
        check("tpu_custom_call" in text,
              "the compiled solve holds the Pallas kernel (tpu_custom_call)")
    t0 = time.perf_counter()
    jax.block_until_ready(solve(w, method="auto").dist)
    print(f"  first solve (compile + run) {first:.2f}s, "
          f"warm solve {time.perf_counter() - t0:.2f}s", flush=True)

    t0 = time.perf_counter()
    ref = np.asarray(fw_blocked(wd, block_size=res.block_size))
    print(f"  fw_blocked reference {time.perf_counter() - t0:.2f}s")
    check(np.array_equal(dist, ref),
          f"solve == jnp fw_blocked, bitwise ({n}x{n})")

    rng = np.random.default_rng(seed)
    for src in rng.choice(n, SOLVE_ROWS, replace=False):
        want = dijkstra(w, int(src))
        got = dist[src].astype(np.float64)
        err = np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-30))
        check(np.allclose(got, want, rtol=SOLVE_RTOL, atol=0),
              f"row {src} == host Dijkstra (max rel err {err:.2e})")


def _int_digraph(n: int, seed: int):
    """Seeded sparse digraph with integer weights (exact f32 path sums)."""
    import numpy as np

    from repro.core.graph import random_digraph

    return np.floor(random_digraph(
        n, density=SERVE_DENSITY, w_lo=1.0, w_hi=1000.0, seed=seed
    ))


def serve_phase(seed: int) -> None:
    import numpy as np

    from repro.apsp import ApspEngine
    from repro.core.graph import grid_graph
    from repro.serve.routing import RoutingEngine

    graphs = {f"grid{SERVE_GRID_SIDE}": grid_graph(SERVE_GRID_SIDE)}
    for i, n in enumerate(SERVE_SIZES):
        graphs[f"rand{i}"] = _int_digraph(n, seed + 1 + i)
    sizes = sorted(g.shape[0] for g in graphs.values())
    print(f"[serve] {len(graphs)} graphs, n={sizes}", flush=True)
    router = RoutingEngine()
    for gid, g in graphs.items():
        router.add_graph(gid, g)
    t0 = time.perf_counter()
    refreshed = router.refresh()
    print(f"  refresh (compile + solve) {time.perf_counter() - t0:.2f}s")
    check(refreshed == len(graphs) and router.solve_refreshes == len(graphs),
          f"refresh solved all {len(graphs)} graphs")

    rng = np.random.default_rng(seed)
    ids = sorted(graphs)
    refs: dict[tuple[str, int], np.ndarray] = {}

    def reference(gid, src):
        if (gid, src) not in refs:
            refs[(gid, src)] = dijkstra(router.registry.peek(gid), src)
        return refs[(gid, src)]

    def ask(gid, src, dst):
        reply = router.query(gid, src, dst)
        want = reference(gid, src)[dst]
        if reply.cost != want:
            raise CheckFailed(f"{gid} {src}->{dst}: cost {reply.cost} != "
                              f"Dijkstra {want}")
        if np.isfinite(want):
            wm = router.registry.peek(gid)
            hops = list(zip(reply.path, reply.path[1:]))
            walked = sum(float(wm[a, b]) for a, b in hops)
            if reply.path[0] != src or reply.path[-1] != dst or walked != want:
                raise CheckFailed(f"{gid} {src}->{dst}: path {reply.path} "
                                  f"walks {walked}, not {want}")
        return reply

    long_paths = []
    t0 = time.perf_counter()
    for _ in range(SERVE_QUERIES):
        gid = ids[rng.integers(len(ids))]
        n = graphs[gid].shape[0]
        src, dst = (int(x) for x in rng.integers(n, size=2))
        reply = ask(gid, src, dst)
        if gid.startswith("rand") and len(reply.path) >= 4:
            long_paths.append(reply)
    print(f"  {SERVE_QUERIES} queries {time.perf_counter() - t0:.2f}s "
          f"(host reference included)")
    print(f"  ok: {SERVE_QUERIES} query costs and paths == host Dijkstra")
    check(bool(long_paths), "some query has a path of 3+ hops")

    eng = router.engine
    fresh = ApspEngine()

    def matches_resolve(gid, what):
        want = np.asarray(fresh.solve(router.registry.peek(gid),
                                      successors=True).dist)
        check(np.array_equal(router.distances(gid), want),
              f"{what}: {gid} table == fresh re-solve, bitwise")
        refs.clear()
        for _ in range(20):
            n = graphs[gid].shape[0]
            ask(gid, *(int(x) for x in rng.integers(n, size=2)))

    # An ⊕-improving update: a shortcut between the ends of a long path.
    target = long_paths[0]
    gid, u, v = target.graph_id, target.src, target.dst
    new_w = float(np.floor(target.cost / 2))
    check(router.update_edge(gid, u, v, new_w),
          f"update_edge({gid}, {u}, {v}, {new_w}) improves the edge")
    repairs, fallbacks = eng.stats.repairs, router.solve_refreshes
    router.refresh()
    check(eng.stats.repairs == repairs + 1
          and router.repair_refreshes == 1
          and router.solve_refreshes == fallbacks,
          "the update was served by fw_repair, not a re-solve")
    matches_resolve(gid, "update_edge")

    # A link failure on a shortest path.  Among the edges of the queried
    # paths, fail the one whose loss touches the fewest source rows (the
    # host witness test the decremental repair runs), so the restricted
    # sweep — not its re-solve fallback — is the right call.
    gid = next(r.graph_id for r in long_paths if r.graph_id != gid)
    wm = router.registry.peek(gid)
    d0 = router.distances(gid)

    def rows_hit(a, b):
        wit = d0[:, a, None] + wm[a, b] + d0[None, b, :]
        return int(((wit == d0) & np.isfinite(d0)).any(axis=1).sum())

    edges = {(p, q) for r in long_paths if r.graph_id == gid
             for p, q in zip(r.path, r.path[1:])}
    a, b = min(sorted(edges), key=lambda e: rows_hit(*e))
    router.fail_link(gid, a, b, symmetric=False)
    dels, dfall = eng.stats.repair_dels, eng.stats.repair_del_fallbacks
    router.refresh()
    check(eng.stats.repair_dels == dels + 1
          and eng.stats.repair_del_fallbacks == dfall
          and router.repair_del_refreshes == 1,
          f"fail_link({gid}, {a}, {b}) was served by fw_repair_del "
          f"({eng.stats.repair_del_rows} affected rows), not a re-solve")
    matches_resolve(gid, "fail_link")


def four_chip_phase(n: int, seed: int) -> None:
    import numpy as np

    from repro.apsp import solve
    from repro.core.graph import random_digraph
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(4)
    print(f"[distributed] n={n} f32 on mesh {dict(mesh.shape)}", flush=True)
    w = random_digraph(n, seed=seed)
    t0 = time.perf_counter()
    single = solve(w, method="fused")
    want = np.asarray(single.dist)
    del single
    print(f"  single-device fused solve (compile + run) "
          f"{time.perf_counter() - t0:.2f}s", flush=True)
    t0 = time.perf_counter()
    res = solve(w, method="distributed", mesh=mesh)
    got = np.asarray(res.dist)
    print(f"  distributed solve (compile + run) "
          f"{time.perf_counter() - t0:.2f}s", flush=True)
    check(res.method == "distributed" and res.padded_n == n,
          f"distributed solve on {mesh.devices.size} chips, no padding")
    check(np.array_equal(got, want),
          "distributed == single-device fused, bitwise")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the distributed solve on a 2x2 mesh")
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.utils.compat import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {devices[0].device_kind} x{len(devices)}", flush=True)
    if args.four_chips:
        four_chip_phase(FOUR_CHIP_N, args.seed)
    else:
        solve_phase(SOLVE_N, args.seed)
        serve_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
