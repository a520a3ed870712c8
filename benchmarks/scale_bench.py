"""Scale benchmarks: BASELINE.md measurement configs 2-5.

  clos_flap   (config 2) — 3-tier Clos fabric, incremental SPF on a single
              link-flap event: LinkState ingest -> changelog array patch ->
              one batched device re-solve (vs CPU oracle event: ingest ->
              memo invalidation -> Dijkstra re-runs).
  wan_multi   (config 3) — synthetic WAN graph, batched multi-source SPF
              throughput on device (vs host Dijkstra samples).
  wan_ksp     (config 4) — ECMP first-hop mask + KSP penalized re-solves
              fused on device: base row + K masked-weight rows in one call,
              first-hop triangle mask computed on device.
  multi_metric(config 5) — M metric variants (e.g. SR-TE vs IGP weight
              sets) x sources solved as one sharded batch over the mesh.

Defaults are sized for the BASELINE configs (10k Clos, 100k WAN, 50k KSP);
env vars scale them down for smoke runs: SCALE_CLOS_PODS, SCALE_WAN_N,
SCALE_KSP_N, SCALE_SOURCES, SCALE_METRICS.
"""

from __future__ import annotations

import heapq
import os
import time
from functools import partial
from typing import List

import numpy as np

from benchmarks.common import compile_edges, emit, note, time_marginal

from openr_tpu.ops.graph import INF


# ---------------------------------------------------------------------------
# config 2: Clos fabric, incremental single-link-flap event
# ---------------------------------------------------------------------------


def bench_clos_flap(pods: int, events: int = 8) -> None:
    from openr_tpu.lsdb import LinkState
    from openr_tpu.solver import TpuSpfSolver
    from openr_tpu.topology import build_adj_dbs, fabric_edges

    edges = fabric_edges(pods)
    t0 = time.time()
    dbs = build_adj_dbs(edges)
    t1 = time.time()
    ls = LinkState("0")
    # production cold-start path: one bulk ingest (full-sync publication)
    ls.bulk_update_adjacency_databases(list(dbs.values()))
    n = len(dbs)
    note(
        f"clos: {n} nodes, {len(edges)} links, built in {time.time()-t0:.1f}s"
        f" (fixtures {t1-t0:.1f}s, cold-start LSDB ingest {time.time()-t1:.1f}s)"
    )

    me = "rsw0_0"
    solver = TpuSpfSolver(me)
    solve = solver._area_solve(ls, me)
    assert solve is not None

    # flap fsw0_1<->rsw0_1 metric between 1 and 5 via adj-db updates
    variants = []
    for metric in (5, 1):
        ev = [
            (a, b, metric if {a, b} == {"fsw0_1", "rsw0_1"} else w)
            for a, b, w in edges
        ]
        variants.append(build_adj_dbs(ev)["fsw0_1"])
    # warm both variants (jit both paths)
    for v in variants:
        ls.update_adjacency_database(v)
        solver._area_solve(ls, me)

    t0 = time.time()
    for i in range(events):
        ls.update_adjacency_database(variants[i % 2])
        solver._area_solve(ls, me)  # incremental refresh + device solve
    wall_event = (time.time() - t0) / events

    # Steady-state marginal event cost: chain flap events device-side (the
    # two weight variants stacked per bucket, indexed by step parity) so the
    # fixed host-device sync latency cancels out, mirroring the bench.py
    # methodology.
    import jax
    import jax.numpy as jnp
    from functools import partial as _partial

    from openr_tpu.ops.graph import refresh_graph
    from openr_tpu.ops.spf import _sell_solver_raw

    area = solver._solves[(ls.area, me)][1]
    g = area.graph
    sell = g.sell
    assert sell is not None
    wg_variants = []
    for v in variants:
        ls.update_adjacency_database(v)
        g = area.graph = refresh_graph(area.graph, ls)
        wg_variants.append(g.sell.wg)
    wg_stacks = tuple(
        jnp.asarray(np.stack([wgs[i] for wgs in wg_variants]))
        for i in range(len(sell.wg))
    )
    nbrs = tuple(jnp.asarray(a) for a in sell.nbr)
    ov = jnp.asarray(g.overloaded)
    from openr_tpu.ops.graph import _next_bucket

    rows_np = np.array([g.node_index[s] for s in area.sources], np.int32)
    s_pad = _next_bucket(len(rows_np), minimum=8)  # match _AreaSolve._solve
    rows = jnp.asarray(
        np.concatenate(
            [rows_np, np.full(s_pad - len(rows_np), rows_np[0], np.int32)]
        )
    )
    solve = _sell_solver_raw(sell.shape_key())

    @_partial(jax.jit, static_argnames=("reps",))
    def chained(reps):
        def body(carry, i):
            wgs_i = tuple(a[i % 2] for a in wg_stacks)
            d = solve(rows, nbrs, wgs_i, ov)
            return carry ^ d[0, -1], None

        acc, _ = jax.lax.scan(
            body, jnp.int32(0), jnp.arange(reps, dtype=jnp.int32)
        )
        return acc

    # long chain: the delta must dwarf host sync jitter
    device_marginal = time_marginal(
        lambda r: int(chained(r)), 2, 2 + 16 * events
    )

    # Host-side share of an event: adj-db ingest + changelog array patch +
    # the delta upload dispatch (async — no device sync in this loop). The
    # honest steady-state event cost is host + device marginal.
    def _host_events(count, t_start):
        nonlocal g, w_host
        for i in range(count):
            ls.update_adjacency_database(variants[(i + t_start) % 2])
            g = area.graph = refresh_graph(area.graph, ls)
            # mirror the solver's provenance fast path: diff only the
            # changelog-touched positions when available
            if g.changed_edges is not None:
                cand = g.changed_edges
                changed = cand[w_host[cand] != g.w[cand]]
            else:
                changed = np.nonzero(w_host[: g.e] != g.w[: g.e])[0]
            if len(changed):
                stacks = list(wg_stacks)
                for k in np.unique(sell.edge_bucket[changed]):
                    sel = changed[sell.edge_bucket[changed] == k]
                    stacks[k] = (
                        stacks[k]
                        .at[0, sell.edge_row[sel], sell.edge_slot[sel]]
                        .set(jnp.asarray(g.w[sel]))
                    )
                w_host[changed] = g.w[changed]

    w_host = g.w.copy()
    _host_events(2, 0)  # warm the scatter executables outside the timing
    t0 = time.time()
    _host_events(events, 0)
    host_event = (time.time() - t0) / events
    per_event = host_event + device_marginal

    # CPU oracle event: same ingest + fresh Dijkstra from me
    t0 = time.time()
    for i in range(events):
        ls.update_adjacency_database(variants[i % 2])
        ls.get_spf_result(me)
    cpu_event = (time.time() - t0) / events

    note(
        f"clos{n} flap event: tpu {per_event*1e3:.2f}ms steady-state "
        f"(host {host_event*1e3:.2f} + device {device_marginal*1e3:.2f}; "
        f"wall {wall_event*1e3:.2f}ms incl. host syncs) "
        f"cpu {cpu_event*1e3:.2f}ms"
    )
    emit(
        {
            "metric": f"clos{n}_flap_event_ms",
            "value": round(per_event * 1e3, 3),
            "unit": "ms/event (ingest + delta patch + device re-solve, "
            "steady state)",
            "vs_baseline": round(cpu_event / per_event, 2),
        }
    )


# ---------------------------------------------------------------------------
# config 3: WAN batched multi-source throughput
# ---------------------------------------------------------------------------


def _host_dijkstra(src_i, dst_i, w_i, n, source) -> np.ndarray:
    """Reference-architecture baseline: binary-heap Dijkstra on the host."""
    adj: List[List] = [[] for _ in range(n)]
    for s, d, w in zip(src_i, dst_i, w_i):
        if w < INF:
            adj[s].append((d, w))
    dist = np.full(n, INF, dtype=np.int64)
    dist[source] = 0
    heap = [(0, source)]
    while heap:
        dm, u = heapq.heappop(heap)
        if dm != dist[u]:
            continue
        for v, w in adj[u]:
            nd = dm + w
            if nd < dist[v]:
                dist[v] = nd
                heapq.heappush(heap, (nd, v))
    return dist


def bench_wan_multi(n: int, n_sources: int, cpu_samples: int = 4) -> None:
    import jax
    import jax.numpy as jnp

    from openr_tpu.ops.graph import compile_edges as graph_compile_edges
    from openr_tpu.ops.spf import _sell_solver_raw, sell_fixpoint
    from openr_tpu.topology import wan_edges

    t0 = time.time()
    graph = graph_compile_edges(wan_edges(n, degree=4, seed=3))
    note(
        f"wan: n={graph.n} e={graph.e} built in {time.time()-t0:.1f}s "
        f"(padded {graph.n_pad}/{graph.e_pad})"
    )
    sell = graph.sell
    assert sell is not None

    rng = np.random.default_rng(7)
    sources = jnp.asarray(
        rng.choice(n, size=n_sources, replace=False).astype(np.int32)
    )
    solve = _sell_solver_raw(sell.shape_key())
    nbrs = tuple(jnp.asarray(a) for a in sell.nbr)
    wgs = tuple(jnp.asarray(a) for a in sell.wg)
    ov_d = jnp.asarray(graph.overloaded)

    @partial(jax.jit, static_argnames=("reps",))
    def chained(reps):
        def body(carry, k):
            # perturbed weights = distinct LSDB events (INF slots stay INF)
            wgs_k = tuple(
                jnp.where(a < INF, (a + k) % 100 + 1, a) for a in wgs
            )
            d = solve(sources, nbrs, wgs_k, ov_d)
            return carry ^ d[0, -1], None

        acc, _ = jax.lax.scan(
            body, jnp.int32(0), jnp.zeros(reps, dtype=jnp.int32)
        )
        return acc

    marginal = time_marginal(lambda r: int(chained(r)), 1, 4)
    rate = n_sources / marginal
    note(
        f"wan{n}: {n_sources}-source batch in {marginal*1e3:.1f}ms "
        f"-> {rate:,.0f} SPF/s"
    )

    # correctness spot-check + native C++ baseline (falls back to the host
    # python Dijkstra when the toolchain is missing); solve only the sampled
    # sources — the full [S, n_pad] matrix is ~0.5GB host-side at 100k nodes
    sample = np.asarray(sources)[: max(cpu_samples, 3)]
    d = np.asarray(sell_fixpoint(sell, sample, sell.wg, graph.overloaded))
    from openr_tpu.solver.native_spf import native_spf_available

    if native_spf_available():
        from openr_tpu.solver.native_spf import NativeSpfSolver

        solver = NativeSpfSolver(graph)
        for i in range(min(cpu_samples, 3)):
            ref = solver.run(int(sources[i]))
            np.testing.assert_array_equal(d[i, : graph.n], ref)
        native_sources = np.linspace(
            0, graph.n - 1, max(cpu_samples, 8), dtype=np.int32
        )
        solver.run_many(native_sources[:2])
        t0 = time.time()
        solver.run_many(native_sources)
        cpu_rate = len(native_sources) / (time.time() - t0)
        solver.close()
        note(f"wan{n}: native C++ Dijkstra {cpu_rate:.1f} SPF/s")
    else:
        t0 = time.time()
        for i in range(cpu_samples):
            ref = _host_dijkstra(
                graph.src, graph.dst, graph.w, graph.n_pad, int(sources[i])
            )
            np.testing.assert_array_equal(
                np.minimum(d[i, : graph.n], INF),
                np.minimum(ref[: graph.n], INF),
            )
        cpu_rate = cpu_samples / (time.time() - t0)
        note(f"wan{n}: host python Dijkstra {cpu_rate:.1f} SPF/s")
    emit(
        {
            "metric": f"wan{n}_spf_per_sec",
            "value": round(rate, 1),
            "unit": f"SPF/s ({n_sources}-source batches)",
            "vs_baseline": round(rate / cpu_rate, 1),
        }
    )


# ---------------------------------------------------------------------------
# config 4: ECMP first-hop mask + KSP penalized re-solves fused on device
# ---------------------------------------------------------------------------


def bench_wan_ksp(n: int, k_dests: int) -> None:
    import jax
    import jax.numpy as jnp

    from openr_tpu.ops.graph import compile_edges as graph_compile_edges
    from openr_tpu.ops.spf import _sell_solver_vw
    from openr_tpu.topology import wan_edges

    graph = graph_compile_edges(wan_edges(n, degree=4, seed=5))
    sell = graph.sell
    assert sell is not None
    src, dst, w = graph.src, graph.dst, graph.w
    e_pad = graph.e_pad
    note(f"ksp wan: n={n} e_pad={e_pad}")

    me = graph.node_index["w0"]
    rng = np.random.default_rng(11)
    # my up-edges; their far ends are the neighbor rows for the first-hop mask
    mine = np.nonzero((src == me) & (w < INF))[0]
    neighbors = dst[mine]
    deg = len(neighbors)

    # batch = [me] + neighbors (base weights) + K penalized me rows, each
    # masking a few edges (the links of a previously traced path set) to
    # INF via the device-side per-bucket masks
    s = 1 + deg + k_dests
    sources = np.concatenate(
        [
            np.array([me], dtype=np.int32),
            neighbors.astype(np.int32),
            np.full(k_dests, me, dtype=np.int32),
        ]
    )
    per_bucket = [[] for _ in range(len(sell.nbr))]
    for row in range(1 + deg, s):
        for p in rng.choice(graph.e, size=8, replace=False):
            per_bucket[sell.edge_bucket[p]].append(
                (sell.edge_row[p], sell.edge_slot[p], row)
            )
    masks = tuple(
        jnp.asarray(
            np.asarray(entries, dtype=np.int32)
            if entries
            else np.full((1, 3), 1 << 30, dtype=np.int32)
        )
        for entries in per_bucket
    )

    my_w = jnp.asarray(w[mine])
    sources_d = jnp.asarray(sources)
    nbrs = tuple(jnp.asarray(a) for a in sell.nbr)
    wgs = tuple(jnp.asarray(a) for a in sell.wg)
    ov_d = jnp.asarray(graph.overloaded)
    solve_vw = _sell_solver_vw(sell.shape_key(), None)

    @partial(jax.jit, static_argnames=("reps",))
    def chained(reps):
        def body(carry, k):
            wgs_k = tuple(
                jnp.where(a < INF, (a + k) % 100 + 1, a) for a in wgs
            )
            d = solve_vw(sources_d, nbrs, wgs_k, masks, ov_d)
            # ECMP first-hop mask fused: edge (me -> v) is a first hop for
            # dest t iff w(me,v) + D[v, t] == D[me, t]
            fh = (my_w[:, None] + d[1 : 1 + deg, :] == d[0][None, :]).sum()
            return carry ^ d[0, -1] ^ fh.astype(jnp.int32), None

        acc, _ = jax.lax.scan(
            body, jnp.int32(0), jnp.zeros(reps, dtype=jnp.int32)
        )
        return acc

    marginal = time_marginal(lambda r: int(chained(r)), 1, 4)

    # measured baseline: the same s solves executed one row at a time with
    # each row's own penalty mask (the reference's sequential
    # per-destination re-run structure). Masks are stacked per batch row
    # and sliced by the loop index so no iteration is loop-invariant (XLA
    # must not be able to hoist the solve).
    one_src = sources_d[:1]
    per_row_bucket = [
        np.full((s, 8, 3), 1 << 30, dtype=np.int32) for _ in sell.nbr
    ]
    for k, entries in enumerate(per_bucket):
        counts = {}
        for r, sl, row in entries:
            j = counts.get(row, 0)
            per_row_bucket[k][row, j] = (r, sl, 0)  # col 0: single-row solve
            counts[row] = j + 1
    masks_rows = tuple(jnp.asarray(a) for a in per_row_bucket)

    @partial(jax.jit, static_argnames=("reps",))
    def chained_seq(reps):
        def body(carry, k):
            wgs_k = tuple(
                jnp.where(a < INF, (a + k) % 100 + 1, a) for a in wgs
            )

            def one(i, acc):
                masks_i = tuple(
                    jax.lax.dynamic_index_in_dim(m, i, axis=0, keepdims=False)
                    for m in masks_rows
                )
                d = solve_vw(one_src, nbrs, wgs_k, masks_i, ov_d)
                return acc ^ d[0, -1]

            acc = jax.lax.fori_loop(0, s, one, carry)
            return acc, None

        acc, _ = jax.lax.scan(
            body, jnp.int32(0), jnp.zeros(reps, dtype=jnp.int32)
        )
        return acc

    seq_marginal = time_marginal(lambda r: int(chained_seq(r)), 1, 2)
    note(
        f"ksp wan{n}: base + {k_dests} penalized solves + first-hop mask "
        f"fused {marginal*1e3:.1f}ms vs sequential {seq_marginal*1e3:.1f}ms"
    )
    emit(
        {
            "metric": f"wan{n}_ksp_fused_ms",
            "value": round(marginal * 1e3, 2),
            "unit": f"ms/event ({k_dests} penalized re-solves fused)",
            "vs_baseline": round(seq_marginal / marginal, 2),
        }
    )


# ---------------------------------------------------------------------------
# config 5: multi-metric/multi-topology solve sharded over the mesh
# ---------------------------------------------------------------------------


def bench_multi_metric(n: int, n_metrics: int, n_sources: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from openr_tpu.ops.spf import _bf_fixpoint_vw
    from openr_tpu.parallel import make_mesh
    from openr_tpu.topology import wan_edges

    edges = wan_edges(n, degree=4, seed=9)
    src, dst, w, overloaded, node_index = compile_edges(edges)

    devices = jax.devices()
    mesh = make_mesh(devices, shape=(len(devices), 1))
    note(f"multi-metric: mesh {dict(mesh.shape)} on {devices[0].platform}")

    rng = np.random.default_rng(13)
    s = n_metrics * n_sources
    # round the batch up to the mesh axis
    batch = mesh.shape["batch"]
    s_pad = ((s + batch - 1) // batch) * batch
    sources = np.tile(
        rng.choice(n, size=n_sources, replace=False).astype(np.int32),
        n_metrics,
    )
    sources = np.concatenate(
        [sources, np.zeros(s_pad - s, dtype=np.int32)]
    )
    # metric variants: scaled/perturbed copies of the base weights (distinct
    # routing topologies, e.g. IGP vs latency-optimized SR-TE planes)
    w_rows = np.empty((s_pad, len(w)), dtype=np.int32)
    finite = w < INF
    for mi in range(n_metrics):
        variant = w.copy()
        variant[finite] = w[finite] * (mi + 1) + mi
        w_rows[mi * n_sources : (mi + 1) * n_sources] = variant
    w_rows[s:] = w

    row_sharded = NamedSharding(mesh, P("batch"))
    repl = NamedSharding(mesh, P())
    fn = jax.jit(
        _bf_fixpoint_vw,
        # (sources, src_e, dst_e, w_rows, overloaded)
        in_shardings=(row_sharded, repl, repl, row_sharded, repl),
        out_shardings=NamedSharding(mesh, P("batch", None)),
    )
    args = (
        jax.device_put(jnp.asarray(sources), row_sharded),
        jax.device_put(jnp.asarray(src), repl),
        jax.device_put(jnp.asarray(dst), repl),
        jax.device_put(jnp.asarray(w_rows), row_sharded),
        jax.device_put(jnp.asarray(overloaded), repl),
    )

    sources_d, src_d, dst_d, w_rows_d, ov_d = args

    @partial(jax.jit, static_argnames=("reps",))
    def chained_fused(reps):
        def body(carry, k):
            # rep-dependent weights: no iteration is loop-invariant
            wk = jnp.where(
                w_rows_d < INF, (w_rows_d + k) % 100 + 1, w_rows_d
            )
            d = _bf_fixpoint_vw(sources_d, src_d, dst_d, wk, ov_d)
            return carry ^ d[0, -1], None

        acc, _ = jax.lax.scan(
            body, jnp.int32(0), jnp.arange(reps, dtype=jnp.int32)
        )
        return acc

    fn(*args).block_until_ready()  # keep the sharded executable validated
    # long chains: per-event time is ms-scale, so the delta must dwarf
    # host sync jitter
    marginal = time_marginal(lambda r: int(chained_fused(r)), 2, 50)
    rate = s / marginal

    # measured baseline: the reference structure — one metric plane (one
    # routing topology) solved at a time — chained device-side on a single
    # device so the comparison isolates plane-fusion, not host syncs. On a
    # one-chip mesh vs_baseline therefore reads as the fusion win; on a
    # real multi-chip mesh it additionally carries the sharding win.
    plane_w = jnp.asarray(
        np.stack(
            [w_rows[mi * n_sources][None, :] for mi in range(n_metrics)]
        )
    )  # [M, 1, E] — per-plane shared weights
    plane_sources = jax.device_put(
        jnp.asarray(sources[:n_sources]), devices[0]
    )
    src1, dst1, ov1 = (
        jax.device_put(jnp.asarray(a), devices[0])
        for a in (src, dst, overloaded)
    )

    @partial(jax.jit, static_argnames=("reps",))
    def chained_planes(reps):
        def rep_body(carry, k):
            def plane(mi, acc):
                wm = jax.lax.dynamic_index_in_dim(
                    plane_w, mi, axis=0, keepdims=False
                )
                wk = jnp.where(wm < INF, (wm + k) % 100 + 1, wm)
                d = _bf_fixpoint_vw(plane_sources, src1, dst1, wk, ov1)
                return acc ^ d[0, -1]

            return jax.lax.fori_loop(0, n_metrics, plane, carry), None

        acc, _ = jax.lax.scan(
            rep_body, jnp.int32(0), jnp.arange(reps, dtype=jnp.int32)
        )
        return acc

    seq_marginal = time_marginal(
        lambda r: int(chained_planes(r)), 2, 50
    )
    note(
        f"multi-metric wan{n}: {n_metrics} metrics x {n_sources} sources "
        f"fused {marginal*1e3:.1f}ms vs plane-at-a-time "
        f"{seq_marginal*1e3:.1f}ms -> {rate:,.0f} solves/s"
    )
    emit(
        {
            "metric": f"wan{n}_multimetric_solves_per_sec",
            "value": round(rate, 1),
            "unit": f"SPF/s ({n_metrics} metric planes fused+sharded)",
            "vs_baseline": round(seq_marginal / marginal, 2),
        }
    )


def main(argv: List[str] = ()) -> None:
    from openr_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    clos_pods = int(os.environ.get("SCALE_CLOS_PODS", "170"))
    wan_n = int(os.environ.get("SCALE_WAN_N", "100000"))
    ksp_n = int(os.environ.get("SCALE_KSP_N", "50000"))
    n_sources = int(os.environ.get("SCALE_SOURCES", "128"))
    n_metrics = int(os.environ.get("SCALE_METRICS", "4"))

    bench_clos_flap(clos_pods)
    bench_wan_multi(wan_n, n_sources)
    bench_wan_ksp(ksp_n, k_dests=15)
    bench_multi_metric(min(wan_n, 8192), n_metrics, max(8, n_sources // 4))


if __name__ == "__main__":
    main()
