"""Benchmark: batched TPU SPF throughput vs the native C++ SpfSolver oracle.

Headline config is BASELINE.md config 3 — batched multi-source SPF on a
100k-node synthetic WAN LSDB — the primary metric named in BASELINE.json
("SPF recomputes/sec on 100k-node LSDB"). The TPU side runs the sliced-ELL
pull relaxation (openr_tpu/ops/spf.py:_bf_fixpoint via _sell_solver); the
baseline of record is the native C++ Dijkstra (native/spf), the honest
stand-in for the reference's SpfSolver hot loop
(openr/decision/LinkState.cpp:806-880).

Methodology: R independent LSDB events are chained inside one jitted
lax.scan — each event patches the edge weights and solves an S-source
batch; a data dependency folds each result into a carry so no solve can be
elided. Throughput is the marginal time between a short and a long chain,
which cancels the fixed dispatch/sync latency of one host round trip.

The script never chooses a backend: it runs on whatever JAX initializes,
every line names that device (platform, device_kind, n_devices), and any
failure propagates with a non-zero exit. BENCH_SMOKE=1 JAX_PLATFORMS=cpu is
tier-1's functional check, chosen by the caller.

Set BENCH_TOPO=grid for the 1k-node grid config (BASELINE.md config 1, with
ECMP first-hop DAG extraction fused — config 4 semantics).

Prints one JSON line per metric (SPF/s headline, convergence p95, TE
optimize latency, destination-tiled scale solve, exporter overhead):
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "baseline": ...}
plus detail lines on stderr.
"""

import json
import os
import time
from functools import partial

import numpy as np


from benchmarks.common import note as _note
from benchmarks.common import time_marginal as _marginal_time


def _mem_columns(
    layout,
    n_nodes,
    structures,
    *,
    n_sources=1,
    graph=None,
    tiling=None,
    mesh_shape=None,
) -> dict:
    """Device-memory columns for one bench line (docs/Monitoring.md
    "Device-memory observatory"): the ledger's peak resident bytes for
    the line's structures next to the predict_fit forward model — the
    same padding/bucketing arithmetic the capacity-admission gate uses —
    so every BENCH round records how tight the prediction tracks what
    was actually pinned."""
    from openr_tpu.monitor.memledger import get_ledger

    ledger = get_ledger()
    verdict = ledger.predict_fit(
        n_nodes,
        layout,
        n_sources=n_sources,
        graph=graph,
        tiling=tiling,
        mesh_shape=mesh_shape,
    )
    peaks = ledger.structure_peak_bytes()
    peak = sum(peaks.get(s, 0) for s in structures)
    return {
        "mem_peak_bytes": int(peak),
        "mem_predicted_bytes": int(verdict["predicted_bytes"]),
        "mem_predicted_vs_live_bytes": int(
            verdict["predicted_bytes"] - peak
        ),
    }


def _native_rate(graph, samples: int) -> float:
    """SPF/s of the native C++ Dijkstra on `samples` sources."""
    from openr_tpu.solver.native_spf import NativeSpfSolver

    solver = NativeSpfSolver(graph)
    sources = np.linspace(0, graph.n - 1, samples, dtype=np.int32)
    solver.run_many(sources[: max(2, samples // 4)])  # warm caches
    t0 = time.time()
    solver.run_many(sources)
    elapsed = time.time() - t0
    rate = samples / elapsed
    _note(
        f"native C++ oracle: {samples} Dijkstra runs in "
        f"{elapsed*1e3:.1f}ms -> {rate:,.0f} SPF/s (baseline of record)"
    )
    solver.close()
    return rate


def _spf_phase_split(solve, sources, nbrs, wg_event, ov) -> dict:
    """One representative event measured with explicit barriers at the
    h2d / relax / d2h seams — the bench-side mirror of the flight
    recorder's sampled PhaseClock (docs/Monitoring.md "Flight recorder &
    profiling"), so the SPF lines carry per-phase attribution, not just
    one wall-clock number."""
    import jax.numpy as jnp

    t0 = time.perf_counter()
    wgs_dev = tuple(jnp.asarray(a) for a in wg_event)
    for a in wgs_dev:
        a.block_until_ready()
    t1 = time.perf_counter()
    d = solve(sources, nbrs, wgs_dev, ov)
    d.block_until_ready()
    t2 = time.perf_counter()
    np.asarray(d[0])  # one distance row host-side (the O(changes) shape)
    t3 = time.perf_counter()
    return {
        "h2d_ms": round((t1 - t0) * 1e3, 3),
        "relax_ms": round((t2 - t1) * 1e3, 3),
        "d2h_ms": round((t3 - t2) * 1e3, 3),
    }


def bench_wan() -> dict:
    import jax
    import jax.numpy as jnp

    from openr_tpu.ops.graph import INF, compile_edges
    from openr_tpu.ops.spf import _sell_solver_raw
    from openr_tpu.solver.native_spf import native_spf_available
    from openr_tpu.topology import wan_edges

    n = int(os.environ.get("BENCH_WAN_N", "100000"))
    # 128 sources = one 128-lane int32 tile in the minor dim
    n_sources = int(os.environ.get("BENCH_WAN_SOURCES", "128"))
    # chains long enough that the measured delta dwarfs host sync jitter
    reps_small = int(os.environ.get("BENCH_REPS_SMALL", "2"))
    reps_big = int(os.environ.get("BENCH_REPS_BIG", "10"))
    events = max(reps_big, reps_small)

    t0 = time.time()
    graph = compile_edges(wan_edges(n, degree=4, seed=3))
    _note(
        f"wan: n={graph.n} e={graph.e} (padded {graph.n_pad}/{graph.e_pad}) "
        f"built in {time.time()-t0:.1f}s on {jax.devices()[0]}"
    )
    sell = graph.sell
    assert sell is not None, "WAN degree profile must qualify for sliced-ELL"

    solve = _sell_solver_raw(sell.shape_key())

    rng = np.random.default_rng(7)
    sources = jnp.asarray(
        rng.choice(graph.n, size=n_sources, replace=False).astype(np.int32)
    )
    nbrs = tuple(jnp.asarray(a) for a in sell.nbr)
    ov = jnp.asarray(graph.overloaded)

    # distinct weight sets = distinct LSDB events, patched into the sliced
    # layout host-side exactly like refresh_graph's flap path
    wg_stacks = []
    for k in range(events):
        w_k = np.where(
            graph.w[: graph.e] < INF,
            (graph.w[: graph.e] + k) % 100 + 1,
            graph.w[: graph.e],
        ).astype(np.int32)
        wg_stacks.append(sell.patched_wg(w_k))
    wg_variants = tuple(
        jnp.asarray(np.stack([ws[i] for ws in wg_stacks]))
        for i in range(len(sell.wg))
    )

    # ledger registration of one event's device working set (the sell
    # planes + one weight set + the [S, n_pad] distance block the scan
    # materializes) — the line's mem columns read these back
    from openr_tpu.monitor.memledger import get_ledger

    ledger = get_ledger()
    ledger.register(
        "bench/wan", "sell", layout="sell",
        arrays=(*nbrs, *wg_stacks[0], ov),
    )
    ledger.register(
        "bench/wan", "dist", layout="sell",
        nbytes=n_sources * graph.n_pad * 4,
    )

    @partial(jax.jit, static_argnames=("reps",))
    def chained(wgv, reps):
        def body(carry, wgs_event):
            d = solve(sources, nbrs, wgs_event, ov)
            return carry ^ d[0, -1], None

        acc, _ = jax.lax.scan(
            body,
            jnp.int32(0),
            tuple(a[:reps] for a in wgv),
        )
        return acc

    t0 = time.time()
    int(chained(wg_variants, reps_small))
    int(chained(wg_variants, reps_big))
    _note(f"compile+first runs: {time.time()-t0:.1f}s")

    marginal = _marginal_time(
        lambda r: int(chained(wg_variants, r)), reps_small, reps_big
    )
    tpu_rate = n_sources / marginal
    _note(
        f"tpu: {n_sources}-source batch per event in {marginal*1e3:.1f}ms "
        f"-> {tpu_rate:,.0f} SPF/s"
    )

    # sanity: distances agree with the native oracle on unmodified weights
    # (solve just the sampled sources, not the full [S, n_pad] matrix)
    from openr_tpu.ops.spf import sell_fixpoint

    sample = np.asarray(sources)[[0, n_sources // 2, n_sources - 1]]
    d = np.asarray(sell_fixpoint(sell, sample, sell.wg, graph.overloaded))
    if native_spf_available():
        from openr_tpu.solver.native_spf import NativeSpfSolver

        solver = NativeSpfSolver(graph)
        for i, s in enumerate(sample):
            ref = solver.run(int(s))
            np.testing.assert_array_equal(d[i, : graph.n], ref)
        solver.close()
        _note("sanity: device distances match native oracle")
        cpu_rate = _native_rate(
            graph, int(os.environ.get("BENCH_CPU_SAMPLES", "32"))
        )
        baseline = "native-c++"
    else:  # toolchain missing: no honest baseline to report
        cpu_rate = None
        baseline = "unavailable"

    mem = _mem_columns(
        "sell", graph.n, ("sell", "dist"),
        n_sources=n_sources, graph=graph,
    )
    ledger.release_area("bench/wan")
    return {
        "metric": f"wan{graph.n}_spf_recomputes_per_sec",
        "value": round(tpu_rate, 1),
        "unit": f"SPF/s ({graph.n}-node WAN LSDB, {n_sources}-source batches)",
        "vs_baseline": round(tpu_rate / cpu_rate, 1) if cpu_rate else 0.0,
        "baseline": baseline,
        "phases": _spf_phase_split(
            solve, sources, nbrs, wg_stacks[0], ov
        ),
        **mem,
    }


def bench_grid() -> dict:
    import jax
    import jax.numpy as jnp

    from openr_tpu.lsdb import LinkState
    from openr_tpu.ops import INF, compile_graph
    from openr_tpu.ops.spf import _ecmp_dag, _sell_solver_raw
    from openr_tpu.solver.native_spf import native_spf_available
    from openr_tpu.topology import build_adj_dbs, grid_edges

    grid_side = int(os.environ.get("BENCH_GRID_SIDE", "32"))  # 32x32 = 1024
    reps_small = int(os.environ.get("BENCH_REPS_SMALL", "8"))
    reps_big = int(os.environ.get("BENCH_REPS_BIG", "64"))

    ls = LinkState("0")
    for db in build_adj_dbs(grid_edges(grid_side)).values():
        ls.update_adjacency_database(db)
    graph = compile_graph(ls)
    sell = graph.sell
    assert sell is not None
    _note(
        f"grid: n={graph.n} e={graph.e} (padded {graph.n_pad}/{graph.e_pad})"
        f" on {jax.devices()[0]}"
    )

    solve = _sell_solver_raw(sell.shape_key())
    sources = jnp.arange(graph.n_pad, dtype=jnp.int32)
    nbrs = tuple(jnp.asarray(a) for a in sell.nbr)
    ov = jnp.asarray(graph.overloaded)
    src_e = jnp.asarray(graph.src)
    dst_e = jnp.asarray(graph.dst)

    reps = reps_big
    w_rows = []
    wg_stacks = []
    for k in range(reps):
        w_k = np.where(
            graph.w < INF, (graph.w + k) % 7 + 1, graph.w
        ).astype(np.int32)
        w_rows.append(w_k)
        wg_stacks.append(sell.patched_wg(w_k[: graph.e]))
    w_variants = jnp.asarray(np.stack(w_rows))
    wg_variants = tuple(
        jnp.asarray(np.stack([ws[i] for ws in wg_stacks]))
        for i in range(len(sell.wg))
    )

    # one event's device working set on the ledger (mem columns below)
    from openr_tpu.monitor.memledger import get_ledger

    ledger = get_ledger()
    ledger.register(
        "bench/grid", "sell", layout="sell",
        arrays=(*nbrs, *wg_stacks[0], ov),
    )
    ledger.register(
        "bench/grid", "dist", layout="sell",
        nbytes=graph.n_pad * graph.n_pad * 4,
    )

    @partial(jax.jit, static_argnames=("reps",))
    def chained(wv, wgv, reps):
        def body(carry, event):
            w_e, wgs_event = event
            d = solve(sources, nbrs, wgs_event, ov)
            dag = _ecmp_dag(d, src_e, dst_e, w_e, ov)
            # fold a data dependency so no solve can be elided
            return carry ^ d[0, -1] ^ dag[0, -1].astype(jnp.int32), None

        acc, _ = jax.lax.scan(
            body,
            jnp.int32(0),
            (wv[:reps], tuple(a[:reps] for a in wgv)),
        )
        return acc

    t0 = time.time()
    int(chained(w_variants, wg_variants, reps_small))
    int(chained(w_variants, wg_variants, reps_big))
    _note(f"compile+first runs: {time.time()-t0:.1f}s")

    marginal = _marginal_time(
        lambda r: int(chained(w_variants, wg_variants, r)),
        reps_small,
        reps_big,
    )
    tpu_rate = graph.n / marginal
    _note(
        f"tpu: {graph.n}-source solve + ECMP DAG in {marginal*1e3:.2f}ms "
        f"-> {tpu_rate:,.0f} SPF/s"
    )

    # sanity: corner-to-corner distance with the unmodified weights
    from openr_tpu.ops.spf import sell_fixpoint

    d = sell_fixpoint(sell, np.arange(graph.n_pad), sell.wg, graph.overloaded)
    got = int(
        np.asarray(
            d[
                graph.node_index["g0_0"],
                graph.node_index[f"g{grid_side-1}_{grid_side-1}"],
            ]
        )
    )
    assert got == 2 * (grid_side - 1), got

    if native_spf_available():
        cpu_rate = _native_rate(graph, graph.n)
        baseline = "native-c++"
    else:
        t0 = time.time()
        sample = graph.names[:: max(1, graph.n // 8)][:8]
        for node in sample:
            ls.run_spf(node)
        cpu_rate = len(sample) / (time.time() - t0)
        baseline = "python-oracle"

    mem = _mem_columns(
        "sell", graph.n, ("sell", "dist"),
        n_sources=graph.n_pad, graph=graph,
    )
    ledger.release_area("bench/grid")
    return {
        "metric": "spf_recomputes_per_sec",
        "value": round(tpu_rate, 1),
        "unit": f"SPF/s ({graph.n}-node grid, ECMP DAG fused)",
        "vs_baseline": round(tpu_rate / cpu_rate, 1),
        "baseline": baseline,
        "phases": _spf_phase_split(
            solve, sources, nbrs, wg_stacks[0], ov
        ),
        **mem,
    }


def _apply_env_defaults(pairs) -> None:
    for key, val in pairs:
        os.environ.setdefault(key, val)


def _apply_smoke_env() -> None:
    """BENCH_SMOKE=1: tiny topology + short chains so the full bench path
    (compile, chained events, sanity checks, JSON emission) runs in CI —
    bench bitrot fails tier-1 instead of silently zeroing BENCH rounds."""
    _apply_env_defaults(
        (
            ("BENCH_WAN_N", "192"),
            ("BENCH_WAN_SOURCES", "8"),
            ("BENCH_GRID_SIDE", "6"),
            ("BENCH_REPS_SMALL", "1"),
            # 7 extra events: time_marginal raises when jitter inverts
            # every round, which a 1-vs-2 chain invites on a busy CI host
            ("BENCH_REPS_BIG", "8"),
            ("BENCH_CPU_SAMPLES", "4"),
            ("BENCH_TE_STEPS", "6"),
            ("BENCH_TE_SCENARIOS", "2"),
            ("BENCH_TE_REPEATS", "1"),
            ("BENCH_SCALE_N", "384"),
            ("BENCH_SCALE_SOURCES", "8"),
            ("BENCH_SCALE_FLAPS", "2"),
            ("BENCH_EXPORTER_RECORDS", "200"),
            ("BENCH_STREAM_SUBS", "8"),
            ("BENCH_STREAM_SWEEP", "4"),
            ("BENCH_APSP_N", "96"),
            ("BENCH_APSP_SWEEP", "48,96"),
            ("BENCH_APSP_REPEATS", "1"),
        )
    )


# the convergence flap batch's summary, kept so the exporter-overhead
# line measures on the SAME run instead of spinning a second emulator
_CONV_SUMMARY = {}


def _bench_convergence() -> dict:
    """Second metric line: p95 hello-to-programmed-route from an emulator
    line-topology flap run (VirtualNetwork.convergence_report), so the
    incremental/DeltaPath work shows up in the trajectory as
    convergence.e2e_ms, not just raw SPF/s."""
    from openr_tpu.testing.decision_harness import run_bench_convergence

    nodes = int(os.environ.get("BENCH_CONV_NODES", "5"))
    flaps = int(os.environ.get("BENCH_CONV_FLAPS", "2"))
    backend = os.environ.get("BENCH_CONV_BACKEND", "tpu")
    summary = run_bench_convergence(nodes=nodes, flaps=flaps, backend=backend)
    _CONV_SUMMARY.update(summary)
    _note(
        f"convergence: {summary['spans_total']} spans over "
        f"{summary['flaps']} flap cycles on a {summary['nodes']}-node line "
        f"-> p50 {summary['e2e_p50_ms']:.1f}ms / p95 "
        f"{summary['e2e_p95_ms']:.1f}ms"
    )
    return {
        "metric": "convergence_e2e_p95_ms",
        "value": round(summary["e2e_p95_ms"], 2),
        "unit": (
            f"ms p95 hello-to-programmed-route ({summary['nodes']}-node "
            f"line emulator, {summary['flaps']} flap cycles, "
            f"{backend} backend)"
        ),
        "vs_baseline": 0.0,
        "baseline": "none",
        "spans": summary["spans_total"],
        "e2e_p50_ms": round(summary["e2e_p50_ms"], 2),
        "e2e_max_ms": round(summary["e2e_max_ms"], 2),
    }


def _bench_te() -> dict:
    """Third metric line: wall-clock of one what-if differentiable-TE
    optimization (openr_tpu/te) on the congested 2-pod Clos fixture with
    its skewed synthetic demand matrix — the TE workload enters the bench
    trajectory from day one as te_optimize_ms."""
    from openr_tpu.lsdb import LinkState
    from openr_tpu.te import TeService, congested_clos_fixture
    from openr_tpu.topology import build_adj_dbs

    steps = int(os.environ.get("BENCH_TE_STEPS", "48"))
    scenarios = int(os.environ.get("BENCH_TE_SCENARIOS", "4"))
    repeats = int(os.environ.get("BENCH_TE_REPEATS", "3"))

    edges, spec = congested_clos_fixture()
    ls = LinkState("0")
    for db in build_adj_dbs(edges).values():
        ls.update_adjacency_database(db)
    svc = TeService("l0_0", {"0": ls})
    params = {"demands": spec, "steps": steps, "scenarios": scenarios}
    report = svc.optimize(params)  # compile + first run, excluded
    times = []
    for _ in range(max(repeats, 1)):
        report = svc.optimize(params)
        times.append(report["solve_ms"])
    best = min(times)
    _note(
        f"te-optimize: {report['nodes']}-node Clos, {report['scenarios']} "
        f"scenario(s), {report['steps']} steps in {best:.1f}ms (best of "
        f"{len(times)}; first+compile excluded) — max util "
        f"{report['initial_max_util']:.2f} -> "
        f"{report['optimized_max_util']:.2f}"
    )
    # TE registers its [B, n, n] scenario batch on the ledger for each
    # run's duration (te/service.py seam); the structure peak is what one
    # optimization actually pinned
    from openr_tpu.ops.graph import compile_graph

    mem = _mem_columns(
        "te", report["nodes"], ("te",),
        n_sources=report["scenarios"], graph=compile_graph(ls),
    )
    return {
        "metric": "te_optimize_ms",
        "value": round(best, 2),
        "unit": (
            f"ms per what-if TE optimization ({report['nodes']}-node Clos, "
            f"{report['scenarios']} scenario(s), {report['steps']} Adam "
            f"steps, compile excluded)"
        ),
        "vs_baseline": 0.0,
        "baseline": "none",
        "initial_max_util": report["initial_max_util"],
        "optimized_max_util": report["optimized_max_util"],
        "improved": report["improved"],
        **mem,
    }


def _bench_scale() -> dict:
    """Fourth metric line: the destination-tiled 2-D layout at scale — a
    synthetic WAN cold solve plus a warm link-flap batch with D tiled
    P('batch', 'graph') over every available device, per-device tile bytes
    reported next to the [S, n_pad] replica bytes the old row-sharded
    layout would have pinned per chip. Defaults to the 1M-node config
    (the ROADMAP "heavy traffic from millions of users" topology class);
    BENCH_SMOKE shrinks it for tier-1's functional check."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from openr_tpu.ops.graph import INF, compile_edges
    from openr_tpu.ops.spf import _tile_solver, _tile_solver_warm
    from openr_tpu.parallel import make_mesh, tile_graph
    from openr_tpu.topology import wan_edges

    n = int(os.environ.get("BENCH_SCALE_N", "1000000"))
    n_sources = int(os.environ.get("BENCH_SCALE_SOURCES", "16"))
    flaps = int(os.environ.get("BENCH_SCALE_FLAPS", "4"))

    devices = jax.devices()
    total = 1
    while total * 2 <= len(devices):
        total *= 2
    b_ax = 2 if total >= 4 else 1
    g_ax = total // b_ax
    mesh = make_mesh(devices[:total], shape=(b_ax, g_ax))

    t0 = time.time()
    graph = compile_edges(wan_edges(n, degree=4, seed=5))
    if graph.n_pad % g_ax:
        # tiny-n smoke configs can under-run the graph axis; shrink it
        while g_ax > 1 and graph.n_pad % g_ax:
            g_ax //= 2
        mesh = make_mesh(devices[: b_ax * g_ax], shape=(b_ax, g_ax))
    tiling = tile_graph(graph, g_ax)
    _note(
        f"scale: n={graph.n} e={graph.e} (n_pad {graph.n_pad}) built in "
        f"{time.time()-t0:.1f}s; mesh {dict(mesh.shape)}, tile "
        f"{graph.n_pad // g_ax} cols x {tiling.e_tile} edges/partition"
    )

    gs = NamedSharding(mesh, P("graph", None))
    repl = NamedSharding(mesh, P())
    rng = np.random.default_rng(11)
    s_pad = n_sources + (-n_sources) % b_ax
    rows = rng.choice(graph.n, size=s_pad, replace=False).astype(np.int32)
    args = (
        jax.device_put(
            jnp.asarray(rows), NamedSharding(mesh, P("batch"))
        ),
        jax.device_put(jnp.asarray(tiling.src_l), gs),
        jax.device_put(jnp.asarray(tiling.hseg), gs),
        jax.device_put(jnp.asarray(tiling.w), gs),
        jax.device_put(jnp.asarray(tiling.hcols), gs),
        jax.device_put(jnp.asarray(graph.overloaded), repl),
    )
    key = tiling.shape_key() + (graph.n_pad,)
    solve = _tile_solver(key, mesh)
    # the resident tile working set on the ledger (mem columns below):
    # edge tiles + halo frontier + the tiled D (logical global bytes)
    from openr_tpu.monitor.memledger import get_ledger

    ledger = get_ledger()
    ledger.register(
        "bench/scale", "tile", layout="tile2d",
        arrays=(args[1], args[2], args[3], args[5]),
    )
    ledger.register(
        "bench/scale", "halo", layout="tile2d", arrays=(args[4],)
    )
    ledger.register(
        "bench/scale", "dist", layout="tile2d",
        nbytes=s_pad * graph.n_pad * 4,
    )
    d, rounds = solve(*args)  # compile + first run, excluded
    t0 = time.time()
    d, rounds = solve(*args)
    cold_rounds = int(rounds)  # scalar read forces completion
    cold_ms = (time.time() - t0) * 1e3

    # warm link-flap batch: metric wiggles on random up edges, each event
    # one warm dispatch against the resident tile state
    warm = _tile_solver_warm(key, mesh)
    ov = args[5]
    up = np.nonzero(graph.w[: graph.e] < INF)[0]
    w2_old = args[3]
    warm_ms = []
    warm_rounds = []
    for i in range(max(flaps, 1)):
        w_new = graph.w.copy()
        pos = up[rng.integers(len(up))]
        w_new[pos] = (w_new[pos] + 1 + i) % 100 + 1
        w2_new = jax.device_put(jnp.asarray(tiling.tile_weights(w_new)), gs)
        t0 = time.time()
        d, r, ir, _, num = warm(
            args[0], args[1], args[2], w2_new, w2_old, args[4], ov, ov, d
        )
        warm_rounds.append(int(r) + int(ir))  # forces completion
        warm_ms.append((time.time() - t0) * 1e3)
        w2_old = w2_new
    warm_best = min(warm_ms)

    # phase-split attribution of one more warm flap, with explicit
    # barriers at the h2d / relax / d2h seams (the tiled layout's halo
    # traffic rides inside relax — the rounds split it, like the flight
    # recorder's sampled traces; docs/Monitoring.md)
    w_new = graph.w.copy()
    pos = up[rng.integers(len(up))]
    w_new[pos] = (w_new[pos] + 7) % 100 + 1
    t0 = time.perf_counter()
    w2_new = jax.device_put(jnp.asarray(tiling.tile_weights(w_new)), gs)
    w2_new.block_until_ready()
    t1 = time.perf_counter()
    d, r, ir, _, num = warm(
        args[0], args[1], args[2], w2_new, w2_old, args[4], ov, ov, d
    )
    d.block_until_ready()
    t2 = time.perf_counter()
    np.asarray(d[0])  # one distance row host-side
    t3 = time.perf_counter()
    phases = {
        "h2d_ms": round((t1 - t0) * 1e3, 3),
        "relax_ms": round((t2 - t1) * 1e3, 3),
        "d2h_ms": round((t3 - t2) * 1e3, 3),
    }

    tile_bytes = (s_pad // b_ax) * (graph.n_pad // g_ax) * 4
    replica_bytes = s_pad * graph.n_pad * 4
    _note(
        f"scale: cold solve {cold_ms:.0f}ms ({cold_rounds} rounds), warm "
        f"flap best {warm_best:.0f}ms over {len(warm_ms)} event(s); "
        f"per-device D tile {tile_bytes / 1e6:.1f}MB vs full replica "
        f"{replica_bytes / 1e6:.1f}MB ({replica_bytes / max(tile_bytes, 1):.0f}x)"
    )
    mem = _mem_columns(
        "tile2d", graph.n, ("tile", "halo", "dist"),
        n_sources=s_pad, graph=graph, tiling=tiling,
        mesh_shape=(b_ax, g_ax),
    )
    ledger.release_area("bench/scale")
    return {
        "metric": f"scale{graph.n}_tiled_cold_solve_ms",
        "value": round(cold_ms, 2),
        "unit": (
            f"ms cold {s_pad}-source solve ({graph.n}-node WAN, D tiled "
            f"P('batch','graph') over mesh {dict(mesh.shape)})"
        ),
        "vs_baseline": 0.0,
        "baseline": "none",
        "warm_flap_ms": round(warm_best, 2),
        "tile_bytes_per_device": tile_bytes,
        "replica_bytes_per_device": replica_bytes,
        "mesh": [mesh.shape["batch"], mesh.shape["graph"]],
        "phases": phases,
        **mem,
    }


def _bench_exporter() -> dict:
    """Fifth metric line: continuous-telemetry overhead on the standard
    flap batch — best full-registry Prometheus exposition render (each
    render parsed back, so the sample only counts if the text round-trips)
    plus the per-record windowed-rollup fold cost, both measured on the
    converged emulator run behind the convergence line (one emulator spin
    serves both; with BENCH_CONVERGENCE=0 a one-cycle flap batch is run
    here instead)."""
    summary = dict(_CONV_SUMMARY)
    if "scrape_render_ms" not in summary:
        from openr_tpu.testing.decision_harness import run_bench_convergence

        summary = run_bench_convergence(
            nodes=int(os.environ.get("BENCH_CONV_NODES", "5")),
            flaps=1,
            backend=os.environ.get("BENCH_CONV_BACKEND", "tpu"),
        )
    _note(
        f"exporter: {summary['metrics_series']}-family registry rendered "
        f"in {summary['scrape_render_ms']:.3f}ms, rollup fold "
        f"{summary['rollup_record_us']:.2f}us/span "
        f"({summary['nodes']}-node flap batch)"
    )
    return {
        "metric": "exporter_scrape_render_ms",
        "value": summary["scrape_render_ms"],
        "unit": (
            f"ms best full-registry Prometheus exposition render "
            f"({summary['metrics_series']} metric families, "
            f"{summary['nodes']}-node line emulator flap batch, "
            f"parse-validated)"
        ),
        "vs_baseline": 0.0,
        "baseline": "none",
        "rollup_record_us": summary["rollup_record_us"],
        "metrics_series": summary["metrics_series"],
    }


def _bench_stream() -> dict:
    """Sixth metric line: streaming control-plane fan-out throughput —
    the standard convergence flap batch re-run with BENCH_STREAM_SUBS
    concurrent `subscribeKvStore` subscriptions riding every node's real
    ctrl socket (docs/Streaming.md). The metric is sustained
    delta-delivery rate summed across subscribers (deliveries/s); the
    line also carries the run's convergence e2e p95 next to the
    zero-subscriber baseline's (the convergence line measured earlier on
    the same config), asserting fan-out does not move the convergence
    path outside noise."""
    from openr_tpu.testing.decision_harness import run_bench_convergence

    nodes = int(os.environ.get("BENCH_CONV_NODES", "5"))
    flaps = int(os.environ.get("BENCH_CONV_FLAPS", "2"))
    backend = os.environ.get("BENCH_CONV_BACKEND", "tpu")
    subscribers = int(os.environ.get("BENCH_STREAM_SUBS", "64"))
    summary = run_bench_convergence(
        nodes=nodes,
        flaps=flaps,
        backend=backend,
        measure_exporter=False,
        subscribers=subscribers,
    )
    baseline_p95 = _CONV_SUMMARY.get("e2e_p95_ms", 0.0)
    p95 = summary["e2e_p95_ms"]
    if baseline_p95 > 0:
        # "held flat": generous noise envelope — an emulator flap batch
        # on shared CI jitters; a real fan-out regression (subscribers
        # serialized into the convergence path) blows through 5x+250ms
        assert p95 <= baseline_p95 * 5.0 + 250.0, (
            f"convergence p95 {p95:.1f}ms with {subscribers} subscribers "
            f"vs {baseline_p95:.1f}ms baseline: fan-out is not isolated"
        )
    # subscriber sweep: the same flap batch at other fan-out widths, so
    # one BENCH round records how delivery rate and encode share scale
    # with subscriber count (BENCH_STREAM_SWEEP, comma-separated counts;
    # BENCH_SMOKE pins tiny defaults)
    sweep_counts = [
        int(x)
        for x in os.environ.get("BENCH_STREAM_SWEEP", "16,256").split(",")
        if x.strip() and int(x) != subscribers
    ]
    sweep = []
    for count in sweep_counts:
        point = run_bench_convergence(
            nodes=nodes,
            flaps=flaps,
            backend=backend,
            measure_exporter=False,
            subscribers=count,
        )
        sweep.append(
            {
                "subscribers": count,
                "events_s": round(point["stream_events_per_s"], 1),
                "encode_share": point["stream_encode_share"],
                "class_hit_rate": point["stream_class_hit_rate"],
            }
        )
    _note(
        f"stream: {subscribers} subscriber(s) x {summary['nodes']}-node "
        f"flap batch -> {summary['stream_deltas']} deliveries "
        f"({summary['stream_events_per_s']:,.0f}/s), "
        f"{summary['stream_resyncs']} resync(s); encode share "
        f"{summary['stream_encode_share'] * 100:.1f}% (class hit rate "
        f"{summary['stream_class_hit_rate'] * 100:.0f}%); e2e p95 "
        f"{p95:.1f}ms vs {baseline_p95:.1f}ms without subscribers"
    )
    return {
        "metric": "stream_fanout_events_s",
        "value": round(summary["stream_events_per_s"], 1),
        "unit": (
            f"delta deliveries/s across {subscribers} concurrent "
            f"subscribeKvStore subscriber(s) ({summary['nodes']}-node "
            f"line emulator, {summary['flaps']} flap cycles)"
        ),
        "vs_baseline": 0.0,
        "baseline": "none",
        "subscribers": subscribers,
        "deliveries": summary["stream_deltas"],
        "resyncs": summary["stream_resyncs"],
        # the shared-encode meters (docs/Streaming.md): fraction of the
        # batch wall clock spent on REAL body serializations, and how
        # often subscribers reused a filter-class's shared bytes
        "encode_share": summary["stream_encode_share"],
        "encode_classes": summary["stream_encode_classes"],
        "class_hit_rate": summary["stream_class_hit_rate"],
        "sweep": sweep,
        "e2e_p95_ms": round(p95, 2),
        "baseline_e2e_p95_ms": round(baseline_p95, 2),
    }


def _bench_apsp() -> dict:
    """Seventh metric line: the blocked min-plus Floyd–Warshall APSP close
    (openr_tpu/apsp, docs/Apsp.md) on a synthetic WAN — cold close wall
    time (compile excluded), the warm re-close of a single-link weight
    event (rounds + ms, the O(dirty-blocks) path), and the
    FW-vs-batched-Dijkstra crossover sweep: at each node count the dense
    blocked close races the batched min-plus column solve for ALL sources
    (what serving the same all-pairs demand through the one-source batch
    machinery would cost), bracketing where the solver should hand off."""
    from openr_tpu.apsp import ApspState, np_floyd_warshall, build_weight_matrix
    from openr_tpu.ops.graph import compile_edges
    from openr_tpu.ops.spf import batched_spf
    from openr_tpu.topology import wan_edges

    n = int(os.environ.get("BENCH_APSP_N", "2048"))
    sweep = [
        int(x)
        for x in os.environ.get("BENCH_APSP_SWEEP", "256,512,1024").split(",")
        if x.strip()
    ]
    repeats = int(os.environ.get("BENCH_APSP_REPEATS", "3"))

    def graph_for(nodes):
        return compile_edges(wan_edges(nodes, degree=4, seed=7))

    graph = graph_for(n)
    apsp = ApspState(max_nodes=n)
    apsp.ensure(graph)  # compile + first close, excluded
    cold_times = []
    for _ in range(max(repeats, 1)):
        apsp.invalidate("bench_cold")
        apsp.ensure(graph)
        cold_times.append(apsp.close_ms_last)
    cold_ms = min(cold_times)

    # warm re-close of a single-link weight event: patch one real edge
    # (the first warm event compiles the seed + re-close executables and
    # is dropped, same compile-excluded convention as the cold loop)
    w_mut = graph.w.copy()
    pos = graph.e // 2
    warm_times = []
    rounds = 0
    for i in range(max(repeats, 1) + 1):
        w_mut = w_mut.copy()
        w_mut[pos] = int(w_mut[pos]) % 13 + 1 + i
        graph.w = w_mut
        graph.version += 1
        apsp.ensure(graph)
        if i:
            warm_times.append(apsp.close_ms_last)
        rounds = apsp.reclose_rounds_last or 0
    warm_ms = min(warm_times)

    # mem columns measured while ONLY the main state's FW triple is
    # resident (the sweep below stacks smaller states; ApspState
    # registers its matrices with the ledger itself)
    mem = _mem_columns("apsp", graph.n, ("apsp",), graph=graph)

    crossover = []
    handoff = None
    for nodes in sweep:
        g = compile_edges(wan_edges(nodes, degree=4, seed=7))
        sub = ApspState(max_nodes=nodes)
        sub.ensure(g)  # compile excluded
        sub.invalidate("bench_cold")
        t0 = time.perf_counter()
        sub.ensure(g)
        fw_ms = (time.perf_counter() - t0) * 1e3
        sources = np.arange(g.n_pad, dtype=np.int32)
        np.asarray(batched_spf(g, sources))  # compile excluded
        t0 = time.perf_counter()
        np.asarray(batched_spf(g, sources))
        dj_ms = (time.perf_counter() - t0) * 1e3
        crossover.append(
            {
                "nodes": nodes,
                "fw_close_ms": round(fw_ms, 3),
                "batched_dijkstra_ms": round(dj_ms, 3),
            }
        )
        if handoff is None and fw_ms < dj_ms:
            handoff = nodes
        sub.close()  # return the sweep state's ledger bytes
    # parity spot-check: the bench must not report a number for a wrong
    # matrix (cheap at the smallest sweep size)
    g_chk = compile_edges(wan_edges(sweep[0], degree=4, seed=7))
    chk = ApspState(max_nodes=sweep[0])
    chk.ensure(g_chk)
    ref = np_floyd_warshall(build_weight_matrix(g_chk), g_chk.overloaded)
    assert np.array_equal(chk.d, ref), "APSP bench parity check failed"
    chk.close()
    apsp.close()

    _note(
        f"apsp: {n}-node WAN blocked-FW close {cold_ms:.1f}ms cold / "
        f"{warm_ms:.1f}ms warm re-close ({rounds} round(s)); crossover "
        + ", ".join(
            f"{c['nodes']}n fw {c['fw_close_ms']:.0f}ms vs dj "
            f"{c['batched_dijkstra_ms']:.0f}ms"
            for c in crossover
        )
    )
    return {
        "metric": "fw_apsp_close_ms",
        "value": round(cold_ms, 3),
        "unit": (
            f"ms per cold blocked-FW all-pairs close ({n}-node WAN, "
            f"compile excluded, best of {len(cold_times)})"
        ),
        "vs_baseline": 0.0,
        "baseline": "none",
        "warm_reclose_ms": round(warm_ms, 3),
        "reclose_rounds": rounds,
        "crossover": crossover,
        "crossover_nodes": handoff,
        **mem,
    }


def _bench_fleet() -> dict:
    """Eighth metric line: continuous fleet-observation overhead — the
    standard convergence flap batch re-run with the fleet observer
    (openr_tpu/fleet) attached over every node's real ctrl socket,
    scraping + streaming + evaluating the SLO rules continuously. The
    metric is the mean watchdog tick cost (scrape sweep fold + rule
    evaluation over the store); the line carries the attached run's
    convergence e2e p95 next to the detached baseline's (the convergence
    line measured earlier on the same config) so a fleet watcher that
    perturbs the convergence path is caught, not just a slow one."""
    from openr_tpu.testing.decision_harness import run_bench_convergence

    nodes = int(os.environ.get("BENCH_CONV_NODES", "5"))
    flaps = int(os.environ.get("BENCH_CONV_FLAPS", "2"))
    backend = os.environ.get("BENCH_CONV_BACKEND", "tpu")
    summary = run_bench_convergence(
        nodes=nodes,
        flaps=flaps,
        backend=backend,
        measure_exporter=False,
        fleet_observer=True,
    )
    baseline_p95 = _CONV_SUMMARY.get("e2e_p95_ms", 0.0)
    p95 = summary["e2e_p95_ms"]
    if baseline_p95 > 0:
        # the same held-flat envelope as the fan-out line: an observer
        # that serializes into the convergence path blows through it
        assert p95 <= baseline_p95 * 5.0 + 250.0, (
            f"convergence p95 {p95:.1f}ms with the fleet observer "
            f"attached vs {baseline_p95:.1f}ms detached: the watcher is "
            f"not isolated"
        )
    _note(
        f"fleet: observer on the {summary['nodes']}-node flap batch -> "
        f"{summary['fleet_ticks']} watchdog tick(s) at "
        f"{summary['fleet_tick_ms']:.3f}ms/tick, "
        f"{summary['fleet_scrapes']} scrapes at "
        f"{summary['fleet_scrape_ms']:.3f}ms; e2e p95 {p95:.1f}ms "
        f"attached vs {baseline_p95:.1f}ms detached"
    )
    return {
        "metric": "fleet_watch_overhead_ms",
        "value": round(max(summary["fleet_tick_ms"], 1e-4), 4),
        "unit": (
            f"ms mean SLO-watchdog tick (fleet observer attached to the "
            f"{summary['nodes']}-node line emulator flap batch over real "
            f"ctrl sockets)"
        ),
        "vs_baseline": 0.0,
        "baseline": "none",
        "fleet_ticks": summary["fleet_ticks"],
        "fleet_scrapes": summary["fleet_scrapes"],
        "fleet_scrape_ms": summary["fleet_scrape_ms"],
        "attached_e2e_p95_ms": round(p95, 2),
        "baseline_e2e_p95_ms": round(baseline_p95, 2),
    }


def _bench_journal() -> dict:
    """Ninth metric line: state-journal recording overhead — the standard
    convergence flap batch re-run with every node journaling its KvStore
    publications and RIB deltas (openr_tpu/journal). The metric is the
    mean per-record cost from the sampled `journal.record_ms` guard; the
    line carries the journal-on run's convergence e2e p95 next to the
    journal-off baseline's (the convergence line measured earlier on the
    same config) under the same held-flat envelope as the fan-out and
    fleet lines, and every node's final state is replay-verified against
    the CPU oracle (docs/Journal.md)."""
    from openr_tpu.testing.decision_harness import run_bench_convergence

    nodes = int(os.environ.get("BENCH_CONV_NODES", "5"))
    flaps = int(os.environ.get("BENCH_CONV_FLAPS", "2"))
    backend = os.environ.get("BENCH_CONV_BACKEND", "tpu")
    summary = run_bench_convergence(
        nodes=nodes,
        flaps=flaps,
        backend=backend,
        measure_exporter=False,
        journal=True,
    )
    baseline_p95 = _CONV_SUMMARY.get("e2e_p95_ms", 0.0)
    p95 = summary["e2e_p95_ms"]
    if baseline_p95 > 0:
        # held-flat envelope vs the journal-off baseline: a recorder
        # that serializes into the convergence path blows through it
        assert p95 <= baseline_p95 * 5.0 + 250.0, (
            f"convergence p95 {p95:.1f}ms with the state journal "
            f"recording vs {baseline_p95:.1f}ms journal-off: the "
            f"recorder is not O(changes)"
        )
    verified = summary["journal_replay_verified"]
    assert verified == summary["journal_nodes"], (
        f"replay determinism broke under the flap batch: only {verified} "
        f"of {summary['journal_nodes']} nodes' replayed RIBs matched the "
        f"CPU oracle"
    )
    _note(
        f"journal: {summary['journal_records']} records over the "
        f"{summary['nodes']}-node flap batch at "
        f"{summary['journal_record_us']:.1f}us/record (sampled), "
        f"{verified}/{summary['journal_nodes']} nodes replay-verified; "
        f"e2e p95 {p95:.1f}ms journal-on vs {baseline_p95:.1f}ms off"
    )
    return {
        "metric": "journal_record_us",
        "value": round(max(summary["journal_record_us"], 1e-4), 4),
        "unit": (
            f"us mean journal record (sampled guard, every node of the "
            f"{summary['nodes']}-node line emulator flap batch recording "
            f"publications + RIB deltas)"
        ),
        "vs_baseline": 0.0,
        "baseline": "none",
        "journal_records": summary["journal_records"],
        "journal_evicted": summary["journal_evicted"],
        "journal_replay_verified": verified,
        "journal_nodes": summary["journal_nodes"],
        "attached_e2e_p95_ms": round(p95, 2),
        "baseline_e2e_p95_ms": round(baseline_p95, 2),
    }


def _bench_convergence_under_loss() -> dict:
    """Tenth metric line: convergence under hostile transport — the
    standard flap batch re-run behind a seeded chaos mesh dropping a
    fraction of every KvStore RPC (openr_tpu/testing/chaos.py). The
    dissemination plane has to eat the drops with retried full syncs and
    anti-entropy repair, so the p95 is allowed a much looser envelope
    than the attached lines — the assertion is that loss degrades
    convergence boundedly instead of wedging it (a wedged store never
    converges and the flap batch itself times out). The line carries the
    drop count as evidence that the mesh actually interfered."""
    from openr_tpu.testing.decision_harness import run_bench_convergence

    nodes = int(os.environ.get("BENCH_CONV_NODES", "5"))
    flaps = int(os.environ.get("BENCH_CONV_FLAPS", "2"))
    backend = os.environ.get("BENCH_CONV_BACKEND", "tpu")
    loss = float(os.environ.get("BENCH_LOSS_RATE", "0.15"))
    seed = int(os.environ.get("BENCH_LOSS_SEED", "1"))
    summary = run_bench_convergence(
        nodes=nodes,
        flaps=flaps,
        backend=backend,
        measure_exporter=False,
        chaos_loss=loss,
        chaos_seed=seed,
    )
    baseline_p95 = _CONV_SUMMARY.get("e2e_p95_ms", 0.0)
    p95 = summary["e2e_p95_ms"]
    if baseline_p95 > 0:
        # bounded-degradation envelope vs the lossless baseline: wide,
        # because every dropped flood costs a full-sync retry on a
        # jittered backoff — but a store that livelocks under loss
        # (re-flooding without repairing) blows through even this
        assert p95 <= baseline_p95 * 20.0 + 2000.0, (
            f"convergence p95 {p95:.1f}ms under {loss:.0%} KvStore RPC "
            f"loss vs {baseline_p95:.1f}ms clean: the dissemination "
            f"plane is not recovering boundedly from drops"
        )
    _note(
        f"loss: e2e p95 {p95:.1f}ms under {loss:.0%} seeded RPC loss "
        f"(seed {seed}, {summary['chaos_kv_dropped']} RPCs dropped) vs "
        f"{baseline_p95:.1f}ms clean"
    )
    return {
        "metric": "convergence_under_loss_p95_ms",
        "value": round(p95, 2),
        "unit": (
            f"ms p95 hello-to-programmed-route under {loss:.0%} seeded "
            f"KvStore RPC loss ({summary['nodes']}-node line emulator, "
            f"{summary['flaps']} flap batches, chaos seed {seed})"
        ),
        "vs_baseline": 0.0,
        "baseline": "none",
        "chaos_loss": loss,
        "chaos_seed": seed,
        "chaos_kv_dropped": summary["chaos_kv_dropped"],
        "spans": summary["spans_total"],
        "clean_e2e_p95_ms": round(baseline_p95, 2),
    }


def main(argv=None) -> None:
    if os.environ.get("BENCH_SMOKE") == "1":
        _apply_smoke_env()
    import jax

    from openr_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    # the device every line of this run was measured on (a backend that
    # fails to initialize raises here, before any line is printed)
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "n_devices": len(devices),
    }
    topo = os.environ.get("BENCH_TOPO", "wan")
    results = [bench_grid() if topo == "grid" else bench_wan()]
    if os.environ.get("BENCH_CONVERGENCE", "1") == "1":
        results.append(_bench_convergence())
    if os.environ.get("BENCH_TE", "1") == "1":
        results.append(_bench_te())
    if os.environ.get("BENCH_SCALE", "1") == "1":
        results.append(_bench_scale())
    if os.environ.get("BENCH_EXPORTER", "1") == "1":
        results.append(_bench_exporter())
    if (
        os.environ.get("BENCH_STREAM", "1") == "1"
        and os.environ.get("BENCH_CONVERGENCE", "1") == "1"
    ):
        # defined against the convergence flap batch: without the
        # baseline run there is no held-flat comparison to make
        results.append(_bench_stream())
    if os.environ.get("BENCH_APSP", "1") == "1":
        results.append(_bench_apsp())
    if (
        os.environ.get("BENCH_FLEET", "1") == "1"
        and os.environ.get("BENCH_CONVERGENCE", "1") == "1"
    ):
        # defined against the convergence flap batch: the detached
        # baseline p95 is the held-flat comparison
        results.append(_bench_fleet())
    if (
        os.environ.get("BENCH_JOURNAL", "1") == "1"
        and os.environ.get("BENCH_CONVERGENCE", "1") == "1"
    ):
        # defined against the convergence flap batch: the journal-off
        # baseline p95 is the held-flat comparison
        results.append(_bench_journal())
    if (
        os.environ.get("BENCH_LOSS", "1") == "1"
        and os.environ.get("BENCH_CONVERGENCE", "1") == "1"
    ):
        # defined against the convergence flap batch: the lossless
        # baseline p95 anchors the bounded-degradation envelope
        results.append(_bench_convergence_under_loss())
    from openr_tpu.utils.build_info import (
        ARTIFACT_SCHEMA_VERSION,
        build_fingerprint,
    )

    fingerprint = build_fingerprint()
    for result in results:
        # artifact provenance stamp: BENCH_r* consumers trace every line
        # to the device, code and field contract that produced it
        result.update(device)
        result["schema_version"] = ARTIFACT_SCHEMA_VERSION
        result["build"] = fingerprint
        print(json.dumps(result))


if __name__ == "__main__":
    main()
