"""chip_smoke.py — the quickest proof that openr-tpu still starts on the chip.

One process, run from the root of a checkout: `python3 chip_smoke.py`.
Everything is generated from `--seed`; nothing is fetched; no child
process imports JAX (the only children are `make` building native/).

  stage A  the served path at BASELINE config 2's size: one OpenrDaemon
           (solver_backend=tpu, every other setting at its default, Fib
           in dryrun on the mock handler) fed the 9,556-node Clos through
           its ctrl socket, then 32 topology events, each awaited until
           Fib has it; FIB == CPU oracle, every fallback/failure counter 0.
  stage B  every other jitted family through the TPU compiler once, at
           the sizes of `CHIP` below, each against its tier-1 oracle.
  stage C  with >= 4 devices: stage A's load and 8 events on
           solver_mesh (4,1) and (2,2), with proof of spread.

Refuses to run unless jax.devices()[0].platform == "tpu" and never sets
JAX_PLATFORMS. `--cpu-rehearsal` runs the same code at toy sizes on
whatever JAX finds, for tier-1 and for rehearsing a change off the chip;
its output says it is not a chip run and carries no "ok" verdict.

Any failed check or exception ends the run at once with a non-zero exit.
The last stdout line of a passing chip run is
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time
from typing import Dict, List, Optional, Tuple

T_START = time.perf_counter()
AREA = "0"


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Problem sizes: the chip run's follow BASELINE.md's configs (1k
    grid, 100k-node WAN x 128 sources, 50k-node KSP)."""

    fabric: dict  # topology.fabric_edges arguments (3-tier Clos)
    wan_n: int  # batched_spf WAN nodes x wan_sources rows
    wan_sources: int
    ksp_n: int  # per-row-weights (KSP) WAN nodes x ksp_rows rows
    ksp_rows: int
    hub_leaves: int  # star that disqualifies sliced-ELL (edge-list family)
    grid_side: int  # ecmp_dag grid
    apsp_n: int  # blocked-FW close (device), rows spot-checked
    apsp_ref_n: int  # full-matrix comparison with np_floyd_warshall
    te_steps: int
    te_scenarios: int


CHIP = Sizes(
    fabric=dict(pods=170),  # 9,556 nodes, 77,520 links
    wan_n=100_000, wan_sources=128, ksp_n=50_000, ksp_rows=16,
    hub_leaves=1100, grid_side=32, apsp_n=2048, apsp_ref_n=256,
    te_steps=48, te_scenarios=4,
)
REHEARSAL = Sizes(
    fabric=dict(
        pods=3, planes=2, ssw_per_plane=2, fsw_per_pod=4, rsw_per_pod=6
    ),
    wan_n=400, wan_sources=8, ksp_n=300, ksp_rows=8,
    hub_leaves=1100, grid_side=6, apsp_n=256, apsp_ref_n=64,
    te_steps=8, te_scenarios=4,
)


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:7.1f}s] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    """One named pass/fail line; a failure ends the run non-zero."""
    if not cond:
        print(f"FAIL  {what}", flush=True)
        raise SystemExit(1)
    say(f"ok    {what}")


# ---------------------------------------------------------------------------
# the LSDB: one copy goes to the daemon as KvStore bytes, an independent
# copy feeds the CPU oracle
# ---------------------------------------------------------------------------


class Lsdb:
    def __init__(self, sz: Sizes) -> None:
        from openr_tpu.lsdb import LinkState
        from openr_tpu.lsdb.prefix_state import PrefixState
        from openr_tpu.topology import build_adj_dbs, fabric_edges

        edges = fabric_edges(**sz.fabric)
        self.n_links = len(edges)
        self.adj = build_adj_dbs(edges)
        self.nodes = sorted(self.adj)
        self.prefix_of = {
            node: f"10.{i // 256}.{i % 256}.0/24"
            for i, node in enumerate(self.nodes)
        }
        self.announced = {node: True for node in self.nodes}
        self.versions: Dict[str, int] = {}
        self.link_state = LinkState(AREA)
        self.link_state.bulk_update_adjacency_databases(
            list(self.adj.values())
        )
        self.prefix_state = PrefixState()
        for node in self.nodes:
            self.prefix_state.update_prefix_database(self._prefix_db(node))

    def _prefix_db(self, node: str):
        from openr_tpu.types import IpPrefix, PrefixDatabase, PrefixEntry

        entries = (
            [PrefixEntry(IpPrefix(self.prefix_of[node]))]
            if self.announced[node]
            else []
        )
        return PrefixDatabase(node, entries, area=AREA)

    # -- mutations: each returns the KvStore keys it touched --------------

    def set_metric(self, a: str, b: str, metric: int) -> List[str]:
        """Both directions of link a<->b to `metric`."""
        for node, peer in ((a, b), (b, a)):
            db = self.adj[node]
            self._replace_adj(
                node,
                [
                    dataclasses.replace(adj, metric=metric)
                    if adj.other_node_name == peer
                    else adj
                    for adj in db.adjacencies
                ],
            )
        return [f"adj:{a}", f"adj:{b}"]

    def set_link(self, a: str, b: str, up: bool, saved: dict) -> List[str]:
        """Take link a<->b out of (or back into) both adjacency dbs."""
        for node, peer in ((a, b), (b, a)):
            db = self.adj[node]
            if up:
                adjs = list(db.adjacencies) + [saved.pop((node, peer))]
            else:
                saved[(node, peer)] = next(
                    x for x in db.adjacencies if x.other_node_name == peer
                )
                adjs = [
                    x for x in db.adjacencies if x.other_node_name != peer
                ]
            self._replace_adj(node, adjs)
        return [f"adj:{a}", f"adj:{b}"]

    def set_overload(self, node: str, overloaded: bool) -> List[str]:
        self.adj[node] = dataclasses.replace(
            self.adj[node], is_overloaded=overloaded
        )
        self.link_state.update_adjacency_database(self.adj[node])
        return [f"adj:{node}"]

    def set_announced(self, node: str, announced: bool) -> List[str]:
        self.announced[node] = announced
        self.prefix_state.update_prefix_database(self._prefix_db(node))
        return [f"prefix:{node}"]

    def _replace_adj(self, node: str, adjacencies) -> None:
        self.adj[node] = dataclasses.replace(
            self.adj[node], adjacencies=list(adjacencies)
        )
        self.link_state.update_adjacency_database(self.adj[node])

    # -- the daemon's side: versioned KvStore values as ctrl JSON ---------

    def key_vals(self, keys: List[str]) -> Dict[str, dict]:
        from openr_tpu.kvstore.wire import value_to_json
        from openr_tpu.types import Value
        from openr_tpu.utils import serializer

        out = {}
        for key in keys:
            kind, node = key.split(":", 1)
            obj = self.adj[node] if kind == "adj" else self._prefix_db(node)
            self.versions[key] = self.versions.get(key, 0) + 1
            out[key] = value_to_json(
                Value(self.versions[key], node, serializer.dumps(obj))
            )
        return out

    def all_keys(self) -> List[str]:
        return [f"adj:{n}" for n in self.nodes] + [
            f"prefix:{n}" for n in self.nodes
        ]


def topology_events(lsdb: Lsdb, n_events: int):
    """(description, apply() -> keys, touched nodes) per event. The mix the
    served path sees in production: metric decreases and increases on
    fsw0_1<->rsw0_1 (warm path; increases run the invalidation fixpoint),
    a link of the vantage itself down and up (poisons the delta, forces a
    cold solve), a node overload set and cleared, a prefix withdrawn and
    re-announced. Solve #17 (the second PhaseClock sample) lands on a
    metric event so delta_extract is timed against the device."""
    saved: dict = {}
    metrics = [5, 1, 7, 2, 9, 3, 6, 1]
    link_down, link_up, ov_set, ov_clear, withdraw, announce = (
        ("rsw0_0<->fsw0_3 down",
         lambda: lsdb.set_link("rsw0_0", "fsw0_3", False, saved), ["fsw0_3"]),
        ("rsw0_0<->fsw0_3 up",
         lambda: lsdb.set_link("rsw0_0", "fsw0_3", True, saved), ["fsw0_3"]),
        ("fsw0_2 overload set",
         lambda: lsdb.set_overload("fsw0_2", True), ["fsw0_2", "rsw0_2"]),
        ("fsw0_2 overload cleared",
         lambda: lsdb.set_overload("fsw0_2", False), ["fsw0_2", "rsw0_2"]),
        ("rsw1_1 prefix withdrawn",
         lambda: lsdb.set_announced("rsw1_1", False), ["rsw1_1"]),
        ("rsw1_1 prefix re-announced",
         lambda: lsdb.set_announced("rsw1_1", True), ["rsw1_1"]),
    )
    if n_events >= 32:
        specials = {5: link_down, 6: link_up, 10: ov_set, 11: ov_clear,
                    20: withdraw, 21: announce}
    else:  # the short schedule stage C repeats on each mesh
        specials = {2: link_down, 3: link_up, 5: ov_set, 6: ov_clear}
    events = []
    m = 0
    for i in range(n_events):
        if i in specials:
            events.append(specials[i])
            continue
        metric = metrics[m % len(metrics)]
        m += 1
        events.append(
            (f"fsw0_1<->rsw0_1 metric -> {metric}",
             lambda metric=metric: lsdb.set_metric("fsw0_1", "rsw0_1", metric),
             ["fsw0_1", "rsw0_1"])
        )
    return events


# ---------------------------------------------------------------------------
# stages A and C: the served path
# ---------------------------------------------------------------------------


async def wait_for(predicate, what: str, timeout: float) -> None:
    deadline = time.monotonic() + timeout
    while not predicate():
        if time.monotonic() > deadline:
            check(False, f"{what} within {timeout:.0f}s")
        await asyncio.sleep(0.002)


async def served_path(
    sz: Sizes, seed: int, *, label: str, mesh: Optional[Tuple[int, int]],
    n_events: int, on_chip: bool,
) -> dict:
    import random

    import jax

    from openr_tpu.config import Config
    from openr_tpu.ctrl.client import CtrlClient, decode_obj
    from openr_tpu.kvstore.native import NativeKvTable
    from openr_tpu.kvstore.transport import InProcessTransport
    from openr_tpu.openr import OpenrDaemon
    from openr_tpu.platform import MockFibHandler
    from openr_tpu.solver import SpfSolver
    from openr_tpu.spark.io_provider import MockIoNetwork
    from openr_tpu.types import IpPrefix

    me = "rsw0_0"
    rng = random.Random(seed)
    t0 = time.perf_counter()
    lsdb = Lsdb(sz)
    say(
        f"stage {label}: LSDB {len(lsdb.nodes)} nodes, {lsdb.n_links} links, "
        f"one /24 per node, vantage {me}, solver_mesh {mesh} "
        f"(built in {time.perf_counter() - t0:.1f}s)"
    )
    decision_cfg = {"solver_backend": "tpu"}
    if mesh is not None:
        decision_cfg["solver_mesh"] = list(mesh)
    daemon = OpenrDaemon(
        Config.from_dict(
            {"node_name": me, "dryrun": True, "decision_config": decision_cfg}
        ),
        io_provider=MockIoNetwork().provider(me),
        kv_transport=InProcessTransport(),
        fib_service=MockFibHandler(),
        ctrl_port=0,
    )
    in_use0 = [
        (d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()
    ]
    port = await daemon.start()
    engine = type(daemon.kvstore.db(AREA).store).__name__
    check(
        isinstance(daemon.kvstore.db(AREA).store, NativeKvTable),
        f"KvStore engine is the native one ({engine})",
    )
    dcount = daemon.decision.counters
    fcount = daemon.fib.counters
    out: dict = {}
    async with CtrlClient(port=port) as client:

        async def send(keys: List[str]) -> None:
            runs = dcount.get("decision.route_build_runs", 0)
            await client.call(
                "setKvStoreKeyVals", area=AREA, key_vals=lsdb.key_vals(keys)
            )
            await wait_for(
                lambda: dcount.get("decision.route_build_runs", 0) > runs,
                "Decision rebuilt routes", 900.0,
            )
            await wait_for(
                lambda: fcount.get("fib.process_route_db", 0)
                == dcount.get("decision.route_updates_published", 0),
                "Fib took every published route delta", 60.0,
            )

        oracle = SpfSolver(me)  # on the independent LSDB copy
        sample_nodes = rng.sample(lsdb.nodes, min(48, len(lsdb.nodes)))

        async def fib_equals_oracle(when: str) -> None:
            want = oracle.build_route_db(
                me, {AREA: lsdb.link_state}, lsdb.prefix_state
            )
            want_u = {
                e.prefix: frozenset(e.to_unicast_route().nexthops)
                for e in want.unicast_entries.values()
                if not e.do_not_install
            }
            want_m = {
                e.label: frozenset(e.to_mpls_route().nexthops)
                for e in want.mpls_entries.values()
            }
            db = await client.call("getRouteDb")
            got_u = {
                r.dest: frozenset(r.nexthops)
                for r in map(decode_obj, db["unicast_routes"])
            }
            got_m = {
                r.top_label: frozenset(r.nexthops)
                for r in map(decode_obj, db["mpls_routes"])
            }
            check(
                got_u == want_u and got_m == want_m,
                f"{when}: FIB == CPU oracle on every prefix and next-hop "
                f"set ({len(want_u)} unicast, {len(want_m)} mpls)",
            )

        async def sample_equals_oracle(touched: List[str]) -> int:
            nodes = sorted((set(sample_nodes) | set(touched)) - {me})
            prefixes = [lsdb.prefix_of[n] for n in nodes]
            routes = await client.call(
                "getUnicastRoutesFiltered", prefixes=prefixes
            )
            got = {
                r.dest: frozenset(r.nexthops) for r in map(decode_obj, routes)
            }
            want = {}
            for p in map(IpPrefix, prefixes):
                entries = lsdb.prefix_state.prefixes.get(p)
                scratch: dict = {}
                if entries:
                    oracle.build_unicast_route(
                        scratch, me, p, entries,
                        {AREA: lsdb.link_state}, lsdb.prefix_state,
                    )
                if p in scratch and not scratch[p].do_not_install:
                    want[p] = frozenset(scratch[p].to_unicast_route().nexthops)
            if got != want:
                bad = [
                    str(p) for p in set(got) | set(want)
                    if got.get(p) != want.get(p)
                ]
                check(False, f"sampled FIB routes == CPU oracle (bad: {bad[:8]})")
            return len(prefixes)

        t0 = time.perf_counter()
        await send(lsdb.all_keys())
        out["load_s"] = time.perf_counter() - t0
        out["first_route_s"] = time.perf_counter() - T_START
        say(
            f"stage {label}: LSDB ingested through ctrl setKvStoreKeyVals and "
            f"first routes programmed in {out['load_s']:.1f}s "
            f"(first compile + cold solve included)"
        )
        await fib_equals_oracle(f"stage {label} after load")

        event_s = []
        for i, (desc, apply, touched) in enumerate(
            topology_events(lsdb, n_events)
        ):
            t0 = time.perf_counter()
            await send(apply())
            event_s.append(time.perf_counter() - t0)
            n = await sample_equals_oracle(touched)
            say(
                f"      event {i + 1:2d}/{n_events} {desc}: "
                f"{event_s[-1] * 1e3:.0f}ms to programmed FIB, "
                f"{n} sampled prefixes == oracle"
            )
        out["event_s"] = event_s
        await fib_equals_oracle(f"stage {label} after event {n_events}")

        # -- queries over the ctrl socket ---------------------------------
        computed = await client.call("getRouteDbComputed")
        check(
            len(computed["unicast_routes"]) == len(lsdb.nodes) - 1,
            f"getRouteDbComputed: {len(computed['unicast_routes'])} unicast "
            f"routes (every node's /24 but my own)",
        )
        counters = await client.call("getCounters")
        hists = await client.call("getHistograms")
        health = await client.call("getSolverHealth")
        mem = await client.call("getDeviceMemory")
        traces = await client.call("getSolveTraces")
        check_served_counters(
            label, counters, hists, health, mem, traces,
            full=n_events >= 32, on_chip=on_chip,
        )
        out["counters"] = {
            k: v for k, v in counters.items()
            if k.startswith(("decision.spf.", "decision.mem.", "decision.route"))
        }
        out["phase_ms"] = {
            k: v for k, v in hists.items() if k.startswith("decision.spf.")
        }

    # -- where the solve's arrays live (in-process: no ctrl surface) ------
    platform = jax.devices()[0].platform
    solve = daemon.decision.solver.primary._solves[(AREA, me)][1]
    arrays = [solve._d_dev]
    for v in solve._dev.values():
        arrays.extend(v if isinstance(v, tuple) else [v])
    arrays = [a for a in arrays if hasattr(a, "devices")]
    placed = {d.platform for a in arrays for d in a.devices()}
    check(
        placed == {platform} and len(arrays) >= 4,
        f"all {len(arrays)} arrays the {solve._dev['kind']} solve keeps live "
        f"on platform {sorted(placed)}",
    )
    if mesh is not None:
        want_n = mesh[0] * mesh[1]
        spread = {d.id for d in solve._d_dev.devices()}
        check(
            len(spread) == want_n,
            f"resident D {solve._d_dev.shape} is spread over {len(spread)} "
            f"distinct devices ({solve._d_dev.sharding.spec})",
        )
        in_use1 = [
            (d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()
        ]
        if on_chip:
            rose = [
                d.id for d, b0, b1 in zip(jax.devices(), in_use0, in_use1)
                if d.id in spread and b1 > b0
            ]
            check(
                len(rose) == want_n,
                f"bytes_in_use rose on every mesh device "
                f"({[(b1 - b0) for b0, b1 in zip(in_use0, in_use1)]})",
            )
    out["layout"] = solve._dev["kind"]
    await daemon.stop()
    return out


def check_served_counters(
    label, counters, hists, health, mem, traces, *, full: bool, on_chip: bool
) -> None:
    c = counters.get
    say(
        f"stage {label}: full_solves {c('decision.spf.full_solves', 0)}, "
        f"incremental_solves {c('decision.spf.incremental_solves', 0)}, "
        f"delta_columns {c('decision.spf.delta_columns', 0)}, "
        f"route_build_delta_runs {c('decision.route_build_delta_runs', 0)}"
    )
    if full:
        check(c("decision.spf.full_solves", 0) >= 2, "full_solves >= 2")
        check(
            c("decision.spf.incremental_solves", 0) >= 20,
            "incremental_solves >= 20",
        )
        check(c("decision.spf.delta_columns", 0) > 0, "delta_columns > 0")
        for phase in (
            "refresh", "prepare", "h2d", "relax", "delta_extract",
            "mirror_patch", "d2h",
        ):
            h = hists.get(f"decision.spf.phase.{phase}_ms") or {}
            check(
                h.get("count", 0) > 0,
                f"decision.spf.phase.{phase}_ms recorded "
                f"({h.get('count', 0)}x, avg {h.get('avg', 0):.2f}ms)",
            )
    else:
        check(c("decision.spf.full_solves", 0) >= 1, "full_solves >= 1")
        check(
            c("decision.spf.incremental_solves", 0) >= 1,
            "incremental_solves >= 1",
        )
    zero = [
        "decision.spf.fallback_active",
        "decision.spf.fallback_solves",
        "decision.spf.breaker_trips",
        "decision.spf.solver_retries",
        "decision.route_build_delta_errors",
        "decision.route_build_errors",
        "decision.mem.capacity_refusals",
        "decision.spf.apsp_fallback_closes",
    ] + sorted(
        k for k in counters if k.startswith("decision.spf.solver_failures")
    )
    bad = {k: counters[k] for k in zero if counters.get(k, 0) != 0}
    check(
        not bad,
        f"every fallback / breaker / retry / failure (deadline included) / "
        f"refusal counter is 0 {bad or ''}",
    )
    check(
        health["degraded"] is False and health["breaker_state"] == "closed",
        f"getSolverHealth: breaker {health['breaker_state']}, "
        f"last solve {health['solve_ms_last']:.1f}ms",
    )
    events = [t["event"] for t in traces["traces"]]
    check(
        traces["enabled"] and events and set(events) == {"solve"},
        f"getSolveTraces: {len(events)} traces, all plain solves, "
        f"{sum(t['compile_cache_misses'] for t in traces['traces'])} "
        f"executables compiled inside them",
    )
    cap, rec = mem["capacity"], mem["reconcile"]
    say(
        f"stage {label}: getDeviceMemory capacity {cap['capacity_bytes']} "
        f"({cap['source']}), reconcile backend {rec['backend_bytes']} vs "
        f"ledger {rec['ledger_bytes']} ({rec['source']})"
    )
    check(mem["exact"], "memory ledger accounting is exact")
    if on_chip:
        check(
            cap["source"] == "memory_stats"
            and rec["source"] == "memory_stats",
            "capacity and reconcile both read the chip's memory_stats "
            "(predict_fit gates on the device, not on 'no source -> admit')",
        )


# ---------------------------------------------------------------------------
# stage B: every other jitted family, once, against its oracle
# ---------------------------------------------------------------------------


def native_rows(graph, sources, masked=None):
    """NativeSpfSolver distance rows; masked[i] = edge positions pinned to
    INF for row i only (the KSP link-ignore re-solve)."""
    import numpy as np

    from openr_tpu.ops.graph import INF
    from openr_tpu.solver.native_spf import NativeSpfSolver

    solver = NativeSpfSolver(graph)
    rows = []
    for i, s in enumerate(sources):
        pos = list(masked[i]) if masked is not None else []
        for p in pos:
            solver.set_weight(p, INF)
        rows.append(solver.run(int(s)))
        for p in pos:
            solver.set_weight(p, int(graph.w[p]))
    solver.close()
    return np.stack(rows)


def stage_b(sz: Sizes, seed: int) -> None:
    import numpy as np

    from openr_tpu.ops.graph import INF, compile_edges

    rng = np.random.default_rng(seed)

    def timed(what: str, fn):
        t0 = time.perf_counter()
        out = fn()
        say(f"      {what}: {time.perf_counter() - t0:.1f}s")
        return out

    # -- batched_spf: the widest [S, n_pad] the repo claims ---------------
    from openr_tpu.ops.spf import batched_spf
    from openr_tpu.topology import wan_edges

    graph = compile_edges(wan_edges(sz.wan_n, degree=4, seed=3))
    check(graph.sell is not None, "WAN degree profile qualifies for sliced-ELL")
    sources = rng.choice(graph.n, size=sz.wan_sources, replace=False).astype(
        np.int32
    )
    d = timed(
        f"batched_spf wan{graph.n} x {sz.wan_sources} compile + solve",
        lambda: batched_spf(graph, sources).block_until_ready(),
    )
    pick = [0, sz.wan_sources // 2, sz.wan_sources - 1]
    got = np.asarray(d[np.asarray(pick)])
    check(
        d.shape == (sz.wan_sources, graph.n_pad)
        and np.array_equal(got[:, : graph.n], native_rows(graph, sources[pick])),
        f"batched_spf [{d.shape[0]}, {d.shape[1]}]: 3 sampled rows == "
        f"native Dijkstra",
    )
    del d

    # -- per-row weights (KSP rows): sell vw cold + warm, edge-list vw ----
    from openr_tpu.ops.spf import batched_spf_vw, sell_fixpoint_masked

    graph = compile_edges(wan_edges(sz.ksp_n, degree=4, seed=5))
    me = graph.node_index["w0"]
    rows = np.full(sz.ksp_rows, me, dtype=np.int32)
    up = np.nonzero(graph.w[: graph.e] < INF)[0]
    masked = [[]] + [
        [int(p) for p in rng.choice(up, size=8, replace=False)]
        for _ in range(sz.ksp_rows - 1)
    ]
    want = native_rows(graph, rows, masked)
    cold = timed(
        f"sell_fixpoint_masked wan{graph.n} x {sz.ksp_rows} cold",
        lambda: np.asarray(
            sell_fixpoint_masked(graph.sell, rows, graph.overloaded, masked)
        ),
    )
    check(
        np.array_equal(cold[:, : graph.n], want),
        "_sell_solver_vw: every link-ignore row == native Dijkstra",
    )
    base = batched_spf(graph, rows)  # unpenalized rows: the warm seed
    warm = timed(
        "sell_fixpoint_masked warm (seeded from the base rows)",
        lambda: np.asarray(
            sell_fixpoint_masked(
                graph.sell, rows, graph.overloaded, masked, d_prev=base
            )
        ),
    )
    check(
        np.array_equal(warm[:, : graph.n], want),
        "_sell_solver_vw_warm: every link-ignore row == native Dijkstra",
    )
    w_rows = np.tile(graph.w, (sz.ksp_rows, 1))
    for i, pos in enumerate(masked):
        w_rows[i, pos] = INF
    vw = timed(
        "batched_spf_vw (edge-list per-row weights)",
        lambda: np.asarray(batched_spf_vw(graph, rows, w_rows)),
    )
    check(
        np.array_equal(vw[:, : graph.n], want),
        "batched_spf_vw: every link-ignore row == native Dijkstra",
    )

    # -- edge-list family on a graph sliced-ELL refuses -------------------
    from openr_tpu.lsdb import LinkState
    from openr_tpu.solver.tpu import _AreaSolve
    from openr_tpu.topology import build_adj_dbs

    leaves = [f"leaf{i:04d}" for i in range(sz.hub_leaves)]
    edges = [("hub", leaf, 1 + i % 5) for i, leaf in enumerate(leaves)]
    edges += [
        (leaves[i], leaves[i + 1], 1 + i % 3)
        for i in range(0, sz.hub_leaves - 1, 2)
    ]
    dbs = build_adj_dbs(edges)
    ls = LinkState(AREA)
    ls.bulk_update_adjacency_databases(list(dbs.values()))
    solve = timed(
        f"_AreaSolve on a {len(dbs)}-node hub (edge-list cold)",
        lambda: _AreaSolve(ls, "leaf0000"),
    )
    check(
        solve.graph.sell is None and solve._dev["kind"] == "bf",
        "hub in-degree disqualifies sliced-ELL: edge-list layout serves",
    )

    def area_equals_native(what: str) -> None:
        g = solve.graph
        src = [g.node_index[s] for s in solve.sources]
        check(
            np.array_equal(
                solve.d[: len(src), : g.n], native_rows(g, src)
            ),
            f"{what}: every batch row == native Dijkstra",
        )

    area_equals_native("_bf_fixpoint")
    for metric in (9, 2):  # an increase (invalidation fixpoint), a decrease
        ev = [
            (a, b, metric if {a, b} == {"hub", "leaf0009"} else w)
            for a, b, w in edges
        ]
        new = build_adj_dbs(ev)
        ls.update_adjacency_database(new["hub"])
        ls.update_adjacency_database(new["leaf0009"])
        timed(f"warm event hub<->leaf0009 -> {metric}", solve.refresh)
        check(
            solve.last_solve_warm and solve._last_solve_delta is not None,
            f"_bf_solver_warm served metric -> {metric} with a device delta "
            f"({len(solve._last_solve_delta)} columns, "
            f"{solve.invalidation_rounds_last} invalidation rounds)",
        )
        area_equals_native("_bf_solver_warm + _delta_extract")
    solve.close()

    # -- ecmp_dag on the grid ---------------------------------------------
    from openr_tpu.ops.spf import ecmp_dag
    from openr_tpu.solver.native_spf import NativeSpfSolver
    from openr_tpu.topology import grid_edges

    graph = compile_edges(grid_edges(sz.grid_side))
    all_rows = np.arange(graph.n_pad, dtype=np.int32)
    dag = timed(
        f"batched_spf all-pairs + ecmp_dag on the {graph.n}-node grid",
        lambda: np.asarray(ecmp_dag(graph, batched_spf(graph, all_rows))),
    )
    native = NativeSpfSolver(graph)
    for src in rng.choice(graph.n, size=8, replace=False):
        _, nh_sets = native.run_with_nexthops(int(src))
        mine = np.nonzero(graph.src[: graph.e] == src)[0]
        for t in range(graph.n):
            got = {int(graph.dst[e]) for e in mine if dag[e, t]}
            if got != (nh_sets[t] if t != src else set()):
                check(False, f"ecmp_dag first hops {src}->{t} == native")
    native.close()
    check(
        dag.shape == (graph.e_pad, graph.n_pad),
        f"ecmp_dag [{dag.shape[0]}, {dag.shape[1]}]: first-hop sets of 8 "
        f"sampled sources == native Dijkstra",
    )

    # -- blocked Floyd–Warshall: cold close + one warm re-close -----------
    from openr_tpu.apsp import ApspState, build_weight_matrix, np_floyd_warshall

    for n, full in ((sz.apsp_n, False), (sz.apsp_ref_n, True)):
        graph = compile_edges(wan_edges(n, degree=4, seed=7))
        apsp = ApspState(max_nodes=n)

        def apsp_ok(what: str) -> None:
            check(
                apsp.backend == "device" and apsp.fallback_closes == 0,
                f"{what} n={n}: closed on the device "
                f"({apsp.close_ms_last:.0f}ms, no numpy fallback)",
            )
            if full:
                ref = np_floyd_warshall(
                    build_weight_matrix(graph), graph.overloaded
                )
                check(
                    np.array_equal(apsp.d, ref),
                    f"{what} n={n}: full matrix == np_floyd_warshall",
                )
            else:
                src = rng.choice(graph.n, size=4, replace=False)
                check(
                    np.array_equal(
                        apsp.d[src][:, : graph.n], native_rows(graph, src)
                    ),
                    f"{what} n={n}: 4 sampled rows == native Dijkstra",
                )

        timed(f"ApspState.ensure cold n={n}", lambda: apsp.ensure(graph))
        apsp_ok("_fw_solver cold close")
        w = graph.w.copy()
        pos = graph.e // 2
        w[pos] = int(w[pos]) % 13 + 1
        graph.w = w
        graph.version += 1
        timed(f"ApspState.ensure warm re-close n={n}", lambda: apsp.ensure(graph))
        check(
            apsp.warm_closes == 1,
            f"_fw_seed_solver + _fw_reclose_solver served the event "
            f"({apsp.reclose_rounds_last} round(s))",
        )
        apsp_ok("warm re-close")
        apsp.close()

    # -- TE: lax.scan / value_and_grad core -------------------------------
    from openr_tpu.ops.graph import compile_graph
    from openr_tpu.te import (
        TeService,
        build_demand_scenarios,
        congested_clos_fixture,
        hard_max_util,
        te_edge_arrays,
    )

    edges, spec = congested_clos_fixture()
    ls = LinkState(AREA)
    for db in build_adj_dbs(edges).values():
        ls.update_adjacency_database(db)
    report = timed(
        "TeService.optimize on congested_clos_fixture",
        lambda: TeService("l0_0", {AREA: ls}).optimize(
            {"demands": spec, "steps": sz.te_steps,
             "scenarios": sz.te_scenarios, "seed": seed}
        ),
    )
    # independent re-score under exact SPF + fractional ECMP (numpy): the
    # initial weights, then the report's proposal replayed onto the edges
    graph = compile_graph(ls)
    src_e, dst_e, w0, up = te_edge_arrays(graph)
    demands, caps, _ = build_demand_scenarios(
        graph, spec, scenarios=sz.te_scenarios, seed=seed
    )

    def worst(w) -> float:
        return max(
            hard_max_util(w, demands[k], caps, src_e, dst_e, up, graph.n)
            for k in range(demands.shape[0])
        )

    w_init = np.rint(w0).astype(np.int64)
    w_best = w_init.copy()
    for change in report["weight_changes"]:
        for link, (fwd, rev) in graph.link_edges.items():
            for pos, node in ((fwd, link.n1), (rev, link.n2)):
                if (
                    node == change["node"]
                    and link.other_node_name(node) == change["neighbor"]
                    and link.iface_from_node(node) == change["iface"]
                ):
                    w_best[pos] = change["metric_after"]
    check(
        report["degraded"] is False and report["backend"] == "primary",
        "TeService.optimize ran on the primary backend, not degraded",
    )
    check(
        abs(worst(w_init) - report["initial_max_util"]) < 1e-4
        and abs(worst(w_best) - report["optimized_max_util"]) < 1e-4
        and report["optimized_max_util"] < report["initial_max_util"],
        f"TE: max util {report['initial_max_util']} -> "
        f"{report['optimized_max_util']} over {demands.shape[0]} scenarios, "
        f"both == the exact-ECMP re-score of the proposed weights",
    )


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--stages", default="A,B,C",
        help="comma-separated subset of A,B,C (default: all)",
    )
    parser.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="toy sizes on whatever JAX finds; NOT a chip run, no verdict",
    )
    args = parser.parse_args(argv)
    stages = {s.strip().upper() for s in args.stages.split(",") if s.strip()}
    if not stages <= {"A", "B", "C"}:
        parser.error(f"unknown stage in {args.stages!r}")

    import jax

    devices = jax.devices()  # raises when the configured backend is dead
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    on_chip = device["platform"] == "tpu"
    if not on_chip and not args.cpu_rehearsal:
        print(
            f"chip_smoke: refusing to run: JAX found {device}, not a TPU "
            f"(--cpu-rehearsal rehearses off the chip, without a verdict)",
            file=sys.stderr,
        )
        return 3
    sz = REHEARSAL if args.cpu_rehearsal else CHIP

    from importlib import metadata

    import jaxlib

    from openr_tpu.utils.compile_cache import (
        ensure_compile_cache,
        persistent_cache_counts,
    )
    from openr_tpu.utils.native_build import build_native

    cache_dir = ensure_compile_cache()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(
        f"chip_smoke: platform={device['platform']} "
        f"device_kind={device['kind']!r} n_devices={device['count']} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu} "
        f"compile_cache={cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        f"entries at start)",
        flush=True,
    )
    if args.cpu_rehearsal:
        print(
            "chip_smoke: CPU REHEARSAL at toy sizes — this is NOT a chip "
            "run; nothing below is a device result",
            flush=True,
        )

    # native/ is rebuilt through make (a no-op when fresh); a missing
    # toolchain fails here instead of quietly serving a Python stand-in
    for lib in ("libopenr_spf.so", "libopenr_kv.so"):
        build_native(lib)
    from openr_tpu.kvstore.native import native_kv_available
    from openr_tpu.solver.native_spf import native_spf_available

    check(
        native_spf_available() and native_kv_available(),
        "native/ built through make: the C++ Dijkstra is the distance "
        "oracle, the C++ engine is the KvStore table",
    )

    summary: dict = {"device": device, "rehearsal": args.cpu_rehearsal}
    if "A" in stages:
        summary["A"] = asyncio.run(
            served_path(
                sz, args.seed, label="A", mesh=None, n_events=32,
                on_chip=on_chip,
            )
        )
        say(
            f"stage A passed: set-up to first programmed route "
            f"{summary['A']['first_route_s']:.1f}s after process start"
        )
    if "B" in stages:
        say("stage B: every other jitted family, once, against its oracle")
        stage_b(sz, args.seed)
        say("stage B passed")
    if "C" in stages:
        if len(devices) >= 4:
            for mesh in ((4, 1), (2, 2)):
                summary[f"C{mesh}"] = asyncio.run(
                    served_path(
                        sz, args.seed, label=f"C{mesh}", mesh=mesh,
                        n_events=8, on_chip=on_chip,
                    )
                )
            say("stage C passed")
        else:
            say(f"stage C: not run ({len(devices)} device)")

    cache = persistent_cache_counts()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(
        f"persistent compile cache {cache_dir}: {cache['hits']} hits, "
        f"{cache['misses']} written, {cache['requests']} requests this "
        f"process; {entries} entries now"
    )
    summary["compile_cache"] = {**cache, "dir": cache_dir, "entries": entries}
    summary["total_s"] = time.perf_counter() - T_START
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "chip_smoke.json"), "w") as fh:
        json.dump(summary, fh, indent=1, sort_keys=True, default=str)
    if args.cpu_rehearsal:
        print(
            json.dumps(
                {"chip_run": False, "rehearsal_passed": True, "device": device}
            ),
            flush=True,
        )
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
