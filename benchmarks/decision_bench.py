"""Decision per-event benchmark: grid + fabric, CPU oracle vs TPU solver.

Port of the reference harness semantics
(openr/decision/tests/DecisionBenchmark.cpp:640-823): build a grid or
3-tier Clos fabric where every node announces one unique prefix, then
measure the steady-state cost of one topology event — a link metric flap
arriving as a fresh AdjacencyDatabase — through the full route-build
pipeline (LinkState ingest -> SPF -> per-prefix ECMP selection -> RouteDb).

The reference measures `adj_receive` and `spf` counters per event on its
CPU SpfSolver; here the same event loop runs twice, once on the CPU oracle
(per-source memoized Dijkstra) and once on the TPU batched solver
(incremental array patch + one batched device solve), and reports both.

Env: DECISION_GRID_SIDES, DECISION_FABRIC_PODS, DECISION_EVENTS,
DECISION_KSP2_SIDES, DECISION_KSP2_PREFIXES.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List

from benchmarks.common import emit, note

from openr_tpu.lsdb import LinkState, PrefixState
from openr_tpu.solver import SpfSolver, TpuSpfSolver
from openr_tpu.topology import build_adj_dbs, fabric_edges, grid_edges
from openr_tpu.types import (
    IpPrefix,
    PrefixDatabase,
    PrefixEntry,
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
)


def _unique_prefix(i: int) -> str:
    return f"10.{(i >> 16) & 255}.{(i >> 8) & 255}.{i & 255}/32"


def _prefix_state(nodes: List[str], cap: int = 0, **entry_kw) -> PrefixState:
    ps = PrefixState()
    use = nodes[:cap] if cap else nodes
    for i, node in enumerate(use):
        ps.update_prefix_database(
            PrefixDatabase(
                node,
                [PrefixEntry(IpPrefix(_unique_prefix(i)), **entry_kw)],
                area="0",
            )
        )
    return ps


def _build_ls(edges) -> LinkState:
    ls = LinkState("0")
    for db in build_adj_dbs(edges).values():
        ls.update_adjacency_database(db)
    return ls


def _flap_event_bench(
    name: str,
    edges,
    me: str,
    flap_edge,
    events: int,
    prefix_cap: int = 0,
    **entry_kw,
) -> None:
    """Measure per-event route rebuild time, CPU vs TPU, on a topology where
    `flap_edge` (a, b) alternates metric 1 <-> 5 each event."""
    a, b, _ = flap_edge
    variants = []
    for metric in (1, 5):
        ev = [
            (x, y, metric if {x, y} == {a, b} else w) for x, y, w in edges
        ]
        variants.append(build_adj_dbs(ev)[a])

    nodes = sorted({n for x, y, _ in edges for n in (x, y)})
    results: Dict[str, float] = {}
    for label, solver_cls in (("cpu", SpfSolver), ("tpu", TpuSpfSolver)):
        ls = _build_ls(edges)
        ps = _prefix_state(nodes, cap=prefix_cap, **entry_kw)
        solver = solver_cls(me)
        db_warm = solver.build_route_db(me, {"0": ls}, ps)  # cold build
        assert db_warm is not None and db_warm.unicast_entries
        # warm one flap cycle (jit compile for both metric variants)
        for v in variants:
            ls.update_adjacency_database(v)
            solver.build_route_db(me, {"0": ls}, ps)
        t0 = time.time()
        for i in range(events):
            ls.update_adjacency_database(variants[i % 2])
            solver.build_route_db(me, {"0": ls}, ps)
        per_event = (time.time() - t0) / events
        results[label] = per_event
        note(f"{name} {label}: {per_event*1e3:.2f} ms/event")

    emit(
        {
            "metric": f"decision_event_ms[{name}]",
            "value": round(results["tpu"] * 1e3, 3),
            "unit": "ms/event (flap -> RouteDb)",
            "vs_baseline": round(results["cpu"] / results["tpu"], 2),
        }
    )


def main(argv: List[str] = ()) -> None:
    from openr_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    grid_sides = [
        int(x)
        for x in os.environ.get("DECISION_GRID_SIDES", "10,32").split(",")
        if x
    ]
    fabric_pods = [
        int(x)
        for x in os.environ.get("DECISION_FABRIC_PODS", "6").split(",")
        if x
    ]
    ksp2_sides = [
        int(x)
        for x in os.environ.get("DECISION_KSP2_SIDES", "8").split(",")
        if x
    ]
    events = int(os.environ.get("DECISION_EVENTS", "10"))
    ksp2_prefixes = int(os.environ.get("DECISION_KSP2_PREFIXES", "16"))

    for side in grid_sides:
        edges = grid_edges(side)
        mid = side // 2
        flap = (f"g{mid}_{mid}", f"g{mid}_{mid+1}", 1)
        _flap_event_bench(
            f"grid{side*side}", edges, "g0_0", flap, events
        )

    for pods in fabric_pods:
        edges = fabric_edges(pods)
        n = len({x for a, b, _ in edges for x in (a, b)})
        flap = ("fsw0_0", "rsw0_0", 1)
        _flap_event_bench(
            f"fabric{n}", edges, "rsw0_0", flap, events
        )

    for side in ksp2_sides:
        edges = grid_edges(side)
        mid = side // 2
        flap = (f"g{mid}_{mid}", f"g{mid}_{mid+1}", 1)
        # KSP2 variant: capped prefix count (each KSP2 prefix costs a
        # penalized re-solve batch + host path trace)
        _flap_event_bench(
            f"grid{side*side}_ksp2",
            edges,
            "g0_0",
            flap,
            events,
            prefix_cap=ksp2_prefixes,
            forwarding_type=PrefixForwardingType.SR_MPLS,
            forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
        )


if __name__ == "__main__":
    main()
