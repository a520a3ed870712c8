"""Incremental SPF benchmark: warm-start vs cold solve on link-flap events.

BASELINE.md config 2 — "10k-node 3-tier Clos/fat-tree, incremental SPF on
single link-flap event" — is the convergence-latency half of the north-star
metric. This bench chains single-link-flap events (a far-pod rsw<->fsw link
going down, then back up, via fresh AdjacencyDatabases) through two
_AreaSolve instances over the same LinkState:

  - warm: the default device-resident path — the previous distance matrix
    warm-starts the fixpoint (increase events run the on-device
    invalidation pass first), so relaxation rounds scale with the event's
    affected radius instead of the graph diameter.
  - cold: warm_start=False — the same fused patch+solve dispatch, but
    re-relaxing from D0 = INF every event (the pre-warm-start behavior).

Reported: warm events/sec, p99 per-event latency, and the mean relaxation
round counts of both paths. The round-count win is asserted, so the bench
doubles as a regression gate even on CPU CI where wall-clock is noisy.

Env: INC_PODS, INC_PLANES, INC_SSW, INC_FSW, INC_RSW, INC_EVENTS;
BENCH_SMOKE=1 selects tiny defaults.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import List, Tuple

import numpy as np

from benchmarks.common import emit, note

from openr_tpu.lsdb import LinkState
from openr_tpu.topology import build_adj_dbs, fabric_edges


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def _set_link_overload(dbs, ls, node: str, other: str, down: bool) -> bool:
    """Publish `node`'s AdjacencyDatabase with the adjacency toward `other`
    marked (un)overloaded — the weight-only link-flap event shape (the link
    stays in the arrays; its weight patches to INF and back)."""
    db = dbs[node]
    db = dataclasses.replace(
        db,
        adjacencies=[
            dataclasses.replace(adj, is_overloaded=down)
            if adj.other_node_name == other
            else adj
            for adj in db.adjacencies
        ],
    )
    dbs[node] = db
    return ls.update_adjacency_database(db).topology_changed


def main(argv=None) -> None:
    from openr_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    pods = _env_int("INC_PODS", 2 if smoke else 170)
    planes = _env_int("INC_PLANES", 2 if smoke else 4)
    ssw = _env_int("INC_SSW", 2 if smoke else 9)
    fsw = _env_int("INC_FSW", 2 if smoke else 8)
    rsw = _env_int("INC_RSW", 4 if smoke else 48)
    events = _env_int("INC_EVENTS", 6 if smoke else 50)
    warmup = 2

    from openr_tpu.solver.tpu import _AreaSolve

    edges = fabric_edges(
        pods, planes=planes, ssw_per_plane=ssw, fsw_per_pod=fsw,
        rsw_per_pod=rsw,
    )
    dbs = build_adj_dbs(edges)
    ls = LinkState("0")
    t0 = time.time()
    ls.bulk_update_adjacency_databases(list(dbs.values()))
    me = "rsw0_0"
    warm = _AreaSolve(ls, me)
    cold = _AreaSolve(ls, me, warm_start=False)
    assert warm.graph.sell is not None, "Clos must qualify for sliced-ELL"
    note(
        f"clos: n={warm.graph.n} e={warm.graph.e} "
        f"(padded {warm.graph.n_pad}/{warm.graph.e_pad}) "
        f"built + first solves in {time.time()-t0:.1f}s; "
        f"cold rounds={cold.rounds_last}"
    )

    # rotate flaps over far-pod rsw uplinks; rsw index starts at 1 so the
    # flapped link is never incident to me even in a single-pod topology
    # (a link at me changes the source batch and legitimately forces a
    # cold solve — not the steady-state event this bench measures)
    flap_pod = pods - 1
    links: List[Tuple[str, str]] = [
        (f"fsw{flap_pod}_{f}", f"rsw{flap_pod}_{r}")
        for f in range(fsw)
        for r in range(1, rsw)
    ]
    assert links, "need rsw_per_pod >= 2"

    warm_lat: List[float] = []
    cold_lat: List[float] = []
    warm_rounds: List[int] = []
    cold_rounds: List[int] = []
    for i in range(warmup + events):
        node, other = links[(i // 2) % len(links)]
        changed = _set_link_overload(dbs, ls, node, other, down=(i % 2 == 0))
        assert changed, (node, other, i)
        t0 = time.perf_counter()
        warm.refresh()  # blocks: rounds sync per event
        t_warm = time.perf_counter() - t0
        t0 = time.perf_counter()
        cold.refresh()
        t_cold = time.perf_counter() - t0
        if i < warmup:
            continue  # jit compile + cache warm
        warm_lat.append(t_warm)
        cold_lat.append(t_cold)
        warm_rounds.append(warm.rounds_last)
        cold_rounds.append(cold.rounds_last)

    assert warm.incremental_solves >= events, (
        warm.incremental_solves,
        warm.full_solves,
    )
    np.testing.assert_array_equal(warm.d, cold.d)  # bit-identical output

    rounds_warm = float(np.mean(warm_rounds))
    rounds_cold = float(np.mean(cold_rounds))
    # the headline claim, hardware-independent: warm-start converges in
    # fewer relaxation rounds than recompute-from-INF on the same events
    assert rounds_warm < rounds_cold, (warm_rounds, cold_rounds)

    mean_warm = float(np.mean(warm_lat))
    mean_cold = float(np.mean(cold_lat))
    p99_ms = float(np.percentile(warm_lat, 99) * 1e3)
    note(
        f"warm: {1.0/mean_warm:,.1f} events/s "
        f"(mean {mean_warm*1e3:.2f}ms, p99 {p99_ms:.2f}ms, "
        f"rounds {rounds_warm:.1f}) | cold: {1.0/mean_cold:,.1f} events/s "
        f"(mean {mean_cold*1e3:.2f}ms, rounds {rounds_cold:.1f})"
    )
    emit(
        {
            "metric": f"clos{warm.graph.n}_incremental_events_per_sec",
            "value": round(1.0 / mean_warm, 1),
            "unit": (
                f"link-flap events/s ({warm.graph.n}-node Clos, "
                "warm-start incremental solve)"
            ),
            "vs_baseline": round(mean_cold / mean_warm, 2),
            "baseline": "cold-solve",
            "p99_ms": round(p99_ms, 3),
            "rounds_warm_mean": round(rounds_warm, 2),
            "rounds_cold_mean": round(rounds_cold, 2),
        }
    )


if __name__ == "__main__":
    main()
