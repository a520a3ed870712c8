"""DeltaPath's route columns: the route build is handed the destinations
in which my own distance row or my first-hop mask moved
(`_AreaSolve._finish_delta`), not every column in which any row of the
solve moved. The routes stay those of a full rebuild, the update that of
the unnarrowed set; the narrowing engages where a neighbour's row moves
alone (a WAN) and removes nothing where every changed column moves my own
row (a grid's row and column 0). Where the old values are not in hand, or
LFA reads the neighbours' rows, every changed column is handed on."""

import collections
import dataclasses
import json
import os
import random

import pytest

from openr_tpu.lsdb import LinkState
from openr_tpu.solver import (
    DeltaRouteBuilder,
    SolverSupervisor,
    SpfSolver,
    SupervisorConfig,
    TpuSpfSolver,
)
from openr_tpu.topology import build_adj_dbs, grid_edges, wan_edges

from test_route_delta import (
    PFXS,
    DeltaHarness,
    _every_node_announces,
    assert_route_db_equal,
    build_ls,
    make_prefix_state,
    set_metric,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROUTE_COLS = "decision.spf.delta_route_columns"
DELTA_COLS = "decision.spf.delta_columns"
LFA_KW = {"compute_lfa_paths": True, "apsp_max_nodes": 4096}


def _answer_every_changed_column(solver, me):
    """Make `solver`'s area solve hand on every changed column, as the
    route build was handed before route columns; returns the solve."""
    solve = solver._solves[("0", me)][1]
    take = solve.take_route_delta
    solve.take_route_delta = lambda every_changed=False: take(True)
    return solve


def _sorted_update(update):
    return (
        sorted(update.unicast_routes_to_update, key=lambda e: e.prefix),
        sorted(update.unicast_routes_to_delete),
        sorted(update.mpls_routes_to_update, key=lambda e: e.label),
        sorted(update.mpls_routes_to_delete),
    )


def _swap_stream(edges, me, links, high, n_events, seed):
    """`n_events` events, each one write that restores the link raised
    before it and raises another of `links` (both directions) by one of
    `high`. After every event the supervised DeltaPath db equals a full
    rebuild (the route-delta audit at every build) and its update equals
    the one a builder handed every changed column programs. Returns the
    supervised solver's counters."""
    dbs = build_adj_dbs(edges)
    ls = LinkState("0")
    for db in dbs.values():
        ls.update_adjacency_database(db)
    als = {"0": ls}
    ps = make_prefix_state(_every_node_announces(edges))

    def set_both(a, b, metric):
        for x, y in ((a, b), (b, a)):
            set_metric(dbs, ls, x, y, metric)

    sup = SolverSupervisor(
        TpuSpfSolver(me), SpfSolver(me), SupervisorConfig(audit_interval=1)
    )
    builder = DeltaRouteBuilder(sup)
    wide = TpuSpfSolver(me)
    wide_builder = DeltaRouteBuilder(wide)
    db, _, used = builder.build(me, als, ps, None, force_full=True)
    wide_db, _, _ = wide_builder.build(me, als, ps, None, force_full=True)
    assert not used and db.mpls_entries
    assert sup.primary.counters[ROUTE_COLS] == 0  # there from the first sync
    wide_solve = _answer_every_changed_column(wide, me)

    rng = random.Random(seed)
    raised = None
    programmed = 0
    for k in range(n_events):
        if raised is not None:
            set_both(*raised)  # back to its metric, in the same write
        a, b, m = links[rng.randrange(len(links))]
        while raised is not None and (a, b) == raised[:2]:
            a, b, m = links[rng.randrange(len(links))]
        raised = (a, b, m)
        set_both(a, b, m + rng.choice(high))
        new_db, update, used = builder.build(me, als, ps, db)
        assert used, k  # off the vantage's links: every event stays warm
        assert sup.verify_route_delta(new_db, me, als, ps) is None, k
        wide_new, wide_update, wide_used = wide_builder.build(
            me, als, ps, wide_db
        )
        assert wide_used, k
        assert _sorted_update(update) == _sorted_update(wide_update), k
        assert_route_db_equal(wide_new, new_db)
        programmed += not update.empty()
        db, wide_db = new_db, wide_new
    # the unnarrowed side stayed the one warm solve it was made from
    assert wide._solves[("0", me)][1] is wide_solve
    assert sup.counters["decision.spf.delta_audit_runs"] == n_events
    assert "decision.spf.delta_audit_mismatches" not in sup.counters
    assert programmed > n_events // 2
    counters = sup.primary.counters
    assert counters[DELTA_COLS] == wide.counters[DELTA_COLS] > 0
    return counters


class TestRouteColumnsDifferential:
    @pytest.mark.parametrize(
        "n, seed", [(512, 4301), (1024, 4302), (2048, 3)]
    )
    def test_wan_raises_and_restores_on_tree_links(self, n, seed):
        edges = wan_edges(n, degree=4, seed=seed)
        degree = collections.Counter(x for a, b, _ in edges for x in (a, b))
        me = min(degree, key=lambda x: (-degree[x], int(x[1:])))
        ls = build_ls(edges)
        dist = {x: r.metric for x, r in ls.get_spf_result(me).items()}
        # links of the vantage's shortest-path tree, none its own
        tree = sorted(
            (a, b, m)
            for a, b, m in edges
            if me not in (a, b) and abs(dist[a] - dist[b]) == m
        )
        counters = _swap_stream(
            edges, me, tree, list(range(2, 18)), 32, seed
        )
        # the neighbours' rows moved where mine did not: those columns
        # reach no route build
        assert 0 < counters[ROUTE_COLS] < counters[DELTA_COLS]

    def test_grid_row_and_column_zero_remove_nothing(self):
        side = 12
        links = [(f"g0_{k}", f"g0_{k + 1}", 1) for k in range(1, side - 1)]
        links += [(f"g{k}_0", f"g{k + 1}_0", 1) for k in range(1, side - 1)]
        counters = _swap_stream(
            grid_edges(side), "g0_0", links, list(range(2, 17)), 32, 4303
        )
        # a raised ray moves my own distance to every column that moved
        assert counters[ROUTE_COLS] == counters[DELTA_COLS] > 0


# a vantage `a` with neighbours b and c: t lies behind b, and behind c
# only by x. Raising x-t moves c's distances to x, y and t and b's to x,
# and neither my own distance nor my first hops toward any of them
SMALL = [
    ("a", "b", 1),
    ("b", "t", 1),
    ("a", "c", 1),
    ("c", "x", 1),
    ("x", "t", 1),
    ("b", "y", 3),
    ("y", "t", 1),
]
SMALL_ADVERTS = {"t": [PFXS[0]], "x": [PFXS[1]], "y": [PFXS[2]]}


def _raise_x_t(h, metric=5):
    set_metric(h.dbs, h.ls, "x", "t", metric)
    set_metric(h.dbs, h.ls, "t", "x", metric)


def _recorded_polls(solver):
    """The answers of `solver.poll_device_delta`, as a list."""
    seen = []
    poll = solver.poll_device_delta

    def recorded(als):
        seen.append(poll(als))
        return seen[-1]

    solver.poll_device_delta = recorded
    return seen


def _solve(h):
    return h.solver._solves[("0", h.me)][1]


class TestConservativeCases:
    def test_a_neighbours_row_alone_reaches_no_route_build(self):
        h = DeltaHarness(SMALL, "a", SMALL_ADVERTS)
        seen = _recorded_polls(h.solver)
        _raise_x_t(h)
        assert h.step() is True  # the routes are a full rebuild's
        assert seen == [set()]
        solve = _solve(h)
        changed = {solve.graph.names[c] for c in solve._last_solve_delta}
        assert changed == {"x", "y", "t"}
        assert h.solver.counters[DELTA_COLS] == 3
        assert h.solver.counters[ROUTE_COLS] == 0

    def test_lfa_is_handed_every_changed_column(self):
        h = DeltaHarness(SMALL, "a", SMALL_ADVERTS, solver_kwargs=LFA_KW)
        t = h.db.unicast_entries[next(iter(h.ps.prefixes_for_nodes({"t"})))]
        assert {nh.neighbor_node for nh in t.nexthops} == {"b", "c"}
        seen = _recorded_polls(h.solver)
        _raise_x_t(h)
        # c's distance to t moved alone, and c stops being loop-free
        # toward t: an alternate that reads c's row must be rebuilt
        assert h.step() is True
        assert seen == [{"x", "y", "t"}]
        t = h.db.unicast_entries[t.prefix]
        assert {nh.neighbor_node for nh in t.nexthops} == {"b"}
        # the counter says what a build without LFA would have been handed
        assert h.solver.counters[ROUTE_COLS] == 0
        # an event that moves the me column still answers None
        set_metric(h.dbs, h.ls, "b", "a", 9)
        assert h.step() is False
        assert seen[-1] is None

    def test_absent_mirror_hands_every_changed_column(self):
        # a cold solve that nobody read: no old row to compare with
        ls = build_ls(SMALL)
        tpu = TpuSpfSolver("a")
        solve = tpu._area_solve(ls, "a")
        assert solve.take_route_delta() is None  # cold solve poisons
        assert solve._d_host is None and solve._nh_mask is None
        dbs = build_adj_dbs(SMALL)
        set_metric(dbs, ls, "x", "t", 5)
        set_metric(dbs, ls, "t", "x", 5)
        solve = tpu._area_solve(ls, "a")
        cols = solve.take_route_delta()
        assert {solve.graph.names[c] for c in cols} == {"x", "y", "t"}
        assert solve.delta_route_columns == solve.delta_columns == 3

    def test_dropped_mask_hands_every_changed_column(self):
        h = DeltaHarness(SMALL, "a", SMALL_ADVERTS)
        solve = _solve(h)
        assert solve._nh_links == ["b", "c"]
        # a mask built for another up-link set is dropped, not compared
        solve._nh_links = ["c", "b"]
        seen = _recorded_polls(h.solver)
        _raise_x_t(h)
        assert h.step() is True
        assert seen == [{"x", "y", "t"}]
        assert h.solver.counters[ROUTE_COLS] == h.solver.counters[DELTA_COLS]
        assert solve._nh_links == ["b", "c"]  # made again by the build

    def test_overloaded_neighbour_is_compared_after_the_overload_rule(self):
        # c is drained: with c-t at 1 the triangle holds over c toward t,
        # and the rule takes c out again, so my first hops do not move
        edges = [("a", "b", 1), ("b", "t", 1), ("a", "c", 1), ("c", "t", 5)]
        h = DeltaHarness(edges, "a", {"t": [PFXS[0]]})
        h.dbs["c"] = dataclasses.replace(h.dbs["c"], is_overloaded=True)
        h.ls.update_adjacency_database(h.dbs["c"])
        assert h.step() is False  # an overload change takes the full path
        seen = _recorded_polls(h.solver)
        set_metric(h.dbs, h.ls, "c", "t", 1)
        set_metric(h.dbs, h.ls, "t", "c", 1)
        assert h.step() is True
        solve = _solve(h)
        assert "t" in {solve.graph.names[c] for c in solve._last_solve_delta}
        assert seen == [set()]
        t = h.db.unicast_entries[next(iter(h.ps.prefixes_for_nodes({"t"})))]
        assert {nh.neighbor_node for nh in t.nexthops} == {"b"}


def test_metric_entry_and_file_read_the_programs_counter_per_event():
    from chipbench import layer_metrics
    from chipbench import run as bench_run

    name = "delta_route_columns_per_event"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entries = {m["name"]: m for m in bench["per_layer"]}
    entry = dict(entries[name])
    # in the cells that read the columns it is a part of
    assert entry.pop("workloads") == entries["delta_columns_per_event"][
        "workloads"
    ]
    assert entry == {
        "name": name, "unit": "columns", "better": "lower",
        "source": "program_counter", "layer": "route build",
        "moves": "event_to_fib_ms.p50",
    }
    spec = bench_run.load_json("metrics", name + ".json")
    assert spec == {
        "name": name, "layer": "route build", "unit": "columns",
        "moves": "event_to_fib_ms.p50",
        "source": {"counter_delta": ROUTE_COLS, "per": "event"},
    }

    def ctx(before, after):
        return layer_metrics.Context(
            hists={}, counters0=before, counters1=after, n_events=8,
            gauges={}, trace=None, config={}, device_kind="cpu",
        )

    assert layer_metrics.read(spec, ctx({ROUTE_COLS: 8}, {ROUTE_COLS: 408}))[0] == 50
    # a program without the counter leaves the metric out
    assert layer_metrics.read(spec, ctx({}, {})) == (
        None, f"counter {ROUTE_COLS} does not exist"
    )
