"""TPU batched solver: op-level tests + full route-db parity vs the CPU oracle.

The parity tests are the contract from SURVEY.md §7 phase 3: identical
DecisionRouteDb output (routes, nexthops, labels) on every topology, verified
on random graphs and the fixture topologies.
"""

import random

import numpy as np
import pytest

from openr_tpu.lsdb import LinkState, PrefixState
from openr_tpu.ops import INF, batched_spf, compile_graph, ecmp_dag
from openr_tpu.solver import SpfSolver, TpuSpfSolver
from openr_tpu.topology import (
    build_adj_dbs,
    fabric_edges,
    grid_edges,
    ring_edges,
    wan_edges,
)
from openr_tpu.types import (
    IpPrefix,
    PrefixDatabase,
    PrefixEntry,
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
)


def build_ls(edges, area="0", **kwargs):
    ls = LinkState(area)
    for db in build_adj_dbs(edges, area=area, **kwargs).values():
        ls.update_adjacency_database(db)
    return ls


def all_pairs_distance_check(ls):
    """Compare batched BF distances against the Dijkstra oracle for all pairs."""
    graph = compile_graph(ls)
    d = np.asarray(batched_spf(graph, np.arange(graph.n_pad, dtype=np.int32)))
    for src in graph.names:
        oracle = ls.get_spf_result(src)
        row = graph.node_index[src]
        for dst in graph.names:
            col = graph.node_index[dst]
            got = int(d[row, col])
            if dst in oracle:
                assert got == oracle[dst].metric, (src, dst)
            else:
                assert got >= INF, (src, dst)


class TestBatchedSpf:
    def test_line(self):
        ls = build_ls([("a", "b", 1), ("b", "c", 2), ("c", "d", 3)])
        all_pairs_distance_check(ls)

    def test_grid(self):
        all_pairs_distance_check(build_ls(grid_edges(4)))

    def test_weighted_ring(self):
        edges = [(f"r{i}", f"r{(i+1)%8}", (i % 3) + 1) for i in range(8)]
        all_pairs_distance_check(build_ls(edges))

    def test_disconnected(self):
        all_pairs_distance_check(build_ls([("a", "b", 1), ("x", "y", 2)]))

    def test_overloaded_transit(self):
        ls = build_ls(
            [("a", "b", 1), ("b", "c", 1), ("a", "c", 10)],
            overloaded_nodes={"b"},
        )
        all_pairs_distance_check(ls)

    def test_overloaded_cut_vertex(self):
        # b overloaded and the only path a-c: c unreachable from a
        ls = build_ls(
            [("a", "b", 1), ("b", "c", 1)], overloaded_nodes={"b"}
        )
        graph = compile_graph(ls)
        d = np.asarray(
            batched_spf(graph, np.arange(graph.n_pad, dtype=np.int32))
        )
        ia, ib, ic = (graph.node_index[x] for x in "abc")
        assert d[ia, ib] == 1  # reachable
        assert d[ia, ic] >= INF  # no transit through b
        assert d[ib, ic] == 1  # b's own routes unaffected
        all_pairs_distance_check(ls)

    def test_random_graphs(self):
        rng = random.Random(42)
        for trial in range(10):
            n = rng.randint(4, 16)
            nodes = [f"n{i}" for i in range(n)]
            edges = []
            # random spanning tree + chords, random metrics
            for i in range(1, n):
                edges.append(
                    (nodes[rng.randrange(i)], nodes[i], rng.randint(1, 20))
                )
            for _ in range(rng.randint(0, n)):
                a, b = rng.sample(nodes, 2)
                if not any(
                    (x == a and y == b) or (x == b and y == a)
                    for x, y, _ in edges
                ):
                    edges.append((a, b, rng.randint(1, 20)))
            overloaded = {
                nodes[i] for i in range(n) if rng.random() < 0.2
            }
            ls = build_ls(edges, overloaded_nodes=overloaded)
            all_pairs_distance_check(ls)

    def test_ecmp_dag_matches_oracle_nexthops(self):
        ls = build_ls(grid_edges(4))
        graph = compile_graph(ls)
        d = np.asarray(
            batched_spf(graph, np.arange(graph.n_pad, dtype=np.int32))
        )
        dag = np.asarray(ecmp_dag(graph, d))
        # oracle nexthop sets from each source = union over first-hop edges
        for src in graph.names:
            oracle = ls.get_spf_result(src)
            row = graph.node_index[src]
            for dst in graph.names:
                if dst == src:
                    continue
                col = graph.node_index[dst]
                got = {
                    graph.names[graph.dst[e]]
                    for e in range(graph.e)
                    if graph.src[e] == row and dag[e, col]
                }
                want = oracle[dst].next_hops if dst in oracle else set()
                assert got == want, (src, dst)

    def test_bucket_padding_reuse(self):
        # graphs in the same bucket share jit executables (no recompile):
        # just exercise two different sizes in one bucket
        for n in (5, 7):
            all_pairs_distance_check(build_ls(ring_edges(n)))

    def test_sliced_and_edge_list_kernels_agree(self):
        from openr_tpu.ops.spf import _bf_fixpoint, sell_fixpoint

        rng = random.Random(5)
        for trial in range(5):
            n = rng.randint(4, 12)
            nodes = [f"n{i}" for i in range(n)]
            edges = [
                (nodes[rng.randrange(i)], nodes[i], rng.randint(1, 9))
                for i in range(1, n)
            ]
            overloaded = {nodes[i] for i in range(1, n) if rng.random() < 0.2}
            ls = build_ls(edges, overloaded_nodes=overloaded)
            g = compile_graph(ls)
            assert g.sell is not None  # small bounded-degree: sliced layout
            rows = np.arange(g.n_pad, dtype=np.int32)
            d_sell = np.asarray(
                sell_fixpoint(g.sell, rows, g.sell.wg, g.overloaded)
            )
            d_edge = np.asarray(
                _bf_fixpoint(rows, g.src, g.dst, g.w, g.overloaded)
            )
            np.testing.assert_array_equal(d_sell, d_edge)

    def test_star_hub_uses_fori_bucket(self):
        # hub in-degree beyond the unroll threshold exercises the
        # fori_loop bucket path; distances must still match the oracle
        edges = [("hub", f"leaf{i:03d}", 1 + i % 5) for i in range(40)]
        ls = build_ls(edges)
        g = compile_graph(ls)
        assert g.sell is not None
        assert any(a.shape[1] > 32 for a in g.sell.nbr)  # fat bucket
        all_pairs_distance_check(ls)

    def test_masked_solver_matches_link_ignore_spf(self):
        # per-row INF masks == the oracle's links_to_ignore re-solve
        from openr_tpu.ops.spf import sell_fixpoint_masked

        rng = random.Random(9)
        ls = build_ls(grid_edges(4))
        g = compile_graph(ls)
        links = sorted(g.link_edges)
        ignore_sets = [
            set(),
            {links[0]},
            {links[1], links[5]},
            set(rng.sample(links, 4)),
        ]
        me = "g0_0"
        row = g.node_index[me]
        mask_positions = [
            [p for link in ig for p in g.link_edges[link]]
            for ig in ignore_sets
        ]
        d = np.asarray(
            sell_fixpoint_masked(
                g.sell,
                np.full(len(ignore_sets), row, dtype=np.int32),
                g.overloaded,
                mask_positions,
            )
        )
        for i, ig in enumerate(ignore_sets):
            res = ls.run_spf(me, True, ig)
            for node in g.names:
                col = g.node_index[node]
                want = res[node].metric if node in res else INF
                assert d[i, col] == want, (i, node)

    def test_extreme_degree_falls_back_to_edge_list(self):
        # unroll cap exceeded (hub in-degree > _SELL_UNROLL_CAP):
        # edge-list segment-min path takes over
        edges = [("hub", f"leaf{i:04d}", 1) for i in range(1100)]
        ls = build_ls(edges)
        g = compile_graph(ls)
        assert g.sell is None
        d = np.asarray(batched_spf(graph=g, source_rows=np.arange(g.n_pad)))
        hub = g.node_index["hub"]
        leaf = g.node_index["leaf0000"]
        assert d[hub, leaf] == 1 and d[leaf, hub] == 1
        other = g.node_index["leaf0001"]
        assert d[leaf, other] == 2  # via hub


class TestIncrementalRefresh:
    """refresh_graph must patch weight/overload arrays in place for
    non-structural events (metric change, drain) — same shapes, shared
    src/dst identity — and fall back to a rebuild for structural ones
    (a link that leaves or returns to slots it has is not one:
    tests/test_graph_link_patches.py)."""

    def test_metric_change_patches_in_place(self):
        from openr_tpu.ops.graph import refresh_graph

        edges = [("a", "b", 1), ("b", "c", 1), ("a", "c", 5)]
        ls = build_ls(edges)
        g1 = compile_graph(ls)
        # bump a-c metric: weight-only change
        ls.update_adjacency_database(build_adj_dbs(
            [("a", "b", 1), ("a", "c", 9)])["a"])
        g2 = refresh_graph(g1, ls)
        assert g2.src is g1.src and g2.dst is g1.dst  # no rebuild
        assert g2.version == ls.version
        # sliced-layout weights patched consistently with the edge weights
        sell = g2.sell
        assert sell is not None
        for p in range(g2.e):
            assert (
                sell.wg[sell.edge_bucket[p]][
                    sell.edge_row[p], sell.edge_slot[p]
                ]
                == g2.w[p]
            )
        all_pairs_distance_check_graph(ls, g2)

    def test_node_overload_patches_in_place(self):
        from openr_tpu.ops.graph import refresh_graph

        edges = [("a", "b", 1), ("b", "c", 1), ("a", "c", 5)]
        ls = build_ls(edges)
        g1 = compile_graph(ls)
        db_b = build_adj_dbs(edges)["b"]
        db_b.is_overloaded = True
        ls.update_adjacency_database(db_b)
        g2 = refresh_graph(g1, ls)
        assert g2.src is g1.src
        assert g2.overloaded[g2.node_index["b"]]
        all_pairs_distance_check_graph(ls, g2)

    def test_structural_change_rebuilds(self):
        # a link the snapshot never held (both ends announce it only now)
        # has no slots to patch: a full rebuild, with slots for it
        from openr_tpu.ops.graph import refresh_graph

        edges = [("a", "b", 1), ("b", "c", 1)]
        ls = build_ls(edges)
        g1 = compile_graph(ls)
        dbs = build_adj_dbs(edges + [("a", "c", 5)])
        ls.update_adjacency_database(dbs["a"])
        ls.update_adjacency_database(dbs["c"])
        g2 = refresh_graph(g1, ls)
        assert g2.src is not g1.src  # full rebuild
        assert g2.e == g1.e + 2 and g2.links_patched == 0
        all_pairs_distance_check_graph(ls, g2)

    @pytest.mark.parametrize(
        "reason", ["log dropped", "structure", "unknown edge", "unknown node"]
    )
    def test_every_fallback_recompiles_and_says_why(self, reason, caplog):
        from openr_tpu.ops.graph import refresh_graph

        edges = [("a", "b", 1), ("b", "c", 1), ("a", "c", 5)]
        ls = build_ls(edges)
        g1 = compile_graph(ls)
        if reason == "structure":  # a node's first database
            dbs = build_adj_dbs(edges + [("c", "d", 1)])
            ls.update_adjacency_database(dbs["d"])
            ls.update_adjacency_database(dbs["c"])
        else:  # a weight change, which alone would be patched in place
            ls.update_adjacency_database(
                build_adj_dbs([("a", "b", 1), ("a", "c", 9)])["a"]
            )
        if reason == "log dropped":  # the reader fell a cap behind
            for _ in range(ls._GRAPH_LOG_CAP):
                ls._log_graph("node", "b")
        elif reason == "unknown edge":
            g1.link_edges = {}
        elif reason == "unknown node":
            ls._log_graph("node", "z")
        with caplog.at_level("INFO", logger="openr_tpu.ops.graph"):
            g2 = refresh_graph(g1, ls)
        # the graph's own: a collection inside the block may log a task that
        # an earlier test of this worker left pending (asyncio's logger)
        assert [
            r.getMessage()
            for r in caplog.records
            if r.name == "openr_tpu.ops.graph"
        ] == [f"area 0: graph refresh falls back to a full compile ({reason})"]
        assert g2.src is not g1.src and g2.link_edges is not g1.link_edges
        assert g2.version == ls.version and g2.log_pos == ls.graph_log_pos
        all_pairs_distance_check_graph(ls, g2)

    def test_refresh_noop_when_version_unchanged(self):
        from openr_tpu.ops.graph import refresh_graph

        ls = build_ls([("a", "b", 1)])
        g1 = compile_graph(ls)
        assert refresh_graph(g1, ls) is g1

    def test_solver_incremental_weight_event(self):
        # a metric change must produce correct routes through the patched
        # arrays with exactly one extra device call
        edges = [("a", "b", 1), ("b", "c", 1), ("a", "c", 5)]
        ls = build_ls(edges)
        ps = make_prefix_state({"c": [PFXS[0]]})
        tpu = TpuSpfSolver("a")
        db1 = tpu.build_route_db("a", {"0": ls}, ps)
        nh1 = {
            nh.neighbor_node
            for nh in db1.unicast_entries[IpPrefix(PFXS[0])].nexthops
        }
        assert nh1 == {"b"}
        before = tpu.device_solves
        # drop a-c to metric 1: both b and c become ECMP... no — a->b->c = 2,
        # a->c = 1, so c wins outright
        ls.update_adjacency_database(build_adj_dbs(
            [("a", "b", 1), ("a", "c", 1)])["a"])
        db2 = tpu.build_route_db("a", {"0": ls}, ps)
        nh2 = {
            nh.neighbor_node
            for nh in db2.unicast_entries[IpPrefix(PFXS[0])].nexthops
        }
        assert nh2 == {"c"}
        assert tpu.device_solves == before + 1
        # arrays were patched, not rebuilt
        solve = tpu._solves[("0", "a")][1]
        assert solve.graph.version == ls.version


def apply_random_event(rng, dbs, ls, links):
    """One randomized weight-only event: link flap (down/up via adjacency
    overload), metric change, or node-overload toggle. Mutates dbs and ls;
    returns the event kind."""
    import dataclasses

    kind = rng.choice(("flap", "metric", "node_overload"))
    if kind in ("flap", "metric"):
        a, b, _ = links[rng.randrange(len(links))]
        db = dbs[a]
        new_adjs = []
        for adj in db.adjacencies:
            if adj.other_node_name == b:
                if kind == "flap":
                    adj = dataclasses.replace(
                        adj, is_overloaded=not adj.is_overloaded
                    )
                else:
                    adj = dataclasses.replace(adj, metric=rng.randint(1, 9))
            new_adjs.append(adj)
        db = dataclasses.replace(db, adjacencies=new_adjs)
        dbs[a] = db
        ls.update_adjacency_database(db)
    else:
        import dataclasses as dc

        node = sorted(dbs)[rng.randrange(len(dbs))]
        db = dc.replace(dbs[node], is_overloaded=not dbs[node].is_overloaded)
        dbs[node] = db
        ls.update_adjacency_database(db)
    return kind


def assert_solve_matches_oracle(ls, solve):
    """Every solved source row must equal the CPU Dijkstra oracle."""
    d = solve.d
    graph = solve.graph
    for name, row in solve.row_map.items():
        oracle = ls.get_spf_result(name)
        for dst in graph.names:
            col = graph.node_index[dst]
            got = int(d[row, col])
            if dst in oracle:
                assert got == oracle[dst].metric, (name, dst)
            else:
                assert got >= INF, (name, dst)


def run_warm_differential(edges, me, seed, n_events, mesh=None):
    """Randomized event sequence: after every event the warm-started
    incremental solve must be bit-identical to a from-scratch cold solve
    AND to the CPU oracle. Returns the warm _AreaSolve for counter
    assertions."""
    from openr_tpu.solver.tpu import _AreaSolve

    rng = random.Random(seed)
    dbs = build_adj_dbs(edges)
    ls = LinkState("0")
    for db in dbs.values():
        ls.update_adjacency_database(db)
    warm = _AreaSolve(ls, me, mesh=mesh)
    links = list(edges)
    applied = 0
    for _ in range(n_events):
        before = ls.version
        apply_random_event(rng, dbs, ls, links)
        if ls.version == before:
            continue  # event was a topology no-op
        warm.refresh()
        cold = _AreaSolve(ls, me, mesh=mesh)  # cold solve of the same state
        np.testing.assert_array_equal(warm.d, cold.d)
        assert_solve_matches_oracle(ls, warm)
        applied += 1
    assert applied > 0
    return warm


class TestWarmStartDifferential:
    """The warm-start incremental event path (device-resident previous
    distances + on-device invalidation of increased entries) must be
    bit-identical to recompute-from-INF on arbitrary event sequences."""

    def test_grid_random_sequences(self):
        for seed in (3, 11):
            warm = run_warm_differential(grid_edges(4), "g0_0", seed, 14)
            assert warm.incremental_solves > 0

    def test_clos_random_sequence(self):
        edges = fabric_edges(
            pods=2, planes=2, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=3
        )
        warm = run_warm_differential(edges, "rsw0_0", 7, 12)
        assert warm.incremental_solves > 0

    def test_increase_then_decrease_same_link(self):
        import dataclasses

        from openr_tpu.solver.tpu import _AreaSolve

        edges = [("a", "b", 1), ("b", "c", 1), ("c", "d", 1), ("a", "d", 9)]
        dbs = build_adj_dbs(edges)
        ls = build_ls(edges)
        warm = _AreaSolve(ls, "a")
        cold_rounds = warm.rounds_last
        for metric in (8, 1):  # increase (invalidation pass), then decrease
            db = dbs["b"]
            db = dataclasses.replace(
                db,
                adjacencies=[
                    dataclasses.replace(adj, metric=metric)
                    if adj.other_node_name == "c"
                    else adj
                    for adj in db.adjacencies
                ],
            )
            dbs["b"] = db
            ls.update_adjacency_database(db)
            warm.refresh()
            cold = _AreaSolve(ls, "a")
            np.testing.assert_array_equal(warm.d, cold.d)
            assert_solve_matches_oracle(ls, warm)
            assert warm.rounds_last <= cold.rounds_last
        assert warm.incremental_solves == 2
        assert warm.rounds_last < cold_rounds  # warm win visible in counter

    def test_partition_flap_and_heal(self):
        import dataclasses

        from openr_tpu.solver.tpu import _AreaSolve

        # two triangles joined by one bridge: flapping it partitions
        edges = [
            ("a", "b", 1), ("b", "c", 1), ("c", "a", 1),
            ("c", "x", 2),  # bridge
            ("x", "y", 1), ("y", "z", 1), ("z", "x", 1),
        ]
        dbs = build_adj_dbs(edges)
        ls = build_ls(edges)
        warm = _AreaSolve(ls, "a")
        for down in (True, False):
            db = dbs["c"]
            db = dataclasses.replace(
                db,
                adjacencies=[
                    dataclasses.replace(adj, is_overloaded=down)
                    if adj.other_node_name == "x"
                    else adj
                    for adj in db.adjacencies
                ],
            )
            dbs["c"] = db
            ls.update_adjacency_database(db)
            warm.refresh()
            cold = _AreaSolve(ls, "a")
            np.testing.assert_array_equal(warm.d, cold.d)
            assert_solve_matches_oracle(ls, warm)
            far = int(warm.d[0, warm.graph.node_index["z"]])
            assert (far >= INF) == down
        assert warm.incremental_solves == 2

    def test_node_overload_toggle_rides_warm_path(self):
        # ROADMAP item closed: an overload toggle is expressed as weight
        # increases on the node's out-edges and rides the existing warm
        # invalidation path — differential against cold AND the CPU oracle
        import dataclasses

        from openr_tpu.solver.tpu import _AreaSolve

        edges = [("a", "b", 1), ("b", "c", 1), ("a", "c", 5)]
        dbs = build_adj_dbs(edges)
        ls = build_ls(edges)
        warm = _AreaSolve(ls, "a")
        full_before = warm.full_solves
        for overloaded in (True, False):
            db = dataclasses.replace(dbs["b"], is_overloaded=overloaded)
            dbs["b"] = db
            ls.update_adjacency_database(db)
            warm.refresh()
            cold = _AreaSolve(ls, "a")
            np.testing.assert_array_equal(warm.d, cold.d)
            assert_solve_matches_oracle(ls, warm)
        # overload ON invalidates via out-edge seeds (inv rounds ran);
        # overload OFF is decrease-only and warm-starts directly
        assert warm.incremental_solves == 2
        assert warm.full_solves == full_before

    def test_node_overload_toggle_grid_differential(self):
        # the same toggle on a larger graph with ECMP structure: every
        # event sequence must stay bit-identical to cold + oracle
        import dataclasses

        from openr_tpu.solver.tpu import _AreaSolve

        edges = grid_edges(4)
        dbs = build_adj_dbs(edges)
        ls = build_ls(edges)
        warm = _AreaSolve(ls, "g0_0")
        # overload a transit node on the diagonal, then a corner, then heal
        for node, overloaded in (
            ("g1_1", True),
            ("g2_2", True),
            ("g1_1", False),
            ("g2_2", False),
        ):
            db = dataclasses.replace(dbs[node], is_overloaded=overloaded)
            dbs[node] = db
            ls.update_adjacency_database(db)
            warm.refresh()
            cold = _AreaSolve(ls, "g0_0")
            np.testing.assert_array_equal(warm.d, cold.d)
            assert_solve_matches_oracle(ls, warm)
        assert warm.incremental_solves == 4

    def test_oversized_event_falls_back_to_cold(self, monkeypatch):
        import dataclasses

        import openr_tpu.solver.tpu as tpu_mod

        # any non-empty patch overflows a zero-slot budget
        monkeypatch.setattr(tpu_mod, "_PATCH_SLOTS", 0)
        edges = [("a", "b", 1), ("b", "c", 1), ("c", "d", 1), ("a", "d", 9)]
        dbs = build_adj_dbs(edges)
        ls = build_ls(edges)
        warm = tpu_mod._AreaSolve(ls, "a")
        full_before = warm.full_solves
        db = dbs["b"]
        db = dataclasses.replace(
            db,
            adjacencies=[
                dataclasses.replace(adj, metric=4) for adj in db.adjacencies
            ],
        )
        dbs["b"] = db
        ls.update_adjacency_database(db)
        warm.refresh()
        assert warm.incremental_solves == 0
        assert warm.full_solves == full_before + 1
        cold = tpu_mod._AreaSolve(ls, "a")
        np.testing.assert_array_equal(warm.d, cold.d)
        assert_solve_matches_oracle(ls, warm)

    def test_solver_exposes_spf_counters(self):
        import dataclasses

        edges = [("a", "b", 1), ("b", "c", 1), ("a", "c", 5)]
        dbs = build_adj_dbs(edges)
        ls = build_ls(edges)
        ps = make_prefix_state({"c": [PFXS[0]]})
        tpu = TpuSpfSolver("a")
        tpu.build_route_db("a", {"0": ls}, ps)
        assert tpu.counters["decision.spf.full_solves"] == 1
        assert tpu.counters["decision.spf.rounds_last"] >= 1
        cold_rounds = tpu.counters["decision.spf.rounds_last"]
        # weight-only event rides the warm path and the counters show it
        db = dbs["b"]
        db = dataclasses.replace(
            db,
            adjacencies=[
                dataclasses.replace(adj, metric=3)
                if adj.other_node_name == "c"
                else adj
                for adj in db.adjacencies
            ],
        )
        dbs["b"] = db
        ls.update_adjacency_database(db)
        db2 = tpu.build_route_db("a", {"0": ls}, ps)
        assert db2 is not None
        assert tpu.counters["decision.spf.incremental_solves"] == 1
        assert tpu.counters["decision.spf.full_solves"] == 1
        assert tpu.counters["decision.spf.rounds_last"] <= cold_rounds


class TestDistanceReadsOutsideABuild:
    """ISSUE 30: `_spf` / `_dist` fold the solve's statistics into the
    counters only where a solve ran; a read on a current solve folds
    nothing, and a LinkState that moved between two reads is still
    re-solved before the second one answers."""

    EDGES = [("a", "b", 1), ("b", "c", 1), ("a", "c", 5)]

    def _set_bc(self, ls, dbs, metric):
        import dataclasses

        dbs["b"] = dataclasses.replace(
            dbs["b"],
            adjacencies=[
                dataclasses.replace(adj, metric=metric)
                if adj.other_node_name == "c"
                else adj
                for adj in dbs["b"].adjacencies
            ],
        )
        ls.update_adjacency_database(dbs["b"])

    @pytest.mark.parametrize("read", ["dist", "spf"])
    def test_a_moved_link_state_is_re_solved_before_the_next_read(self, read):
        dbs = build_adj_dbs(self.EDGES)
        ls = build_ls(self.EDGES)
        tpu = TpuSpfSolver("a")

        def a_to_c():
            if read == "dist":
                return tpu._dist(ls, "a", "c")
            return tpu._spf(ls, "a")["c"].metric

        def syncs():
            return tpu.counters["decision.spf.counter_syncs"]

        assert a_to_c() == 2  # through b
        assert (tpu.device_solves, syncs()) == (1, 1)
        for _ in range(50):
            assert a_to_c() == 2
            assert tpu._dist(ls, "b", "c") == 1  # a neighbour's row
        assert (tpu.device_solves, syncs()) == (1, 1)
        self._set_bc(ls, dbs, 3)
        assert a_to_c() == 4
        assert (tpu.device_solves, syncs()) == (2, 2)
        assert tpu.counters["decision.spf.incremental_solves"] == 1
        self._set_bc(ls, dbs, 9)
        assert a_to_c() == 5  # the direct link
        assert tpu._spf(ls, "a")["c"].next_hops == {"c"}
        assert (tpu.device_solves, syncs()) == (3, 3)

    def test_what_a_read_leaves_lands_with_the_next_sync(self):
        ls = build_ls(self.EDGES)
        ps = make_prefix_state({"c": [PFXS[0]]})
        tpu = TpuSpfSolver("a")
        assert tpu._dist(ls, "a", "c") == 2  # solves, syncs, then fetches
        solve = tpu._solves[("0", "a")][1]
        assert solve.device_syncs == 2  # the round count, the mirror
        assert tpu.counters["decision.spf.device_syncs"] == 1
        assert "decision.spf.device_to_host_bytes" not in tpu.counters
        # a route build ends in one sync, whatever it read
        tpu.build_route_db("a", {"0": ls}, ps)
        assert tpu.counters["decision.spf.device_syncs"] == 2
        assert (
            tpu.counters["decision.spf.device_to_host_bytes"]
            == solve.d.nbytes
        )
        assert tpu.counters["decision.spf.counter_syncs"] == 2


def all_pairs_distance_check_graph(ls, graph):
    """all_pairs_distance_check against a pre-built CompiledGraph."""
    d = np.asarray(batched_spf(graph, np.arange(graph.n_pad, dtype=np.int32)))
    for src in graph.names:
        oracle = ls.get_spf_result(src)
        row = graph.node_index[src]
        for dst in graph.names:
            col = graph.node_index[dst]
            got = int(d[row, col])
            if dst in oracle:
                assert got == oracle[dst].metric, (src, dst)
            else:
                assert got >= INF, (src, dst)


class TestDeviceKsp:
    """Device-batched k-edge-disjoint shortest paths must reproduce the
    oracle's getKthPaths exactly (same paths, same order)."""

    def check_all_pairs_ksp(self, edges, me, overloaded=None, ks=(1, 2, 3)):
        ls_oracle = build_ls(edges, overloaded_nodes=overloaded)
        ls_dev = build_ls(edges, overloaded_nodes=overloaded)
        solver = TpuSpfSolver(me)
        solve = solver._area_solve(ls_dev, me)
        assert solve is not None
        dests = sorted(set(ls_oracle.node_names()) - {me})
        for k in ks:
            # prefetch path: one device batch for all dests at this k
            solver._prefetch_kth_paths(ls_dev, me, dests, k)
            for dest in dests:
                got = solver._kth_paths(ls_dev, me, dest, k)
                want = ls_oracle.get_kth_paths(me, dest, k)
                assert got == want, (me, dest, k, got, want)
        return solve

    def test_square_ring(self):
        solve = self.check_all_pairs_ksp(
            [("a", "b", 1), ("b", "c", 1), ("c", "d", 1), ("d", "a", 1)], "a"
        )
        assert solve.ksp_device_batches >= 1

    def test_diamond_unequal(self):
        self.check_all_pairs_ksp(
            [("a", "b", 1), ("a", "c", 2), ("b", "d", 1), ("c", "d", 1)], "a"
        )

    def test_grid(self):
        self.check_all_pairs_ksp(grid_edges(4), "g0_0", ks=(1, 2))

    def test_overloaded_transit_node(self):
        self.check_all_pairs_ksp(
            [("a", "b", 1), ("b", "c", 1), ("a", "d", 1), ("d", "c", 1)],
            "a",
            overloaded={"b"},
        )

    def test_random_graphs(self):
        rng = random.Random(99)
        for trial in range(8):
            n = rng.randint(4, 12)
            nodes = [f"n{i}" for i in range(n)]
            edges = []
            for i in range(1, n):
                edges.append(
                    (nodes[rng.randrange(i)], nodes[i], rng.randint(1, 5))
                )
            for _ in range(rng.randint(1, n)):
                a, b = rng.sample(nodes, 2)
                if not any({a, b} == {x, y} for x, y, _ in edges):
                    edges.append((a, b, rng.randint(1, 5)))
            overloaded = {
                nodes[i] for i in range(1, n) if rng.random() < 0.2
            }
            self.check_all_pairs_ksp(
                edges, nodes[0], overloaded=overloaded, ks=(1, 2, 3)
            )

    def test_single_dest_on_demand(self):
        # no prefetch: _kth_paths alone must still batch-solve lazily
        ls_oracle = build_ls(grid_edges(3))
        ls_dev = build_ls(grid_edges(3))
        solver = TpuSpfSolver("g0_0")
        got = solver._kth_paths(ls_dev, "g0_0", "g2_2", 2)
        want = ls_oracle.get_kth_paths("g0_0", "g2_2", 2)
        assert got == want


PFXS = ["10.1.0.0/16", "10.2.0.0/16", "10.3.0.0/16"]


def make_prefix_state(announcers, area="0", **entry_kw):
    ps = PrefixState()
    for node, pfxs in announcers.items():
        ps.update_prefix_database(
            PrefixDatabase(
                node,
                [PrefixEntry(IpPrefix(p), **entry_kw) for p in pfxs],
                area=area,
            )
        )
    return ps


def assert_route_db_equal(db_cpu, db_tpu):
    assert db_cpu is not None and db_tpu is not None
    assert set(db_cpu.unicast_entries) == set(db_tpu.unicast_entries)
    for prefix, entry in db_cpu.unicast_entries.items():
        assert db_tpu.unicast_entries[prefix] == entry, prefix
    assert set(db_cpu.mpls_entries) == set(db_tpu.mpls_entries)
    for label, entry in db_cpu.mpls_entries.items():
        assert db_tpu.mpls_entries[label] == entry, label


def run_parity(edges, announcers, me, overloaded=None, lfa=False, **entry_kw):
    ls_cpu = build_ls(edges, overloaded_nodes=overloaded)
    ls_tpu = build_ls(edges, overloaded_nodes=overloaded)
    ps = make_prefix_state(announcers, **entry_kw)
    cpu = SpfSolver(me, compute_lfa_paths=lfa)
    tpu = TpuSpfSolver(me, compute_lfa_paths=lfa)
    db_cpu = cpu.build_route_db(me, {"0": ls_cpu}, ps)
    db_tpu = tpu.build_route_db(me, {"0": ls_tpu}, ps)
    assert_route_db_equal(db_cpu, db_tpu)
    assert tpu.device_solves >= 1
    return db_tpu


class TestRouteDbParity:
    def test_line(self):
        run_parity(
            [("a", "b", 1), ("b", "c", 2)],
            {"b": [PFXS[0]], "c": [PFXS[1]]},
            "a",
        )

    def test_grid_ecmp(self):
        run_parity(
            grid_edges(4),
            {"g3_3": [PFXS[0]], "g0_3": [PFXS[1]], "g2_1": [PFXS[2]]},
            "g0_0",
        )

    def test_fabric(self):
        edges = fabric_edges(
            pods=2, planes=2, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=4
        )
        run_parity(
            edges,
            {"rsw1_0": [PFXS[0]], "rsw0_3": [PFXS[1]]},
            "rsw0_0",
        )

    def test_anycast(self):
        run_parity(
            [("a", "b", 1), ("a", "c", 1), ("b", "d", 1), ("c", "d", 1)],
            {"b": [PFXS[0]], "d": [PFXS[0]]},
            "a",
        )

    def test_overloaded_announcer(self):
        run_parity(
            [("a", "b", 1), ("a", "c", 1)],
            {"b": [PFXS[0]], "c": [PFXS[0]]},
            "a",
            overloaded={"b"},
        )

    def test_overloaded_transit(self):
        run_parity(
            [("a", "b", 1), ("b", "c", 1), ("a", "c", 10)],
            {"c": [PFXS[0]]},
            "a",
            overloaded={"b"},
        )

    def test_lfa_parity(self):
        run_parity(
            [("a", "b", 1), ("a", "c", 2), ("c", "b", 1)],
            {"b": [PFXS[0]]},
            "a",
            lfa=True,
        )

    def test_ksp2_parity(self):
        run_parity(
            [("a", "b", 1), ("a", "c", 1), ("c", "b", 1)],
            {"b": [PFXS[0]]},
            "a",
            forwarding_type=PrefixForwardingType.SR_MPLS,
            forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
        )

    def test_ksp2_anycast_grid_parity(self):
        # anycast KSP2 over a grid: multiple dests per prefix exercises the
        # one-device-call-per-k prefetch batching in _select_ksp2
        run_parity(
            grid_edges(4),
            {
                "g3_3": [PFXS[0]],
                "g0_3": [PFXS[0], PFXS[1]],
                "g2_1": [PFXS[1], PFXS[2]],
                "g1_2": [PFXS[2]],
            },
            "g0_0",
            forwarding_type=PrefixForwardingType.SR_MPLS,
            forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
        )

    def test_wan_random(self):
        edges = wan_edges(24, degree=4, seed=7)
        run_parity(
            edges,
            {"w3": [PFXS[0]], "w17": [PFXS[1]], "w9": [PFXS[2]]},
            "w0",
        )

    def test_random_parity_sweep(self):
        rng = random.Random(1234)
        for trial in range(6):
            n = rng.randint(5, 14)
            nodes = [f"n{i}" for i in range(n)]
            edges = []
            for i in range(1, n):
                edges.append(
                    (nodes[rng.randrange(i)], nodes[i], rng.randint(1, 9))
                )
            for _ in range(rng.randint(0, n // 2)):
                a, b = rng.sample(nodes, 2)
                if not any(
                    {a, b} == {x, y} for x, y, _ in edges
                ):
                    edges.append((a, b, rng.randint(1, 9)))
            announcers = {
                rng.choice(nodes[1:]): [PFXS[i % 3]] for i in range(3)
            }
            overloaded = {
                nodes[i] for i in range(1, n) if rng.random() < 0.15
            }
            run_parity(edges, announcers, nodes[0], overloaded=overloaded)

    def test_multi_area_parity_with_absent_node(self):
        # me participates in area A only; area B's graph lacks me entirely —
        # the TPU backend must fall back to the CPU oracle for area B
        def build(area, edges):
            ls = LinkState(area)
            for db in build_adj_dbs(edges, area=area).values():
                ls.update_adjacency_database(db)
            return ls

        als_cpu = {
            "A": build("A", [("a", "b", 1)]),
            "B": build("B", [("x", "y", 1)]),
        }
        als_tpu = {
            "A": build("A", [("a", "b", 1)]),
            "B": build("B", [("x", "y", 1)]),
        }
        ps = PrefixState()
        ps.update_prefix_database(
            PrefixDatabase("b", [PrefixEntry(IpPrefix(PFXS[0]))], area="A")
        )
        ps.update_prefix_database(
            PrefixDatabase("y", [PrefixEntry(IpPrefix(PFXS[1]))], area="B")
        )
        db_cpu = SpfSolver("a").build_route_db("a", als_cpu, ps)
        db_tpu = TpuSpfSolver("a").build_route_db("a", als_tpu, ps)
        assert_route_db_equal(db_cpu, db_tpu)
        # reachable prefix programmed, unreachable (other area) not
        assert IpPrefix(PFXS[0]) in db_tpu.unicast_entries
        assert IpPrefix(PFXS[1]) not in db_tpu.unicast_entries

    def test_incremental_update_recompiles(self):
        # topology change bumps LinkState.version; solver must re-solve
        edges = [("a", "b", 1), ("b", "c", 1), ("a", "c", 5)]
        ls = build_ls(edges)
        ps = make_prefix_state({"c": [PFXS[0]]})
        tpu = TpuSpfSolver("a")
        db1 = tpu.build_route_db("a", {"0": ls}, ps)
        nh1 = {
            nh.neighbor_node
            for nh in db1.unicast_entries[IpPrefix(PFXS[0])].nexthops
        }
        assert nh1 == {"b"}
        solves_before = tpu.device_solves
        # flap a-b: now direct a-c wins
        dbs = build_adj_dbs([("a", "c", 5)])
        from openr_tpu.types import AdjacencyDatabase

        new_a = AdjacencyDatabase(
            "a",
            [x for x in build_adj_dbs(edges)["a"].adjacencies
             if x.other_node_name != "b"],
            area="0",
        )
        ls.update_adjacency_database(new_a)
        db2 = tpu.build_route_db("a", {"0": ls}, ps)
        nh2 = {
            nh.neighbor_node
            for nh in db2.unicast_entries[IpPrefix(PFXS[0])].nexthops
        }
        assert nh2 == {"c"}
        assert tpu.device_solves == solves_before + 1
        # unchanged topology: cached solve reused
        tpu.build_route_db("a", {"0": ls}, ps)
        assert tpu.device_solves == solves_before + 1


class TestDeviceBufferProvenance:
    def test_two_refreshes_without_solve_fall_back_to_full_diff(self):
        """Safety of the changed-edges fast path: if the solver's device
        snapshot is two refreshes behind (parent_version mismatch), the
        full diff must catch BOTH events' weight changes — a silent miss
        here means stale device weights and wrong routes, not a crash."""
        import dataclasses

        from openr_tpu.solver import SpfSolver, TpuSpfSolver
        from openr_tpu.lsdb.prefix_state import PrefixState
        from openr_tpu.types import IpPrefix, PrefixDatabase, PrefixEntry

        edges = [("a", "b", 1), ("b", "c", 1), ("c", "d", 1), ("a", "d", 9)]
        dbs = build_adj_dbs(edges)
        ls = build_ls(edges)
        ps = PrefixState()
        for i, node in enumerate(sorted(dbs)):
            ps.update_prefix_database(
                PrefixDatabase(
                    node, [PrefixEntry(IpPrefix(f"10.{i}.0.0/24"))], area="0"
                )
            )
        tpu = TpuSpfSolver("a")
        assert tpu.build_route_db("a", {"0": ls}, ps) == SpfSolver(
            "a"
        ).build_route_db("a", {"0": ls}, ps)

        # two graph refreshes with NO solve in between: the device
        # snapshot (w_ver) is two versions behind, so the fast-path guard
        # must fail and the full diff must catch both events' changes
        from openr_tpu.ops.graph import refresh_graph

        area = tpu._solves[(ls.area, "a")][1]
        for metric in (5, 7):
            db = dbs["b"]
            db = dataclasses.replace(
                db,
                adjacencies=[
                    dataclasses.replace(adj, metric=metric)
                    for adj in db.adjacencies
                ],
            )
            dbs["b"] = db
            ls.update_adjacency_database(db)
            area.graph = refresh_graph(area.graph, ls)
        assert area.graph.parent_version != area._dev["w_ver"]

        # solving against the doubly-refreshed graph must see the final
        # weights (stale device buffers here would mean wrong distances)
        area._solve()
        got = tpu.build_route_db("a", {"0": ls}, ps)
        want = SpfSolver("a").build_route_db("a", {"0": ls}, ps)
        assert got == want
        assert area._dev["w_ver"] == area.graph.version


class TestSkewedWan:
    """ISSUE 35: `wan_edges(2048, 4, 3)` from its node of highest degree,
    the shape of the benchmark's `wan65536` in small: in-degrees 2-11 that
    the sliced-ELL layout merges into more than one class with padded
    slots, metrics 1-100, a deep shortest-path tree. Through a full build
    and 32 warm DeltaPath events the routes are the CPU oracle's."""

    def test_routes_equal_the_oracles_after_32_raises_and_restores(self):
        import collections
        import dataclasses

        from openr_tpu.solver import DeltaRouteBuilder

        edges = wan_edges(2048, degree=4, seed=3)
        degree = collections.Counter(n for a, b, _ in edges for n in (a, b))
        me = min(degree, key=lambda n: (-degree[n], int(n[1:])))
        assert (me, degree[me]) == ("w1986", 11)
        dbs = build_adj_dbs(edges)
        ls = LinkState("0")
        for db in dbs.values():
            ls.update_adjacency_database(db)
        als = {"0": ls}
        ps = make_prefix_state({
            n: [f"10.{i // 256}.{i % 256}.0/24"] for i, n in enumerate(sorted(dbs))
        })

        def set_metric(a, b, metric):  # both directions, as one event
            for x, y in ((a, b), (b, a)):
                dbs[x] = dataclasses.replace(dbs[x], adjacencies=[
                    dataclasses.replace(adj, metric=metric)
                    if adj.other_node_name == y else adj
                    for adj in dbs[x].adjacencies
                ])
                ls.update_adjacency_database(dbs[x])

        tpu = TpuSpfSolver(me)
        builder = DeltaRouteBuilder(tpu)
        db, _, used = builder.build(me, als, ps, None, force_full=True)
        assert not used
        assert_route_db_equal(SpfSolver(me).build_route_db(me, als, ps), db)
        assert len(db.unicast_entries) == 2047

        # the layout: degrees merged under the waste budget into several
        # classes, some slots of which are padding; the gauges read it
        graph = tpu._solves[("0", me)][1].graph
        sell = graph.sell
        slots = sum(a.size for a in sell.nbr)
        assert 1 < len(sell.nbr) < len(set(degree.values()))
        assert graph.e == 2 * len(edges) == 8192 < slots
        assert graph.n_pad == 2048
        gauges = {
            "decision.spf.nodes_padded_last": 2048,
            "decision.spf.sell_classes_last": len(sell.nbr),
            "decision.spf.sell_slots_last": slots,
            "decision.spf.rows_last": 12,
            "decision.spf.rows_padded_last": 16,
        }
        assert {k: tpu.counters[k] for k in gauges} == gauges

        # links of the vantage's shortest-path tree, none its own
        dist = {n: r.metric for n, r in ls.get_spf_result(me).items()}
        tree = sorted(
            (a, b, m) for a, b, m in edges
            if me not in (a, b) and abs(dist[a] - dist[b]) == m
        )
        from openr_tpu.ops.spf import _delta_extract, compile_cache_stats

        misses0 = compile_cache_stats()["misses"]
        extracts0 = _delta_extract._cache_size()
        rng = random.Random(2**31 + 35)
        raised = None
        moved = 0
        for k in range(32):
            if raised is not None:
                set_metric(*raised)  # the restore of the raise before it
                raised = None
            else:
                a, b, m = tree[rng.randrange(len(tree))]
                raised = (a, b, m)
                set_metric(a, b, m + rng.randint(2, 17))
            db, update, used = builder.build(me, als, ps, db)
            assert used, k  # a metric change off the vantage's links stays warm
            moved += len(update.unicast_routes_to_update)
            assert_route_db_equal(SpfSolver(me).build_route_db(me, als, ps), db)
        assert moved >= 32
        assert tpu.counters["decision.spf.incremental_solves"] == 32
        assert tpu.counters["decision.spf.full_solves"] == 1
        assert tpu.counters["decision.spf.delta_columns"] > 0
        assert {k: tpu.counters[k] for k in gauges} == gauges
        # events of several sizes: each power-of-two bucket of changed
        # columns is an executable of the extraction, counted as a miss
        extracts = _delta_extract._cache_size() - extracts0
        assert extracts >= 2
        assert compile_cache_stats()["misses"] - misses0 >= extracts
        assert (
            tpu.counters["decision.spf.compile_cache_misses"]
            >= compile_cache_stats()["misses"] - misses0
        )
        assert tpu.counters.get("decision.route_build_generic_routes", 0) == 0
