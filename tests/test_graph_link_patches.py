"""A link that leaves or re-enters the LSDB is a weight patch on the slots
the compiled graph already has for it (ISSUE 34).

`LinkState`'s changelog names the link (`link_added`, `link_removed`) and
`refresh_graph` writes INF, or the returning link's metric, into the two
slots of its key. The invariant: after any refresh that did not recompile,
every slot of a link the LSDB holds reads what `compile_graph` would write
there, every other slot reads INF, and the snapshot keeps the identity of
`src`, `dst` and `link_edges`. On the served path that makes the vantage's
own link a cold solve over resident buffers (still a full route build) and
a remote link a warm solve and a DeltaPath build.
"""

import dataclasses
import random

import numpy as np
import pytest

from openr_tpu.lsdb import LinkState
from openr_tpu.ops import INF, compile_graph
from openr_tpu.ops.graph import refresh_graph
from openr_tpu.topology import build_adj_dbs, fabric_edges, grid_edges
from openr_tpu.types import IpPrefix

from test_route_delta import DeltaHarness, set_adj_overload, set_metric
from test_tpu_solver import all_pairs_distance_check_graph

PATCHED = "decision.spf.graph_links_patched"
RECOMPILES = "decision.spf.graph_recompiles"


def load(edges):
    """(dbs, ls): one database at a time in sorted order, so the Link a-b
    (a < b) is made when b's database arrives and has n1 == b."""
    dbs = build_adj_dbs(edges)
    ls = LinkState("0")
    for node in sorted(dbs):
        ls.update_adjacency_database(dbs[node])
    return dbs, ls


def withdraw(dbs, ls, node, other):
    """Take `node`'s adjacency toward `other` out of its database; returns
    it for `restore`."""
    adj = next(a for a in dbs[node].adjacencies if a.other_node_name == other)
    dbs[node] = dataclasses.replace(
        dbs[node], adjacencies=[a for a in dbs[node].adjacencies if a is not adj]
    )
    ls.update_adjacency_database(dbs[node])
    return adj


def restore(dbs, ls, node, adj, hold_up_ttl=0):
    dbs[node] = dataclasses.replace(
        dbs[node], adjacencies=dbs[node].adjacencies + [adj]
    )
    ls.update_adjacency_database(dbs[node], hold_up_ttl=hold_up_ttl)


def slot_weights(graph, link):
    """{source node name: weight} of the two slots the link's key has."""
    return {
        graph.names[graph.src[p]]: int(graph.w[p])
        for p in graph.link_edges[link]
    }


def assert_patched_like_compiled(ls, graph, compiled_from):
    """`graph`, refreshed from `compiled_from` without a recompile, holds
    what a fresh compile_graph holds."""
    assert graph.src is compiled_from.src and graph.dst is compiled_from.dst
    assert graph.link_edges is compiled_from.link_edges
    assert graph.names is compiled_from.names
    assert graph.sell.nbr is compiled_from.sell.nbr
    assert graph.sell.shape_key() == compiled_from.sell.shape_key()
    assert graph.version == ls.version
    fresh = compile_graph(ls)
    held = set()
    for link in ls.all_links:
        assert slot_weights(graph, link) == slot_weights(fresh, link), link.key
        held.update(graph.link_edges[link])
    others = np.setdiff1d(np.arange(graph.e_pad), sorted(held))
    assert (graph.w[others] == INF).all()
    sell = graph.sell
    for p in range(graph.e):
        assert (
            sell.wg[sell.edge_bucket[p]][sell.edge_row[p], sell.edge_slot[p]]
            == graph.w[p]
        )
    return fresh


class TestChangelogNamesTheLink:
    def test_withdrawal_and_arrival_carry_the_link(self):
        dbs, ls = load([("a", "b", 1), ("b", "c", 1), ("a", "c", 5)])
        (held,) = [l for l in ls.all_links if {l.n1, l.n2} == {"a", "b"}]
        pos = ls.graph_log_pos
        adj = withdraw(dbs, ls, "a", "b")
        assert ls.graph_changes_since(pos) == [("link_removed", held)]
        pos = ls.graph_log_pos
        restore(dbs, ls, "a", adj)
        ((kind, back),) = ls.graph_changes_since(pos)
        assert kind == "link_added" and back == held and back is not held
        assert back in ls.all_links

    def test_what_moves_the_node_set_stays_structure(self):
        edges = [("a", "b", 1), ("b", "c", 1)]
        dbs, ls = load(edges)
        more = build_adj_dbs(edges + [("c", "d", 1)])
        pos = ls.graph_log_pos
        ls.update_adjacency_database(more["d"])  # a node's first database
        assert ls.graph_changes_since(pos) == [("structure", None)]
        pos = ls.graph_log_pos
        ls.update_adjacency_database(more["c"])  # the link c-d, both ends known
        assert [k for k, _ in ls.graph_changes_since(pos)] == ["link_added"]
        pos = ls.graph_log_pos
        ls.delete_adjacency_database("d")
        assert ("structure", None) in ls.graph_changes_since(pos)
        bulk = LinkState("0")
        bulk.bulk_update_adjacency_databases(list(more.values()))
        assert bulk.graph_changes_since(0) == [("structure", None)]


class TestRefreshPatchesTheSlots:
    EDGES = [("a", "b", 1), ("b", "c", 1), ("a", "c", 5), ("c", "d", 2)]

    def test_withdrawn_is_inf_and_returned_is_the_metric(self):
        dbs, ls = load(self.EDGES)
        g0 = compile_graph(ls)
        (link,) = [l for l in ls.all_links if {l.n1, l.n2} == {"a", "c"}]
        adj = withdraw(dbs, ls, "a", "c")
        g1 = refresh_graph(g0, ls)
        assert slot_weights(g1, link) == {"a": INF, "c": INF}
        assert g1.links_patched == 1 and g1.log_pos == ls.graph_log_pos
        assert sorted(g1.changed_edges) == sorted(g0.link_edges[link])
        assert_patched_like_compiled(ls, g1, g0)
        all_pairs_distance_check_graph(ls, g1)
        restore(dbs, ls, "a", adj)
        g2 = refresh_graph(g1, ls)
        assert slot_weights(g2, link) == {"a": 5, "c": 5}
        assert g2.links_patched == 1 and g2.parent_version == g1.version
        assert_patched_like_compiled(ls, g2, g0)
        all_pairs_distance_check_graph(ls, g2)
        # the snapshots before it are untouched: w is copied, not written
        assert slot_weights(g1, link) == {"a": INF, "c": INF}
        assert slot_weights(g0, link) == {"a": 5, "c": 5}

    @pytest.mark.parametrize("from_end", ["a", "b"])
    def test_a_link_made_again_from_either_end_lands_each_metric(self, from_end):
        """The compiled Link a-b has n1 == "b" (b's database made it); taken
        out of and put back into a's database it is made with n1 == "a".
        Each slot still reads its own source node's metric, on the arrival
        and on the metric change after it."""
        dbs, ls = load(self.EDGES)
        set_metric(dbs, ls, "a", "b", 3)
        set_metric(dbs, ls, "b", "a", 7)
        (compiled,) = [l for l in ls.all_links if {l.n1, l.n2} == {"a", "b"}]
        assert compiled.n1 == "b"
        g0 = compile_graph(ls)
        assert slot_weights(g0, compiled) == {"a": 3, "b": 7}
        other = "b" if from_end == "a" else "a"
        adj = withdraw(dbs, ls, from_end, other)
        restore(dbs, ls, from_end, adj)
        (again,) = [l for l in ls.all_links if l == compiled]
        assert again is not compiled and again.n1 == from_end
        g1 = refresh_graph(g0, ls)
        assert slot_weights(g1, again) == {"a": 3, "b": 7}
        assert_patched_like_compiled(ls, g1, g0)
        set_metric(dbs, ls, "a", "b", 4)  # a "link" entry on the new object
        g2 = refresh_graph(g1, ls)
        assert slot_weights(g2, again) == {"a": 4, "b": 7}
        assert g2.links_patched == 0
        assert_patched_like_compiled(ls, g2, g0)
        set_adj_overload(dbs, ls, "b", "a", True)
        g3 = refresh_graph(g2, ls)
        assert slot_weights(g3, again) == {"a": INF, "b": INF}
        assert_patched_like_compiled(ls, g3, g0)
        all_pairs_distance_check_graph(ls, g3)

    def test_withdrawal_then_return_inside_one_refresh_ends_with_the_metric(self):
        dbs, ls = load(self.EDGES)
        g0 = compile_graph(ls)
        adj = withdraw(dbs, ls, "c", "a")
        restore(dbs, ls, "c", dataclasses.replace(adj, metric=8))
        g1 = refresh_graph(g0, ls)
        (link,) = [l for l in ls.all_links if {l.n1, l.n2} == {"a", "c"}]
        assert slot_weights(g1, link) == {"a": 5, "c": 8}
        assert g1.links_patched == 2
        assert_patched_like_compiled(ls, g1, g0)
        all_pairs_distance_check_graph(ls, g1)

    def test_return_then_withdrawal_inside_one_refresh_ends_with_inf(self):
        dbs, ls = load(self.EDGES)
        adj = withdraw(dbs, ls, "c", "a")
        g0 = compile_graph(ls)  # compiled without a-c: no slots for it
        restore(dbs, ls, "c", adj)
        g1 = refresh_graph(g0, ls)  # recompiles, and now has them
        assert g1.link_edges is not g0.link_edges
        (link,) = [l for l in ls.all_links if {l.n1, l.n2} == {"a", "c"}]
        withdraw(dbs, ls, "c", "a")
        g2 = refresh_graph(g1, ls)
        restore(dbs, ls, "c", adj)
        withdraw(dbs, ls, "a", "c")  # from the other end this time
        g3 = refresh_graph(g2, ls)
        assert slot_weights(g3, link) == {"a": INF, "c": INF}
        assert g3.links_patched == 2
        assert_patched_like_compiled(ls, g3, g1)
        all_pairs_distance_check_graph(ls, g3)

    def test_an_arrival_held_down_is_inf_until_its_hold_ends(self):
        dbs, ls = load(self.EDGES)
        g0 = compile_graph(ls)
        adj = withdraw(dbs, ls, "a", "c")
        g1 = refresh_graph(g0, ls)
        restore(dbs, ls, "a", adj, hold_up_ttl=1)
        (link,) = [l for l in ls.all_links if {l.n1, l.n2} == {"a", "c"}]
        assert not link.is_up()
        set_metric(dbs, ls, "c", "d", 4)  # moves the version: a refresh runs
        g2 = refresh_graph(g1, ls)
        assert slot_weights(g2, link) == {"a": INF, "c": INF}
        assert_patched_like_compiled(ls, g2, g0)
        assert ls.decrement_holds().topology_changed
        g3 = refresh_graph(g2, ls)
        assert slot_weights(g3, link) == {"a": 5, "c": 5}
        assert_patched_like_compiled(ls, g3, g0)


class TestWhatStillRecompiles:
    EDGES = [("a", "b", 1), ("b", "c", 1)]

    def _reason(self, caplog, g, ls):
        with caplog.at_level("INFO", logger="openr_tpu.ops.graph"):
            g2 = refresh_graph(g, ls)
        assert g2.link_edges is not g.link_edges and g2.src is not g.src
        assert g2.links_patched == 0
        all_pairs_distance_check_graph(ls, g2)
        # the graph's own: a collection inside the block may log a task that
        # an earlier test of this worker left pending (asyncio's logger)
        (record,) = (
            r for r in caplog.records if r.name == "openr_tpu.ops.graph"
        )
        return record.getMessage()

    def test_an_arrival_without_slots_says_unknown_edge(self, caplog):
        dbs, ls = load(self.EDGES)
        g = compile_graph(ls)
        more = build_adj_dbs(self.EDGES + [("a", "c", 5)])
        ls.update_adjacency_database(more["a"])
        ls.update_adjacency_database(more["c"])
        assert self._reason(caplog, g, ls).endswith("(unknown edge)")

    @pytest.mark.parametrize("how", ["new node", "deleted node", "bulk ingest"])
    def test_a_moved_node_set_says_structure(self, how, caplog):
        dbs, ls = load(self.EDGES)
        g = compile_graph(ls)
        more = build_adj_dbs(self.EDGES + [("c", "d", 1), ("d", "e", 1)])
        if how == "new node":
            ls.update_adjacency_database(more["d"])
            ls.update_adjacency_database(more["c"])
        elif how == "deleted node":
            ls.delete_adjacency_database("c")
        else:
            ls.bulk_update_adjacency_databases([more["d"], more["e"]])
        assert self._reason(caplog, g, ls).endswith("(structure)")


def _random_walk(edges, seed, batches):
    """Random batches of withdrawals (from one end or both), returns (in
    either order of the ends), metric changes, link overloads and node
    overloads; one refresh a batch, never a recompile."""
    rng = random.Random(seed)
    dbs, ls = load(edges)
    g0 = graph = compile_graph(ls)
    present = [(a, b) for a, b, _ in edges]
    gone = []  # (pair, [(node, adjacency taken out of its database)])
    patched = 0
    for batch in range(batches):
        for _ in range(rng.randint(1, 4)):
            op = rng.choice(
                ("withdraw", "withdraw", "return", "return", "metric",
                 "link_overload", "node_overload")
            )
            if op == "withdraw" and len(present) > 1:
                pair = present.pop(rng.randrange(len(present)))
                ends = [pair, pair[::-1]]
                rng.shuffle(ends)
                taken = [
                    (node, withdraw(dbs, ls, node, other))
                    for node, other in ends[: rng.randint(1, 2)]
                ]
                gone.append((pair, taken))
            elif op == "return" and gone:
                pair, taken = gone.pop(rng.randrange(len(gone)))
                rng.shuffle(taken)
                for node, adj in taken:
                    restore(dbs, ls, node, adj)
                present.append(pair)
            elif op == "metric":
                a, b = rng.choice(present)
                if rng.random() < 0.5:
                    a, b = b, a
                set_metric(dbs, ls, a, b, rng.randint(1, 9))
            elif op == "link_overload":
                a, b = rng.choice(present)
                adj = next(
                    x for x in dbs[a].adjacencies if x.other_node_name == b
                )
                set_adj_overload(dbs, ls, a, b, not adj.is_overloaded)
            elif op == "node_overload":
                node = rng.choice(sorted(dbs))
                dbs[node] = dataclasses.replace(
                    dbs[node], is_overloaded=not dbs[node].is_overloaded
                )
                ls.update_adjacency_database(dbs[node])
        before = graph
        graph = refresh_graph(graph, ls)
        # where nothing of the batch moved the topology (a down link left
        # or returned) the snapshot stands, and is still true
        patched += graph.links_patched if graph is not before else 0
        fresh = assert_patched_like_compiled(ls, graph, g0)
        if batch % 5 == 0:
            all_pairs_distance_check_graph(ls, graph)
            all_pairs_distance_check_graph(ls, fresh)
    return patched


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize(
    "edges",
    [
        fabric_edges(
            pods=2, planes=2, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=3
        ),
        grid_edges(4),
    ],
    ids=["fabric", "grid"],
)
def test_random_withdrawals_and_returns_keep_the_snapshot_true(edges, seed):
    patched = _random_walk(edges, seed, batches=40)
    assert patched > 20  # the walk did ask the mechanism


def _announcers(edges, me):
    nodes = sorted({n for a, b, _ in edges for n in (a, b)} - {me})
    return {n: [f"10.0.{i}.0/24"] for i, n in enumerate(nodes)}


class TestThroughTheSolver:
    """DeltaHarness.step() holds every build to a cold TpuSpfSolver's and
    to SpfSolver's route db."""

    def _harness(self, edges, me):
        h = DeltaHarness(edges, me, _announcers(edges, me))
        solve = h.solver._solves[("0", me)][1]
        assert h.solver.counters[PATCHED] == 0  # there from the first sync
        return h, solve

    def test_the_vantages_own_link_is_a_cold_solve_and_a_full_build(self):
        edges = fabric_edges(
            pods=2, planes=2, ssw_per_plane=2, fsw_per_pod=4, rsw_per_pod=3
        )
        me = "rsw0_0"
        h, solve = self._harness(edges, me)
        src, link_edges, dev = solve.graph.src, solve.graph.link_edges, solve._dev
        down = {}
        # the cell's event: the adjacency leaves both ends' databases, and
        # at the next event it returns while another leaves
        for n, (fsw, back) in enumerate(
            [("fsw0_0", None), ("fsw0_1", "fsw0_0"), ("fsw0_2", "fsw0_1")]
        ):
            full, incremental = solve.full_solves, solve.incremental_solves
            if back is not None:
                restore(h.dbs, h.ls, me, down.pop((me, back)))
                restore(h.dbs, h.ls, back, down.pop((back, me)))
            down[(me, fsw)] = withdraw(h.dbs, h.ls, me, fsw)
            down[(fsw, me)] = withdraw(h.dbs, h.ls, fsw, me)
            assert h.step() is False  # a full build, equal to the oracle's
            assert solve.full_solves == full + 1
            assert solve.incremental_solves == incremental
            assert solve.graph.src is src and solve._dev is dev
            assert solve.graph.link_edges is link_edges
            assert sorted(solve.sources[1:]) == sorted(
                {f"fsw0_{i}" for i in range(4)} - {fsw}
            )
            # one link leaves, and from the second event on one returns
            assert solve.graph_links_patched == 1 + 2 * n
        assert solve.graph_recompiles == 0
        assert h.solver.counters[RECOMPILES] == 0
        assert h.solver.counters[PATCHED] == solve.graph_links_patched == 5
        assert h.builder.delta_builds == 0

    def test_a_remote_link_is_a_warm_solve_and_a_delta_build(self):
        edges = grid_edges(5)
        me = "g0_0"
        h, solve = self._harness(edges, me)
        src, link_edges = solve.graph.src, solve.graph.link_edges
        set_metric(h.dbs, h.ls, "g2_2", "g2_3", 2)  # the resident solve warms
        assert h.step() is True
        steps = 0

        def warm_delta_step():
            nonlocal steps
            full, incremental = solve.full_solves, solve.incremental_solves
            assert h.step() is True
            assert solve.full_solves == full
            assert solve.incremental_solves == incremental + 1
            assert solve.graph.src is src
            assert solve.graph.link_edges is link_edges
            steps += 1

        # a link in the middle, from one end's database and back
        adj = withdraw(h.dbs, h.ls, "g1_1", "g1_2")
        warm_delta_step()
        restore(h.dbs, h.ls, "g1_1", adj)
        warm_delta_step()
        # the far corner's two links, one at a time: with the second its
        # last link goes, it cannot be reached and its route is deleted
        routes = set(h.db.unicast_entries)
        first = withdraw(h.dbs, h.ls, "g4_4", "g3_4")
        warm_delta_step()
        assert set(h.db.unicast_entries) == routes
        last = withdraw(h.dbs, h.ls, "g4_3", "g4_4")  # from the other end
        warm_delta_step()
        (deleted,) = routes - set(h.db.unicast_entries)
        assert deleted == IpPrefix(_announcers(edges, me)["g4_4"][0])
        restore(h.dbs, h.ls, "g4_3", last)
        warm_delta_step()
        assert set(h.db.unicast_entries) == routes
        restore(h.dbs, h.ls, "g4_4", first)
        warm_delta_step()
        assert solve.graph_recompiles == 0
        assert solve.graph_links_patched == steps == 6
        assert h.solver.counters[PATCHED] == 6
        assert h.solver.counters[RECOMPILES] == 0
        assert h.builder.delta_builds == steps + 1
        assert h.builder.full_builds == 1  # the first build alone
