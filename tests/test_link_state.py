"""LinkState graph + SPF tests, mirroring openr/decision/tests/LinkStateTest.cpp."""

import pytest

from openr_tpu.lsdb import HoldableValue, LinkState
from openr_tpu.lsdb.link_state import path_a_in_path_b
from openr_tpu.topology import build_adj_dbs, grid_edges, make_adj_pair
from openr_tpu.types import Adjacency, AdjacencyDatabase


def build_link_state(edges, area="0", **kwargs):
    ls = LinkState(area)
    for db in build_adj_dbs(edges, area=area, **kwargs).values():
        ls.update_adjacency_database(db)
    return ls


class TestGraphChangelog:
    """The compiled graph's changelog alone: a sliding window of the
    newest entries under absolute positions."""

    CAP = LinkState._GRAPH_LOG_CAP

    @pytest.mark.parametrize("stride", [1, 4, 64, 1024])
    def test_reader_that_keeps_up_gets_every_entry_once_in_order(self, stride):
        ls = LinkState("0")
        pos, got = ls.graph_log_pos, []
        for i in range(3 * self.CAP):
            ls._log_graph("link", i)
            assert len(ls._graph_log) <= self.CAP
            assert ls.graph_log_pos == i + 1  # absolute across trims
            # read out of step with the cap, as a reader is after a load
            if (i + 1) % stride == stride // 2:
                changes = ls.graph_changes_since(pos)
                assert changes is not None, i
                got += changes
                pos = ls.graph_log_pos
        got += ls.graph_changes_since(pos)
        assert ls._graph_log_base > 0  # the log was trimmed on the way
        assert got == [("link", i) for i in range(3 * self.CAP)]
        assert ls.graph_changes_since(ls.graph_log_pos) == []

    def test_reader_left_behind_rebuilds_and_the_newest_half_is_kept(self):
        ls = LinkState("0")
        for i in range(3 * self.CAP):
            ls._log_graph("link", i)
            # whatever the moment of a trim, half a cap of entries is there
            behind = max(0, ls.graph_log_pos - self.CAP // 2)
            changes = ls.graph_changes_since(behind)
            assert len(changes) == i + 1 - behind
            assert changes[0] == ("link", behind) and changes[-1] == ("link", i)
        assert ls.graph_changes_since(ls.graph_log_pos - self.CAP - 1) is None
        assert ls.graph_changes_since(0) is None


class TestHoldableValue:
    def test_bool_holds(self):
        hv = HoldableValue(True)
        assert hv.value is True
        assert not hv.has_hold()
        assert not hv.decrement_ttl()
        hold_up, hold_down = 10, 5
        # True->False is a "down" change... for bool, bringing-up means
        # clearing overload (True->False), so holdUpTtl applies
        assert not hv.update_value(False, hold_up, hold_down)
        for _ in range(hold_up - 1):
            assert hv.has_hold()
            assert hv.value is True
            assert not hv.decrement_ttl()
        assert hv.decrement_ttl()
        assert not hv.has_hold()
        assert hv.value is False

        # same-value update: no-op
        assert not hv.update_value(False, hold_up, hold_down)
        assert not hv.has_hold()

        # False->True uses holdDownTtl
        assert not hv.update_value(True, hold_up, hold_down)
        for _ in range(hold_down - 1):
            assert hv.has_hold()
            assert hv.value is False
            assert not hv.decrement_ttl()
        assert hv.decrement_ttl()
        assert hv.value is True

        # double change within ttl falls back to fast update
        assert not hv.update_value(False, hold_up, hold_down)
        assert hv.has_hold()
        assert hv.value is True
        assert not hv.decrement_ttl()
        assert hv.update_value(True, hold_up, hold_down)
        assert not hv.has_hold()
        assert hv.value is True

    def test_metric_holds(self):
        hv = HoldableValue(10)
        # lowering a metric is a bringing-up change
        assert not hv.update_value(5, 10, 5)
        for _ in range(9):
            assert hv.has_hold()
            assert hv.value == 10
            assert not hv.decrement_ttl()
        assert hv.decrement_ttl()
        assert hv.value == 5
        # raising is a down change -> holdDownTtl
        assert not hv.update_value(7, 10, 5)
        for _ in range(4):
            assert not hv.decrement_ttl()
        assert hv.value == 7 or hv.has_hold()  # hold expired on 5th
        # zero ttl -> immediate
        hv2 = HoldableValue(1)
        assert hv2.update_value(2, 0, 0)
        assert hv2.value == 2


class TestLink:
    def test_accessors(self):
        a1, a2 = make_adj_pair("node1", "node2", 7, 9)
        from openr_tpu.lsdb.link_state import Link

        l = Link("0", "node1", a1, "node2", a2)
        assert l.other_node_name("node1") == "node2"
        assert l.other_node_name("node2") == "node1"
        with pytest.raises(ValueError):
            l.other_node_name("node3")
        assert l.iface_from_node("node1") == "if-node1-node2"
        assert l.metric_from_node("node1") == 7
        assert l.metric_from_node("node2") == 9
        assert not l.overload_from_node("node1")
        assert l.is_up()
        assert l.set_metric_from_node("node1", 2, 0, 0)
        assert l.metric_from_node("node1") == 2
        assert l.set_overload_from_node("node2", True, 0, 0)
        assert not l.is_up()
        # second overload on other side: up-ness unchanged -> no topo change
        assert not l.set_overload_from_node("node1", True, 0, 0)

    def test_identity(self):
        a1, a2 = make_adj_pair("node1", "node2")
        from openr_tpu.lsdb.link_state import Link

        l1 = Link("0", "node1", a1, "node2", a2)
        l2 = Link("0", "node2", a2, "node1", a1)  # same link, other direction
        assert l1 == l2
        assert hash(l1) == hash(l2)
        assert l1.first_node_name() == "node1"


class TestLinkStateTopology:
    def test_bidirectional_only(self):
        """A link exists only once both ends advertise it."""
        ls = LinkState("0")
        a1, a2 = make_adj_pair("n1", "n2")
        ch = ls.update_adjacency_database(
            AdjacencyDatabase("n1", [a1], area="0")
        )
        assert not ch.topology_changed  # unidirectional: no link yet
        assert ls.num_links() == 0
        ch = ls.update_adjacency_database(
            AdjacencyDatabase("n2", [a2], area="0")
        )
        assert ch.topology_changed
        assert ls.num_links() == 1
        assert ls.num_nodes() == 2

    def test_link_removal(self):
        ls = build_link_state([("n1", "n2", 1), ("n2", "n3", 1)])
        assert ls.num_links() == 2
        # n2 withdraws adjacency to n3
        a1, _ = make_adj_pair("n2", "n1")
        ch = ls.update_adjacency_database(
            AdjacencyDatabase("n2", [a1], area="0")
        )
        assert ch.topology_changed
        assert ls.num_links() == 1

    def test_delete_adjacency_database(self):
        ls = build_link_state([("n1", "n2", 1), ("n2", "n3", 1)])
        ch = ls.delete_adjacency_database("n2")
        assert ch.topology_changed
        assert ls.num_links() == 0
        assert not ls.has_node("n2")
        assert not ls.delete_adjacency_database("nope").topology_changed

    def test_metric_change_invalidates_spf(self):
        ls = build_link_state([("n1", "n2", 1), ("n2", "n3", 1), ("n1", "n3", 5)])
        assert ls.get_metric_from_a_to_b("n1", "n3") == 2
        # raise n1-n2 metric from n1 side to 10 => direct path wins
        dbs = build_adj_dbs(
            [("n1", "n2", 10), ("n1", "n3", 5)]
        )
        ch = ls.update_adjacency_database(dbs["n1"])
        assert ch.topology_changed
        assert ls.get_metric_from_a_to_b("n1", "n3") == 5

    def test_node_label_change(self):
        ls = LinkState("0")
        db = AdjacencyDatabase("n1", [], area="0", node_label=100)
        ch = ls.update_adjacency_database(db)
        assert ch.node_label_changed
        db2 = AdjacencyDatabase("n1", [], area="0", node_label=100)
        assert not ls.update_adjacency_database(db2).node_label_changed
        db3 = AdjacencyDatabase("n1", [], area="0", node_label=200)
        assert ls.update_adjacency_database(db3).node_label_changed


class TestSpf:
    def test_line_topology(self):
        ls = build_link_state([("a", "b", 1), ("b", "c", 2), ("c", "d", 3)])
        res = ls.get_spf_result("a")
        assert res["a"].metric == 0
        assert res["b"].metric == 1
        assert res["c"].metric == 3
        assert res["d"].metric == 6
        assert res["d"].next_hops == {"b"}

    def test_ecmp_nexthops(self):
        # a->b->d and a->c->d equal cost
        ls = build_link_state(
            [("a", "b", 1), ("a", "c", 1), ("b", "d", 1), ("c", "d", 1)]
        )
        res = ls.get_spf_result("a")
        assert res["d"].metric == 2
        assert res["d"].next_hops == {"b", "c"}
        # with unequal costs only one nexthop
        ls2 = build_link_state(
            [("a", "b", 1), ("a", "c", 2), ("b", "d", 1), ("c", "d", 1)]
        )
        assert ls2.get_spf_result("a")["d"].next_hops == {"b"}

    def test_overloaded_node_no_transit(self):
        # b overloaded: a can reach b but must not transit through it
        ls = build_link_state(
            [("a", "b", 1), ("b", "c", 1), ("a", "c", 10)],
            overloaded_nodes={"b"},
        )
        res = ls.get_spf_result("a")
        assert res["b"].metric == 1  # still reachable
        assert res["c"].metric == 10  # but not via b
        assert res["c"].next_hops == {"c"}

    def test_overloaded_source_ok(self):
        # the source itself overloaded still computes its own routes
        ls = build_link_state(
            [("a", "b", 1), ("b", "c", 1)], overloaded_nodes={"a"}
        )
        res = ls.get_spf_result("a")
        assert res["c"].metric == 2

    def test_link_down_via_overload(self):
        ls = build_link_state([("a", "b", 1), ("a", "c", 1), ("c", "b", 1)])
        assert ls.get_spf_result("a")["b"].metric == 1
        # overload the a-b link from a's side => path a->c->b
        dbs = build_adj_dbs([("a", "b", 1), ("a", "c", 1)])
        a_adjs = []
        for adj in dbs["a"].adjacencies:
            if adj.other_node_name == "b":
                from openr_tpu.types import replace

                adj = replace(adj, is_overloaded=True)
            a_adjs.append(adj)
        ch = ls.update_adjacency_database(
            AdjacencyDatabase("a", a_adjs, area="0")
        )
        assert ch.topology_changed
        assert ls.get_spf_result("a")["b"].metric == 2
        assert ls.get_spf_result("a")["b"].next_hops == {"c"}

    def test_hop_count_mode(self):
        ls = build_link_state([("a", "b", 10), ("b", "c", 20)])
        assert ls.get_metric_from_a_to_b("a", "c") == 30
        assert ls.get_hops_from_a_to_b("a", "c") == 2
        assert ls.get_max_hops_to_node("a") == 2

    def test_unreachable(self):
        ls = build_link_state([("a", "b", 1), ("c", "d", 1)])
        assert ls.get_metric_from_a_to_b("a", "c") is None
        assert ls.get_metric_from_a_to_b("a", "a") == 0

    def test_memoization(self):
        ls = build_link_state([("a", "b", 1), ("b", "c", 1)])
        ls.get_spf_result("a")
        runs = ls.spf_runs
        ls.get_spf_result("a")
        assert ls.spf_runs == runs  # cached
        ls.get_spf_result("b")
        assert ls.spf_runs == runs + 1
        # topology change invalidates
        ls.update_adjacency_database(
            build_adj_dbs([("a", "b", 5), ("b", "c", 1)])["a"]
        )
        ls.get_spf_result("a")
        assert ls.spf_runs == runs + 2


class TestHolds:
    def test_ordered_fib_hold(self):
        # new link held up for hold_up_ttl ticks
        ls = LinkState("0")
        dbs = build_adj_dbs([("a", "b", 1)])
        ls.update_adjacency_database(dbs["a"], hold_up_ttl=2, hold_down_ttl=1)
        ch = ls.update_adjacency_database(
            dbs["b"], hold_up_ttl=2, hold_down_ttl=1
        )
        # new link is held (not up) => no topology change yet
        assert not ch.topology_changed
        assert ls.has_holds()
        assert "b" not in ls.get_spf_result("a")
        assert not ls.decrement_holds().topology_changed
        assert ls.decrement_holds().topology_changed  # hold expired
        assert not ls.has_holds()
        assert ls.get_spf_result("a")["b"].metric == 1

    def test_metric_hold(self):
        ls = build_link_state([("a", "b", 10)])
        # lower the metric with holds: old value visible until expiry
        dbs = build_adj_dbs([("a", "b", 1)])
        ch = ls.update_adjacency_database(
            dbs["a"], hold_up_ttl=3, hold_down_ttl=1
        )
        assert not ch.topology_changed  # held
        assert ls.get_spf_result("a")["b"].metric == 10
        ls.decrement_holds()
        ls.decrement_holds()
        assert ls.decrement_holds().topology_changed
        assert ls.get_spf_result("a")["b"].metric == 1


class TestKthPaths:
    def test_two_disjoint_paths(self):
        # square: two edge-disjoint equal-cost paths a->d
        ls = build_link_state(
            [("a", "b", 1), ("a", "c", 1), ("b", "d", 1), ("c", "d", 1)]
        )
        paths = ls.get_kth_paths("a", "d", 1)
        assert len(paths) == 2
        used = set()
        for p in paths:
            assert len(p) == 2
            for link in p:
                assert link not in used  # edge-disjoint
                used.add(link)
        assert ls.get_kth_paths("a", "d", 2) == []

    def test_second_shortest(self):
        # triangle with a longer detour: k=1 direct, k=2 via c
        ls = build_link_state(
            [("a", "b", 1), ("a", "c", 1), ("c", "b", 1)]
        )
        k1 = ls.get_kth_paths("a", "b", 1)
        assert len(k1) == 1 and len(k1[0]) == 1
        k2 = ls.get_kth_paths("a", "b", 2)
        assert len(k2) == 1 and len(k2[0]) == 2

    def test_path_a_in_path_b(self):
        ls = build_link_state(
            [("a", "b", 1), ("b", "c", 1), ("c", "d", 1)]
        )
        pab = ls.get_kth_paths("a", "b", 1)[0]
        pad = ls.get_kth_paths("a", "d", 1)[0]
        assert path_a_in_path_b(pab, pad)
        assert not path_a_in_path_b(pad, pab)

    def test_same_node(self):
        ls = build_link_state([("a", "b", 1)])
        assert ls.get_kth_paths("a", "a", 1) == []


class TestGrid:
    def test_grid_spf(self):
        n = 5
        ls = build_link_state(grid_edges(n))
        res = ls.get_spf_result("g0_0")
        assert len(res) == n * n
        # manhattan distance on unit grid
        assert res[f"g{n-1}_{n-1}"].metric == 2 * (n - 1)
        # corner-to-corner ECMP: both neighbors of source are nexthops
        assert res[f"g{n-1}_{n-1}"].next_hops == {"g0_1", "g1_0"}


class TestPrefixState:
    def test_advertise_withdraw(self):
        from openr_tpu.lsdb import PrefixState
        from openr_tpu.types import (
            IpPrefix,
            PrefixDatabase,
            PrefixEntry,
            PrefixType,
        )

        ps = PrefixState()
        p1 = IpPrefix("10.1.0.0/16")
        p2 = IpPrefix("10.2.0.0/16")
        db = PrefixDatabase(
            "n1",
            [PrefixEntry(p1), PrefixEntry(p2)],
            area="0",
        )
        changed = ps.update_prefix_database(db)
        assert changed == {p1, p2}
        # no-op re-advertisement
        assert ps.update_prefix_database(db) == set()
        # withdraw p2
        db2 = PrefixDatabase("n1", [PrefixEntry(p1)], area="0")
        assert ps.update_prefix_database(db2) == {p2}
        assert ps.has_prefix(p1) and not ps.has_prefix(p2)

    def test_multi_node_multi_area(self):
        from openr_tpu.lsdb import PrefixState
        from openr_tpu.types import IpPrefix, PrefixDatabase, PrefixEntry

        ps = PrefixState()
        p = IpPrefix("10.0.0.0/8")
        ps.update_prefix_database(
            PrefixDatabase("n1", [PrefixEntry(p)], area="a1")
        )
        ps.update_prefix_database(
            PrefixDatabase("n2", [PrefixEntry(p)], area="a2")
        )
        assert set(ps.prefixes[p].keys()) == {"n1", "n2"}
        # withdraw from n1/a1 only
        ps.update_prefix_database(PrefixDatabase("n1", [], area="a1"))
        assert set(ps.prefixes[p].keys()) == {"n2"}

    def test_loopback_tracking(self):
        from openr_tpu.lsdb import PrefixState
        from openr_tpu.types import (
            IpPrefix,
            PrefixDatabase,
            PrefixEntry,
            PrefixType,
        )

        ps = PrefixState()
        lo = IpPrefix("192.168.0.1/32")
        ps.update_prefix_database(
            PrefixDatabase(
                "n1", [PrefixEntry(lo, type=PrefixType.LOOPBACK)], area="0"
            )
        )
        vias = ps.get_loopback_vias({"n1"}, is_v4=True, igp_metric=5)
        assert len(vias) == 1
        assert vias[0].address == "192.168.0.1"
        assert vias[0].metric == 5
        assert ps.get_loopback_vias({"n1"}, is_v4=False) == []
        # withdrawal clears it
        ps.update_prefix_database(PrefixDatabase("n1", [], area="0"))
        assert ps.get_loopback_vias({"n1"}, is_v4=True) == []
