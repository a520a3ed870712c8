"""ISSUE 32: the TPU backend answers "which next hops, at what metric,
toward this destination set" from the resident solve's mask and distance
row by table lookup. The route db built through the table must equal,
object for object, the one the generic next-hop stack builds on the same
solve and the CPU oracle's; inputs the table does not take (LFA, per-
destination actions, two areas with the vantage) walk the generic stack,
and two counters say which way a route's next hops came."""

import dataclasses

import pytest

from openr_tpu.lsdb import LinkState, PrefixState
from openr_tpu.solver import DeltaRouteBuilder, SpfSolver, TpuSpfSolver
from openr_tpu.solver.rib_policy import (
    RibPolicy,
    RibPolicyStatement,
    SetWeightAction,
)
from openr_tpu.topology import (
    build_adj_dbs,
    fabric_edges,
    grid_edges,
    make_adj_pair,
)
from openr_tpu.types import (
    AdjacencyDatabase,
    IpPrefix,
    MplsAction,
    MplsActionCode,
    PrefixDatabase,
    PrefixEntry,
    PrefixForwardingType,
)
from test_route_delta import (
    assert_route_db_equal,
    make_prefix_state as prefix_state,
    set_metric,
)

TABLE = "decision.route_build_table_routes"
GENERIC = "decision.route_build_generic_routes"


def link_state(dbs, area="0"):
    ls = LinkState(area)
    for db in dbs.values():
        ls.update_adjacency_database(db)
    return ls


def every_node_announces(dbs, v6_too=False):
    out = {}
    for i, node in enumerate(sorted(dbs)):
        out[node] = [f"10.{i // 256}.{i % 256}.0/24"]
        if v6_too:
            out[node].append(f"fc00:{i:x}::/64")
    return out


def assert_same_db(got, want):
    """Entry for entry and next hop for next hop: address, interface,
    metric, area, neighbour, MPLS action (NextHop's own equality), and
    what RibUnicastEntry's equality leaves out."""
    assert_route_db_equal(want, got)
    for prefix, entry in want.unicast_entries.items():
        assert got.unicast_entries[prefix].best_area == entry.best_area, prefix


def generic_stack(solver):
    """The same solver with its seam put back to SpfSolver's: today's
    pair over the same resident solve."""

    def base_seam(*args):
        return SpfSolver.next_hops_toward(solver, *args)

    def one_by_one(*args):
        return SpfSolver.build_unicast_routes(solver, *args)

    solver.next_hops_toward = base_seam
    # the plain prefixes of a build go to the table together, past the
    # seam above: here they go one by one, through it
    solver.build_unicast_routes = one_by_one
    return solver


def three_dbs(me, als, ps, **solver_kw):
    """(through the table, through the generic stack on a TpuSpfSolver,
    the CPU oracle's), and the table solver."""
    table_solver = TpuSpfSolver(me, **solver_kw)
    return (
        table_solver.build_route_db(me, als, ps),
        generic_stack(TpuSpfSolver(me, **solver_kw)).build_route_db(
            me, als, ps
        ),
        SpfSolver(me, **solver_kw).build_route_db(me, als, ps),
        table_solver,
    )


def routes_with_next_hops(db, me_label):
    """Routes whose next hops the seam gave: every unicast entry and every
    node-label entry but my own (the toys carry no adjacency label)."""
    return len(db.unicast_entries) + len(db.mpls_entries.keys() - {me_label})


def parallel_links_dbs(metric_one, metric_two):
    """a has two links to b (if1, if2) and one to c; b and c reach d."""
    def pair(a, b, metric, tag):
        adj_a, adj_b = make_adj_pair(a, b, metric)
        return (
            dataclasses.replace(
                adj_a, if_name=f"{adj_a.if_name}-{tag}",
                other_if_name=f"{adj_a.other_if_name}-{tag}",
                nexthop_v4=f"169.254.7.{tag}", nexthop_v6=f"fe80::7:{tag}",
            ),
            dataclasses.replace(
                adj_b, if_name=f"{adj_b.if_name}-{tag}",
                other_if_name=f"{adj_b.other_if_name}-{tag}",
            ),
        )

    adjs = {n: [] for n in "abcd"}
    ab1, ba1 = pair("a", "b", metric_one, 1)
    ab2, ba2 = pair("a", "b", metric_two, 2)
    adjs["a"] += [ab1, ab2]
    adjs["b"] += [ba1, ba2]
    for x, y, metric in (("a", "c", 2), ("b", "d", 1), ("c", "d", 1)):
        adj_x, adj_y = make_adj_pair(x, y, metric)
        adjs[x].append(adj_x)
        adjs[y].append(adj_y)
    return {
        node: AdjacencyDatabase(
            this_node_name=node, adjacencies=adjs[node], area="0",
            node_label=100 + i,
        )
        for i, node in enumerate(sorted(adjs))
    }


def clos():
    edges = fabric_edges(2, planes=2, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=3)
    return build_adj_dbs(edges), "rsw0_0"


def grid():
    return build_adj_dbs(grid_edges(5)), "g0_0"


def overloaded_neighbour():
    # g0_1 relays nothing: what lay behind it goes round through g1_0,
    # and g0_1 itself stays a destination over its own link
    return build_adj_dbs(grid_edges(4), overloaded_nodes={"g0_1"}), "g0_0"


def own_link_down():
    dbs, me = clos()
    peer = dbs[me].adjacencies[0].other_node_name
    dbs[me] = dataclasses.replace(
        dbs[me],
        adjacencies=[
            dataclasses.replace(adj, is_overloaded=adj.other_node_name == peer)
            for adj in dbs[me].adjacencies
        ],
    )
    return dbs, me


def partitioned():
    dbs = build_adj_dbs(grid_edges(3) + [("x", "y", 1)])
    return dbs, "g0_0"


SCENARIOS = {
    "clos_from_a_rack": clos,
    "grid_from_its_corner": grid,
    "parallel_links_unequal": lambda: (parallel_links_dbs(1, 3), "a"),
    "parallel_links_equal": lambda: (parallel_links_dbs(2, 2), "a"),
    "overloaded_neighbour": overloaded_neighbour,
    "own_link_down": own_link_down,
    "unreachable_nodes": partitioned,
}


class TestTableEqualsGenericStackAndOracle:
    @pytest.mark.parametrize("scenario", sorted(SCENARIOS))
    @pytest.mark.parametrize("v6_too", [False, True], ids=["v4", "v4_and_v6"])
    def test_whole_route_db(self, scenario, v6_too):
        dbs, me = SCENARIOS[scenario]()
        als = {"0": link_state(dbs)}
        ps = prefix_state(every_node_announces(dbs, v6_too))
        table, generic, oracle, solver = three_dbs(me, als, ps)
        assert_same_db(table, generic)
        assert_same_db(table, oracle)
        assert solver.counters[GENERIC] == 0
        assert solver.counters[TABLE] == routes_with_next_hops(
            table, dbs[me].node_label
        )
        assert solver.counters[TABLE] > 0

    @pytest.mark.parametrize("announcers, metric, neighbours", [
        # equidistant announcers: the union of their first hops
        (("g0_2", "g2_0"), 2, {"g0_1", "g1_0"}),
        # a nearer announcer: its first hops alone
        (("g0_1", "g2_2"), 1, {"g0_1"}),
        # one of the announcers lies in another partition
        (("g2_2", "x"), 4, {"g0_1", "g1_0"}),
        # three, two of them nearest through one neighbour each
        (("g0_2", "g2_0", "g2_2"), 2, {"g0_1", "g1_0"}),
    ], ids=["equidistant", "one_nearer", "one_unreachable", "three"])
    def test_anycast_prefix(self, announcers, metric, neighbours):
        dbs = build_adj_dbs(grid_edges(3) + [("x", "y", 1)])
        als = {"0": link_state(dbs)}
        ps = prefix_state({n: ["10.9.9.0/24", "fc00:9::/64"] for n in announcers})
        table, generic, oracle, solver = three_dbs("g0_0", als, ps)
        assert_same_db(table, generic)
        assert_same_db(table, oracle)
        for prefix in ("10.9.9.0/24", "fc00:9::/64"):
            nexthops = table.unicast_entries[IpPrefix(prefix)].nexthops
            assert {nh.neighbor_node for nh in nexthops} == neighbours
            assert {nh.metric for nh in nexthops} == {metric}
        assert solver.counters[GENERIC] == 0

    def test_v4_and_v6_addresses_and_label_actions(self):
        dbs, me = clos()
        als = {"0": link_state(dbs)}
        table, _, oracle, _ = three_dbs(
            me, als, prefix_state(every_node_announces(dbs, v6_too=True))
        )
        me_adjs = {a.other_node_name: a for a in dbs[me].adjacencies}
        far = next(n for n in sorted(dbs) if n.startswith("rsw1_"))
        i = sorted(dbs).index(far)
        v4 = table.unicast_entries[IpPrefix(f"10.0.{i}.0/24")].nexthops
        v6 = table.unicast_entries[IpPrefix(f"fc00:{i:x}::/64")].nexthops
        assert {nh.address for nh in v4} == {a.nexthop_v4 for a in me_adjs.values()}
        assert {nh.address for nh in v6} == {a.nexthop_v6 for a in me_adjs.values()}
        assert all(nh.mpls_action is None for nh in v4 | v6)
        # a neighbour's label: PHP over its own link
        peer = sorted(me_adjs)[0]
        php = table.mpls_entries[dbs[peer].node_label].nexthops
        assert [(nh.neighbor_node, nh.mpls_action) for nh in php] == [
            (peer, MplsAction(MplsActionCode.PHP))
        ]
        # a far node's label: SWAP to itself over every first hop
        swap = table.mpls_entries[dbs[far].node_label].nexthops
        assert {nh.neighbor_node for nh in swap} == set(me_adjs)
        assert {nh.mpls_action for nh in swap} == {
            MplsAction(MplsActionCode.SWAP, swap_label=dbs[far].node_label)
        }
        assert {nh.address for nh in swap} == {a.nexthop_v6 for a in me_adjs.values()}
        assert_same_db(table, oracle)

    def test_rib_policy_on_one_entry_leaves_its_groups_others(self):
        dbs, me = clos()
        als = {"0": link_state(dbs)}
        ps = prefix_state(every_node_announces(dbs))
        solver = TpuSpfSolver(me)
        db = solver.build_route_db(me, als, ps)
        racks = [n for n in sorted(dbs) if n.startswith("rsw1_")]
        one, other = (
            db.unicast_entries[IpPrefix(f"10.0.{sorted(dbs).index(n)}.0/24")]
            for n in racks[:2]
        )
        assert one.nexthops == other.nexthops  # one group, one distance
        assert one.nexthops is not other.nexthops
        before = set(other.nexthops)
        policy = RibPolicy(
            [RibPolicyStatement("w", {one.prefix}, SetWeightAction(default_weight=7))],
            ttl_secs=60,
        )
        assert policy.apply_action(one) and not policy.apply_action(other)
        assert {nh.weight for nh in one.nexthops} == {7}
        assert other.nexthops == before
        one.nexthops.clear()
        # and a route built after it reads the shared set unchanged
        again = solver.build_route_db(me, als, ps)
        assert again.unicast_entries[other.prefix].nexthops == before
        assert again.unicast_entries[one.prefix].nexthops == before


class TestTableFollowsItsMask:
    def _harness(self, side=5):
        dbs = build_adj_dbs(grid_edges(side))
        ls = link_state(dbs)
        ps = prefix_state(every_node_announces(dbs, v6_too=True))
        solver = TpuSpfSolver("g0_0")
        builder = DeltaRouteBuilder(solver)
        als = {"0": ls}
        db, _, used = builder.build("g0_0", als, ps, None, force_full=True)
        assert not used
        return dbs, ls, ps, solver, builder, als, db

    def _check(self, db, als, ps):
        assert_same_db(db, SpfSolver("g0_0").build_route_db("g0_0", als, ps))
        assert_same_db(
            db,
            generic_stack(TpuSpfSolver("g0_0")).build_route_db("g0_0", als, ps),
        )

    def test_warm_events_patch_columns_under_a_standing_table(self):
        dbs, ls, ps, solver, builder, als, db = self._harness()
        solve = solver._solves[("0", "g0_0")][1]
        table = solve.next_hop_table()
        mask = solve.nh_mask()[1]
        moved = 0
        for k, metric in enumerate((7, 1, 4, 9, 1, 3)):
            # row 0's far end moves out and back: rays of columns change
            set_metric(dbs, ls, "g0_2", "g0_3", metric)
            set_metric(dbs, ls, "g0_3", "g0_2", metric)
            table_before = solver.counters[TABLE]
            db, update, used = builder.build("g0_0", als, ps, db)
            assert used, k
            self._check(db, als, ps)
            # patched in place: the same mask, the same table over it
            assert solve.nh_mask()[1] is mask
            assert solve.next_hop_table() is table
            routes = len(update.unicast_routes_to_update) + len(
                update.mpls_routes_to_update
            )
            moved += routes
            # a unicast (v4 and v6) and a label route per changed column
            assert solver.counters[TABLE] - table_before >= routes
            assert solver.counters[GENERIC] == 0
        assert moved > 0

    def test_a_cold_event_drops_the_table_with_the_mask(self):
        dbs, ls, ps, solver, builder, als, db = self._harness()
        solve = solver._solves[("0", "g0_0")][1]
        table = solve.next_hop_table()
        # my own link's metric: the event does not qualify for a delta
        set_metric(dbs, ls, "g0_0", "g0_1", 5)
        set_metric(dbs, ls, "g0_1", "g0_0", 5)
        assert solver._dist(ls, "g0_0", "g0_1") == 3  # re-solved: round g1_0
        assert solve._nh_table is None and solve._nh_mask is None
        db, _, used = builder.build("g0_0", als, ps, db)
        assert not used
        self._check(db, als, ps)
        assert solve.next_hop_table() is not table
        assert solver.counters[GENERIC] == 0

    def test_a_moved_next_hop_address_builds_the_table_again(self):
        """An address change bumps no topology version: the mask stands,
        and the table over it reads the links' attributes again."""
        dbs, ls, ps, solver, builder, als, db = self._harness(side=3)
        solve = solver._solves[("0", "g0_0")][1]
        table = solve.next_hop_table()
        dbs["g0_0"] = dataclasses.replace(
            dbs["g0_0"],
            adjacencies=[
                dataclasses.replace(
                    adj, nexthop_v4="169.254.200.1", nexthop_v6="fe80::c8:1"
                )
                if adj.other_node_name == "g0_1"
                else adj
                for adj in dbs["g0_0"].adjacencies
            ],
        )
        version = ls.version
        change = ls.update_adjacency_database(dbs["g0_0"])
        assert change.link_attributes_changed and ls.version == version
        db = solver.build_route_db("g0_0", als, ps)
        self._check(db, als, ps)
        assert solve.next_hop_table() is not table
        addresses = {
            nh.address
            for entry in db.unicast_entries.values()
            for nh in entry.nexthops
        }
        assert {"169.254.200.1", "fe80::c8:1"} <= addresses


class TestFallbackAndCounters:
    def test_counters_exist_from_the_solvers_start(self):
        solver = TpuSpfSolver("a")
        assert solver.counters[TABLE] == 0 and solver.counters[GENERIC] == 0

    def test_lfa_walks_the_generic_stack(self):
        dbs = build_adj_dbs(grid_edges(4))
        als = {"0": link_state(dbs)}
        ps = prefix_state(every_node_announces(dbs))
        solver = TpuSpfSolver("g0_0", compute_lfa_paths=True)
        db = solver.build_route_db("g0_0", als, ps)
        assert_same_db(
            db,
            SpfSolver("g0_0", compute_lfa_paths=True).build_route_db(
                "g0_0", als, ps
            ),
        )
        assert solver.counters[TABLE] == 0
        assert solver.counters[GENERIC] == routes_with_next_hops(
            db, dbs["g0_0"].node_label
        )

    def test_an_sr_mpls_prefix_does_not_come_from_the_table(self):
        dbs = build_adj_dbs(grid_edges(3))
        als = {"0": link_state(dbs)}
        ps = prefix_state({"g2_2": ["10.1.0.0/16"]})
        ps.update_prefix_database(
            PrefixDatabase(
                "g1_2",
                [
                    PrefixEntry(
                        IpPrefix("10.2.0.0/16"),
                        forwarding_type=PrefixForwardingType.SR_MPLS,
                    )
                ],
                area="0",
            )
        )
        solver = TpuSpfSolver("g0_0")
        db = solver.build_route_db("g0_0", als, ps)
        assert_same_db(db, SpfSolver("g0_0").build_route_db("g0_0", als, ps))
        assert IpPrefix("10.2.0.0/16") in db.unicast_entries
        # the IP prefix and the eight other nodes' labels; the SR_MPLS
        # prefix's route is KSP2's, which asks the seam nothing
        assert solver.counters[TABLE] == 1 + 8
        assert solver.counters[GENERIC] == 0
        # and a per-destination question put to the seam is the generic
        # stack's, whose answer carries the PUSH of the destination's label
        nexthops = solver.next_hops_toward(
            "g0_0", {"g2_2"}, True, True, None, als, {"0"}
        )
        solver.sync_counters(als)
        assert (solver.counters[TABLE], solver.counters[GENERIC]) == (9, 1)
        assert {nh.mpls_action.action for nh in nexthops} == {MplsActionCode.PUSH}

    def test_two_areas_with_the_vantage_walk_the_generic_stack(self):
        als = {
            "A": link_state(
                build_adj_dbs([("a", "b", 1), ("b", "c", 1)], area="A"), "A"
            ),
            "B": link_state(
                build_adj_dbs([("a", "x", 1), ("x", "c", 1)], area="B"), "B"
            ),
        }
        ps = PrefixState()
        for area in ("A", "B"):
            ps.update_prefix_database(
                PrefixDatabase(
                    "c", [PrefixEntry(IpPrefix("10.3.0.0/16"))], area=area
                )
            )
        solver = TpuSpfSolver("a")
        db = solver.build_route_db("a", als, ps)
        assert_same_db(db, SpfSolver("a").build_route_db("a", als, ps))
        nexthops = db.unicast_entries[IpPrefix("10.3.0.0/16")].nexthops
        assert {nh.neighbor_node for nh in nexthops} == {"b", "x"}
        assert solver.counters[TABLE] == 0
        assert solver.counters[GENERIC] > 0

    def test_a_second_area_without_the_vantage_leaves_the_table_on(self):
        als = {
            "A": link_state(build_adj_dbs([("a", "b", 1)], area="A"), "A"),
            "B": link_state(build_adj_dbs([("x", "y", 1)], area="B"), "B"),
        }
        ps = prefix_state({"b": ["10.1.0.0/16"]}, area="A")
        ps.update_prefix_database(
            PrefixDatabase("y", [PrefixEntry(IpPrefix("10.2.0.0/16"))], area="B")
        )
        solver = TpuSpfSolver("a")
        db = solver.build_route_db("a", als, ps)
        assert_same_db(db, SpfSolver("a").build_route_db("a", als, ps))
        assert set(db.unicast_entries) == {IpPrefix("10.1.0.0/16")}
        # b's prefix and b's label; area B's labels find no route either way
        assert (solver.counters[TABLE], solver.counters[GENERIC]) == (2, 0)

    def test_another_nodes_view_walks_the_generic_stack(self):
        dbs = build_adj_dbs(grid_edges(3))
        als = {"0": link_state(dbs)}
        solver = TpuSpfSolver("g0_0")
        want = SpfSolver("g1_1").next_hops_toward(
            "g1_1", {"g2_2"}, True, False, None, als, {"0"}
        )
        assert solver.next_hops_toward(
            "g1_1", {"g2_2"}, True, False, None, als, {"0"}
        ) == want
        solver.sync_counters(als)
        assert (solver.counters[TABLE], solver.counters[GENERIC]) == (0, 1)

    @pytest.mark.parametrize("fabric", ["fabric", "grid"])
    def test_a_full_build_looks_the_solve_up_a_constant_number_of_times(
        self, monkeypatch, fabric
    ):
        per_size = []
        for size in (1, 3):
            if fabric == "fabric":
                edges = fabric_edges(
                    2 * size, planes=2, ssw_per_plane=2, fsw_per_pod=2,
                    rsw_per_pod=4 * size,
                )
                me = "rsw0_0"
            else:
                edges, me = grid_edges(3 * size), "g0_0"
            dbs = build_adj_dbs(edges)
            als = {"0": link_state(dbs)}
            ps = prefix_state(every_node_announces(dbs, v6_too=True))
            solver = TpuSpfSolver(me)
            lookups = [0]
            area_solve = solver._area_solve

            def counted(link_state_, node):
                lookups[0] += 1
                return area_solve(link_state_, node)

            monkeypatch.setattr(solver, "_area_solve", counted)
            db, _, _ = DeltaRouteBuilder(solver).build(
                me, als, ps, None, force_full=True
            )
            assert len(db.unicast_entries) == 2 * (len(dbs) - 1)
            table = solver._solves[("0", me)][1].next_hop_table()
            distances = {
                nh.metric
                for entry in db.unicast_entries.values()
                for nh in entry.nexthops
            }
            groups = len(table._groups)
            # one next-hop set per first-hop group, distance and family,
            # however many destinations lie behind it
            assert len(table.unicast_sets) <= groups * len(distances) * 2
            assert len(table.unicast_sets) < len(db.unicast_entries)
            shared = {
                frozenset(entry.nexthops)
                for entry in db.unicast_entries.values()
            }
            assert shared == set(table.unicast_sets.values())
            per_size.append((len(dbs), lookups[0]))
        (small, lookups_small), (large, lookups_large) = per_size
        assert large >= 4 * small
        assert lookups_small == lookups_large == 1

    @pytest.mark.parametrize("supervised", [True, False])
    def test_decision_surfaces_both_counters(self, supervised):
        import asyncio

        from openr_tpu.decision import Decision, DecisionConfig
        from openr_tpu.messaging import ReplicateQueue, RQueue, RWQueue
        from openr_tpu.types import Publication, Value, adj_key, prefix_key
        from openr_tpu.utils import serializer

        async def body():
            kv_q = RWQueue()
            route_q = ReplicateQueue()
            decision = Decision(
                DecisionConfig(
                    my_node_name="a",
                    solver_backend="tpu",
                    solver_supervised=supervised,
                    debounce_min=0.005,
                    debounce_max=0.02,
                ),
                RQueue(kv_q),
                route_q,
            )
            reader = route_q.get_reader()
            decision.start()
            dbs = build_adj_dbs([("a", "b", 1), ("b", "c", 1)])
            pub = Publication(area="0")
            for db in dbs.values():
                pub.key_vals[adj_key(db.this_node_name)] = Value(
                    1, db.this_node_name, serializer.dumps(db)
                )
            pub.key_vals[prefix_key("c")] = Value(
                1, "c", serializer.dumps(
                    PrefixDatabase("c", [PrefixEntry(IpPrefix("10.1.0.0/16"))])
                )
            )
            kv_q.push(pub)
            await asyncio.wait_for(reader.get(), 10)
            # c's prefix, b's and c's labels
            assert decision.counters[TABLE] == 3
            assert decision.counters[GENERIC] == 0
            decision.stop()

        loop = asyncio.new_event_loop()
        try:
            loop.run_until_complete(asyncio.wait_for(body(), 30))
        finally:
            loop.close()
