"""ISSUE 36: a node-label route from the TPU solver's next-hop table holds
what determines its next hops (routes.LabelNextHops) and makes the set when
somebody reads `.nexthops`. The set is, NextHop for NextHop, the one the
table built before; `RibMplsEntry.__eq__` answers what set equality answers
without making a set where both sides hold the form; and with segment
routing off neither the build, nor `get_route_delta`, nor Fib makes one,
while Fib's read API gives the routes it gave before."""

import dataclasses

import pytest

from openr_tpu.fib import get_best_nexthops_mpls
from openr_tpu.platform import FIB_CLIENT_OPENR
from openr_tpu.solver import (
    DeltaRouteBuilder,
    SpfSolver,
    TpuSpfSolver,
    get_route_delta,
)
from openr_tpu.solver.routes import RibMplsEntry
from openr_tpu.topology import build_adj_dbs, grid_edges
from openr_tpu.types import (
    InterfaceDatabase,
    InterfaceInfo,
    MplsAction,
    MplsActionCode,
    MplsRoute,
    NextHop,
)
from test_fib import make_fib, run, wait_until
from test_next_hop_table import (
    SCENARIOS,
    every_node_announces,
    generic_stack,
    link_state,
    prefix_state,
)
from test_route_delta import set_metric

MADE = "decision.route_build_label_sets_made"
PHP = MplsAction(MplsActionCode.PHP)


def made(solver, als):
    solver.sync_counters(als)
    return solver.counters[MADE]


def table_db(scenario):
    dbs, me = SCENARIOS[scenario]()
    als = {"0": link_state(dbs)}
    ps = prefix_state(every_node_announces(dbs))
    solver = TpuSpfSolver(me)
    return dbs, me, als, ps, solver, solver.build_route_db(me, als, ps)


class TestTheSetIsTheOneTheTableBuilt:
    @pytest.mark.parametrize("scenario", sorted(SCENARIOS))
    def test_next_hop_for_next_hop(self, scenario):
        dbs, me, als, ps, solver, db = table_db(scenario)
        oracle = SpfSolver(me).build_route_db(me, als, ps)
        generic = generic_stack(TpuSpfSolver(me)).build_route_db(me, als, ps)
        assert set(db.mpls_entries) == set(oracle.mpls_entries)
        label_of = {db_.node_label: node for node, db_ in dbs.items()}
        # parallel links: one adjacency per interface
        mine = {adj.if_name: adj for adj in dbs[me].adjacencies}
        spf = als["0"].get_spf_result(me)
        others = 0
        for label, entry in db.mpls_entries.items():
            dst = label_of[label]
            if dst == me:
                assert entry._deferred is None
                continue
            others += 1
            # nothing read it yet: the build, and its counters' sync, made none
            assert entry._nexthops is None and entry._deferred is not None
            assert made(solver, als) == others - 1
            nexthops = entry.nexthops
            assert made(solver, als) == others
            assert entry.nexthops is nexthops  # made once, then kept
            assert nexthops == oracle.mpls_entries[label].nexthops
            assert nexthops == generic.mpls_entries[label].nexthops
            assert all(type(nh) is NextHop for nh in nexthops)
            swap = MplsAction(MplsActionCode.SWAP, swap_label=label)
            for nh in nexthops:
                adj = mine[nh.iface]
                assert nh == NextHop(
                    address=adj.nexthop_v6,
                    iface=adj.if_name,
                    metric=spf[dst].metric,
                    mpls_action=PHP if adj.other_node_name == dst else swap,
                    use_non_shortest_route=False,
                    area="0",
                    weight=0,
                    neighbor_node=adj.other_node_name,
                )
        assert others == len(db.mpls_entries) - 1 > 0

    def test_the_generic_stacks_and_the_oracles_entries_hold_plain_sets(self):
        dbs, me, als, ps, _, _ = table_db("grid_from_its_corner")
        for db in (
            generic_stack(TpuSpfSolver(me)).build_route_db(me, als, ps),
            TpuSpfSolver(me, compute_lfa_paths=True).build_route_db(me, als, ps),
            SpfSolver(me).build_route_db(me, als, ps),
        ):
            assert all(
                e._deferred is None and isinstance(e._nexthops, set)
                for e in db.mpls_entries.values()
            )

    def test_an_assigned_set_replaces_the_form(self):
        _, _, als, _, solver, db = table_db("grid_from_its_corner")
        entry = next(e for e in db.mpls_entries.values() if e._deferred)
        other = {NextHop("fe80::9", "if9", 3, PHP)}
        entry.nexthops = other
        assert entry.nexthops is other and entry._deferred is None
        assert entry == RibMplsEntry(entry.label, set(other))
        assert entry.to_mpls_route() == MplsRoute(entry.label, tuple(other))
        assert made(solver, als) == 0
        assert RibMplsEntry(7).nexthops == set()  # the dataclass's default


def _make(form):
    return dataclasses.replace(form, made=[0]).make()


def variants():
    """One table entry of width 2, and what a later build could hold under
    its label: each field moved alone, and the same links built anew."""
    dbs = build_adj_dbs(grid_edges(4))
    als = {"0": link_state(dbs)}
    solver = TpuSpfSolver("g0_0")
    db = solver.build_route_db("g0_0", als, prefix_state({}))
    base = db.mpls_entries[dbs["g1_1"].node_label]._deferred
    assert len(base.links) == 2 and not base.php_neighbors
    tally = base.made

    def form(**moved):
        return dataclasses.replace(base, **moved)

    neighbour = base.links[0][0]
    return tally, {
        "as_built": base,
        "links_built_anew": form(links=tuple(tuple(l) for l in base.links)),
        "metric": form(metric=base.metric + 1),
        "group_narrower": form(links=base.links[:1]),
        "group_other_link": form(
            links=base.links[:1] + (("x",) + base.links[1][1:],)
        ),
        "swap_label": form(swap_label=base.swap_label + 1),
        "php": form(php_neighbors=frozenset({neighbour})),
        "v4": form(is_v4=True),
    }


class TestEqualityAgreesWithSetEquality:
    def test_every_pair_in_every_form(self):
        tally, forms = variants()
        label = forms["as_built"].swap_label
        sets = {name: _make(form) for name, form in forms.items()}
        same = frozenset(("as_built", "links_built_anew"))
        before = tally[0]
        for a in forms:
            for b in forms:
                sets_equal = sets[a] == sets[b]
                assert sets_equal == (a == b or frozenset((a, b)) == same), (a, b)
                assert (
                    RibMplsEntry(label, forms[a]) == RibMplsEntry(label, forms[b])
                ) == sets_equal, (a, b)
                assert tally[0] == before  # no set made for the answer
                for left, right in (
                    (RibMplsEntry(label, forms[a]), RibMplsEntry(label, set(sets[b]))),
                    (RibMplsEntry(label, set(sets[a])), RibMplsEntry(label, forms[b])),
                    (RibMplsEntry(label, set(sets[a])), RibMplsEntry(label, set(sets[b]))),
                ):
                    assert (left == right) == sets_equal, (a, b)
                    assert (left != right) != sets_equal
                before = tally[0]
        # the label is the entry's own
        assert RibMplsEntry(label, forms["as_built"]) != RibMplsEntry(
            label + 1, forms["as_built"]
        )
        assert RibMplsEntry(label, forms["as_built"]) != forms["as_built"]

    def test_a_form_whose_set_was_read_is_still_compared_on_the_form(self):
        tally, forms = variants()
        label = forms["as_built"].swap_label
        read = RibMplsEntry(label, forms["as_built"])
        assert read.nexthops and tally[0] == 1
        assert read == RibMplsEntry(label, forms["links_built_anew"])
        assert read != RibMplsEntry(label, forms["metric"])
        assert tally[0] == 1

    def test_a_table_rebuilt_by_a_cold_solve(self):
        """My own link's metric moves and returns: a cold solve each time,
        the table and its links built anew, and the same routes."""
        dbs = build_adj_dbs(grid_edges(5))
        ls = link_state(dbs)
        als = {"0": ls}
        ps = prefix_state(every_node_announces(dbs))
        solver = TpuSpfSolver("g0_0")
        first = solver.build_route_db("g0_0", als, ps)
        solve = solver._solves[("0", "g0_0")][1]
        table = solve.next_hop_table()
        for metric in (5, 1):
            set_metric(dbs, ls, "g0_0", "g0_1", metric)
            set_metric(dbs, ls, "g0_1", "g0_0", metric)
            moved = solver.build_route_db("g0_0", als, ps)
        assert solve.next_hop_table() is not table
        delta = get_route_delta(moved, first)
        assert delta.empty()
        rebuilt = 0
        for label, entry in moved.mpls_entries.items():
            was = first.mpls_entries[label]
            assert entry == was and was == entry
            if entry._deferred is not None:
                assert entry._deferred.links is not was._deferred.links
                assert entry._deferred.links == was._deferred.links
                rebuilt += 1
        assert rebuilt == len(moved.mpls_entries) - 1
        assert made(solver, als) == 0
        # and against the oracle's plain sets, from either side
        oracle = SpfSolver("g0_0").build_route_db("g0_0", als, ps)
        assert get_route_delta(moved, oracle).empty()
        assert get_route_delta(oracle, first).empty()
        assert made(solver, als) == 2 * rebuilt

    @pytest.mark.parametrize("what", ["metric", "group", "php", "label"])
    def test_a_real_change_is_an_update_and_makes_no_set(self, what):
        """Decision's published update holds the entries the oracle's diff
        holds, full build and DeltaPath alike."""
        dbs = build_adj_dbs(grid_edges(4))
        ls = link_state(dbs)
        als = {"0": ls}
        ps = prefix_state(every_node_announces(dbs))
        solver, oracle = TpuSpfSolver("g0_0"), SpfSolver("g0_0")
        builder = DeltaRouteBuilder(solver)
        db, _, _ = builder.build("g0_0", als, ps, None, force_full=True)
        oracle_db = oracle.build_route_db("g0_0", als, ps)
        if what == "metric":  # a far link: the routes behind it, further
            for a, b in (("g3_2", "g3_3"), ("g2_3", "g3_3")):
                set_metric(dbs, ls, a, b, 4)
                set_metric(dbs, ls, b, a, 4)
        elif what == "group":  # row 0 dearer: g0_2 and g0_3 lose a first hop
            set_metric(dbs, ls, "g0_1", "g0_2", 9)
            set_metric(dbs, ls, "g0_2", "g0_1", 9)
        elif what == "php":  # g0_1 direct no more: SWAP round g1_0, no PHP
            set_metric(dbs, ls, "g0_0", "g0_1", 9)
            set_metric(dbs, ls, "g0_1", "g0_0", 9)
        else:  # g2_2 takes a new label: one route goes, one comes
            dbs["g2_2"] = dataclasses.replace(dbs["g2_2"], node_label=60000)
            ls.update_adjacency_database(dbs["g2_2"])
        new_db, update, _ = builder.build(
            "g0_0", als, ps, db, force_full=what == "label"
        )
        want = get_route_delta(oracle.build_route_db("g0_0", als, ps), oracle_db)
        assert want.mpls_routes_to_update
        assert made(solver, als) == 0
        assert sorted(update.mpls_routes_to_delete) == sorted(
            want.mpls_routes_to_delete
        )
        assert sorted(e.label for e in update.mpls_routes_to_update) == sorted(
            e.label for e in want.mpls_routes_to_update
        )
        by_label = {e.label: e for e in want.mpls_routes_to_update}
        for entry in update.mpls_routes_to_update:
            assert entry.nexthops == by_label[entry.label].nexthops
        if what == "php":
            g0_1 = dbs["g0_1"].node_label
            assert {nh.mpls_action for nh in db.mpls_entries[g0_1].nexthops} == {PHP}
            assert {nh.mpls_action for nh in new_db.mpls_entries[g0_1].nexthops} == {
                MplsAction(MplsActionCode.SWAP, swap_label=g0_1)
            }


class TestFibReadsWhatItPrograms:
    def _through_fib(self, segment_routing, then=None):
        """A full build, its diff, and Fib's process_route_updates; a
        second event (a far metric moves, a label goes) the same way; and
        `then(fib, out)` on the same loop before Fib stops."""
        dbs = build_adj_dbs(grid_edges(4))
        ls = link_state(dbs)
        als = {"0": ls}
        ps = prefix_state(every_node_announces(dbs))
        solver, oracle = TpuSpfSolver("g0_0"), SpfSolver("g0_0")
        out = {"solver": solver, "als": als}

        async def body():
            fib, handler, route_q, _ = make_fib(
                enable_segment_routing=segment_routing
            )
            fib.start()
            await handler.wait_for_sync_fib()
            db = solver.build_route_db("g0_0", als, ps)
            route_q.push(get_route_delta(db, type(db)()))
            await wait_until(lambda: fib.counters.get("fib.num_of_route_updates"))
            out["made_after_first"] = made(solver, als)
            out["first_oracle"] = oracle.build_route_db("g0_0", als, ps)
            out["agent_first"] = dict(handler.mpls_routes.get(FIB_CLIENT_OPENR, {}))
            for a, b in (("g3_2", "g3_3"), ("g2_3", "g3_3")):
                set_metric(dbs, ls, a, b, 4)
                set_metric(dbs, ls, b, a, 4)
            dbs["g1_2"] = dataclasses.replace(dbs["g1_2"], node_label=0)
            ls.update_adjacency_database(dbs["g1_2"])
            second = solver.build_route_db("g0_0", als, ps)
            update = get_route_delta(second, db)
            assert update.mpls_routes_to_update and update.mpls_routes_to_delete
            programmed = fib.counters["fib.num_of_route_updates"]
            route_q.push(update)
            await wait_until(
                lambda: fib.counters["fib.num_of_route_updates"] > programmed
            )
            out.update(
                fib=fib, handler=handler, update=update,
                oracle=oracle.build_route_db("g0_0", als, ps),
                made=made(solver, als),
                table_labels=len(db.mpls_entries) - 1,
            )
            if then is not None:
                await then(fib, out)
            fib.stop()

        run(body())
        return out

    def test_segment_routing_off_makes_no_set_and_programs_no_label(self):
        out = self._through_fib(segment_routing=False)
        assert out["made_after_first"] == out["made"] == 0
        assert not out["handler"].mpls_routes.get(FIB_CLIENT_OPENR)
        assert out["handler"].counters.get("add_mpls_routes", 0) == 0
        fib = out["fib"]
        fib.update_global_counters()
        assert fib.counters["fib.num_mpls_routes"] == len(out["oracle"].mpls_entries)
        assert made(out["solver"], out["als"]) == 0  # counting reads nothing

    def test_segment_routing_on_makes_every_set_and_programs_the_same_routes(self):
        out = self._through_fib(segment_routing=True)
        # my own label's POP_AND_LOOKUP is a plain set
        assert out["made_after_first"] == out["table_labels"]
        moved = sum(
            1 for e in out["update"].mpls_routes_to_update if e._deferred
        )
        assert out["made"] == out["table_labels"] + moved and moved > 0

        def as_programmed(db):
            return {
                label: MplsRoute(label, tuple(get_best_nexthops_mpls(
                    list(entry.to_mpls_route().nexthops)
                )))
                for label, entry in db.mpls_entries.items()
            }

        assert out["agent_first"] == as_programmed(out["first_oracle"])
        assert out["handler"].mpls_routes[FIB_CLIENT_OPENR] == as_programmed(
            out["oracle"]
        )

    @pytest.mark.parametrize("segment_routing", [False, True], ids=["off", "on"])
    def test_the_read_api_gives_the_routes_in_the_order_they_came(
        self, segment_routing
    ):
        out = self._through_fib(segment_routing)
        fib, oracle = out["fib"], out["oracle"]
        # the order a dict of converted routes would have: first arrival,
        # a delete takes the label out
        gone = set(out["update"].mpls_routes_to_delete)
        order = [l for l in out["first_oracle"].mpls_entries if l not in gone]
        want = [oracle.mpls_entries[label].to_mpls_route() for label in order]
        assert set(order) == set(oracle.mpls_entries)
        assert fib.get_mpls_routes() == want
        assert fib.get_route_db()["mpls_routes"] == want
        assert list(fib.route_state.mpls_routes.values()) == want
        some = order[1:4]
        assert fib.get_mpls_routes(some) == [
            oracle.mpls_entries[label].to_mpls_route() for label in some
        ]
        if not segment_routing:
            # the reader paid for what it read, once
            assert made(out["solver"], out["als"]) == len(order) - 1
            fib.get_mpls_routes()
            assert made(out["solver"], out["als"]) == len(order) - 1

    @pytest.mark.parametrize("segment_routing", [False, True], ids=["off", "on"])
    def test_an_interface_event_and_a_full_sync_see_the_label_routes(
        self, segment_routing
    ):
        """process_interface_db shrinks a label route's group whether or
        not segment routing programs it; sync_route_db pushes the routes
        where it is on, and reads none where it is off."""

        async def then(fib, out):
            wide = next(
                entry.to_mpls_route()
                for entry in out["oracle"].mpls_entries.values()
                if len(entry.nexthops) > 1
            )
            made_before = made(out["solver"], out["als"])
            await fib.process_interface_db(InterfaceDatabase(
                "g0_0", {wide.nexthops[0].iface: InterfaceInfo(is_up=False)}
            ))
            out["dirty"] = set(fib.route_state.dirty_labels)
            out["wide"] = wide.top_label
            out["made_by_the_interface_event"] = (
                made(out["solver"], out["als"]) - made_before
            )
            assert await fib.sync_route_db()
            out["made_by_the_sync"] = (
                made(out["solver"], out["als"])
                - made_before
                - out["made_by_the_interface_event"]
            )

        out = self._through_fib(segment_routing, then)
        assert out["wide"] in out["dirty"]
        synced = out["handler"].mpls_routes.get(FIB_CLIENT_OPENR, {})
        assert out["made_by_the_sync"] == 0
        if segment_routing:
            assert set(synced) == set(out["oracle"].mpls_entries)
            assert out["made_by_the_interface_event"] == 0  # all read before
        else:
            assert not synced
            # it reads every label route; those of the table make their sets
            assert out["made_by_the_interface_event"] == len(
                out["oracle"].mpls_entries
            ) - 1
