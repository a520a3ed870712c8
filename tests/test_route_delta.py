"""DeltaPath (device-side route-delta extraction) differential suite.

The O(changes) partial route rebuild (solver/delta.py) must be
byte-identical to the classic full-mirror rebuild on every event class:
randomized flap sequences (metric decrease/increase, adjacency flap,
node-overload toggle), partitions, and `_PATCH_SLOTS` overflow — and the
warm single-link event must copy back O(changes) bytes, never the full
[s_pad, n_pad] mirror (the ISSUE 6 transfer-budget acceptance criterion).
"""

import dataclasses
import random

import numpy as np
import pytest

from openr_tpu.lsdb import LinkState, PrefixState
from openr_tpu.solver import (
    DecisionRouteDb,
    DecisionRouteUpdate,
    DeltaRouteBuilder,
    SolverSupervisor,
    SpfSolver,
    SupervisorConfig,
    TpuSpfSolver,
    apply_route_delta,
    get_route_delta,
)
from openr_tpu.solver.supervisor import OPEN
from openr_tpu.topology import build_adj_dbs, fabric_edges, grid_edges
from openr_tpu.types import IpPrefix, PrefixDatabase, PrefixEntry


def build_ls(edges, area="0", **kwargs):
    ls = LinkState(area)
    for db in build_adj_dbs(edges, area=area, **kwargs).values():
        ls.update_adjacency_database(db)
    return ls


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


def assert_route_db_equal(db_a, db_b):
    assert db_a is not None and db_b is not None
    assert set(db_a.unicast_entries) == set(db_b.unicast_entries)
    for prefix, entry in db_a.unicast_entries.items():
        assert db_b.unicast_entries[prefix] == entry, prefix
    assert set(db_a.mpls_entries) == set(db_b.mpls_entries)
    for label, entry in db_a.mpls_entries.items():
        assert db_b.mpls_entries[label] == entry, label


def apply_weight_event(rng, dbs, ls, links):
    """One randomized weight-only LSDB event (the classes the delta path
    serves or must correctly refuse): adjacency flap via overload, metric
    change, or node-overload toggle. Mutates dbs and ls."""
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
        dbs[a] = dataclasses.replace(db, adjacencies=new_adjs)
        ls.update_adjacency_database(dbs[a])
    else:
        node = sorted(dbs)[rng.randrange(len(dbs))]
        dbs[node] = dataclasses.replace(
            dbs[node], is_overloaded=not dbs[node].is_overloaded
        )
        ls.update_adjacency_database(dbs[node])
    return kind


def set_metric(dbs, ls, a, b, metric):
    """Set the directed metric of a's adjacency toward b."""
    dbs[a] = dataclasses.replace(
        dbs[a],
        adjacencies=[
            dataclasses.replace(adj, metric=metric)
            if adj.other_node_name == b
            else adj
            for adj in dbs[a].adjacencies
        ],
    )
    ls.update_adjacency_database(dbs[a])


def set_adj_overload(dbs, ls, a, b, overloaded):
    dbs[a] = dataclasses.replace(
        dbs[a],
        adjacencies=[
            dataclasses.replace(adj, is_overloaded=overloaded)
            if adj.other_node_name == b
            else adj
            for adj in dbs[a].adjacencies
        ],
    )
    ls.update_adjacency_database(dbs[a])


class DeltaHarness:
    """TpuSpfSolver + DeltaRouteBuilder over a mutable LSDB, checked
    against a cold full rebuild after every step."""

    def __init__(self, edges, me, announcers, solver_kwargs=None, **entry_kw):
        self.me = me
        self.solver_kwargs = dict(solver_kwargs or {})
        self.dbs = build_adj_dbs(edges)
        self.ls = LinkState("0")
        for db in self.dbs.values():
            self.ls.update_adjacency_database(db)
        self.ps = make_prefix_state(announcers, **entry_kw)
        self.solver = TpuSpfSolver(me, **self.solver_kwargs)
        self.builder = DeltaRouteBuilder(self.solver)
        self.als = {"0": self.ls}
        self.db, _, used = self.builder.build(
            me, self.als, self.ps, None, force_full=True
        )
        assert not used  # first build is always full
        assert self.db is not None

    def step(self, dirty_prefixes=frozenset(), force_full=False):
        """One rebuild; asserts the result — delta-built or not — equals a
        from-scratch full rebuild of the same LSDB, and that the emitted
        update folds the previous db into the new one. Returns used_delta."""
        prev = self.db
        new_db, update, used = self.builder.build(
            self.me,
            self.als,
            self.ps,
            prev,
            dirty_prefixes=dirty_prefixes,
            force_full=force_full,
        )
        ref = TpuSpfSolver(self.me, **self.solver_kwargs).build_route_db(
            self.me, self.als, self.ps
        )
        assert_route_db_equal(ref, new_db)
        cpu_kwargs = {
            k: v
            for k, v in self.solver_kwargs.items()
            if not k.startswith("apsp")
        }
        oracle = SpfSolver(self.me, **cpu_kwargs).build_route_db(
            self.me, self.als, self.ps
        )
        assert_route_db_equal(oracle, new_db)
        folded = apply_route_delta(prev, update)
        assert_route_db_equal(new_db, folded)
        self.db = new_db
        return used


PFXS = ["10.1.0.0/16", "10.2.0.0/16", "10.3.0.0/16", "10.4.0.0/16"]


class TestDeltaDifferential:
    """Randomized flap sequences: the delta-built RouteDatabase must stay
    identical to the full-mirror rebuild (TPU) and the CPU oracle."""

    def test_grid_random_sequences(self):
        for seed in (5, 23):
            h = DeltaHarness(
                grid_edges(4),
                "g0_0",
                {
                    "g3_3": [PFXS[0]],
                    "g0_3": [PFXS[1]],
                    "g2_1": [PFXS[2]],
                    "g1_2": [PFXS[3]],
                },
            )
            rng = random.Random(seed)
            links = list(grid_edges(4))
            applied = 0
            for _ in range(14):
                before = h.ls.version
                apply_weight_event(rng, h.dbs, h.ls, links)
                if h.ls.version == before:
                    continue
                h.step()
                applied += 1
            assert applied > 0
            # the sequences mix qualifying and disqualifying events: both
            # paths must have served
            assert h.builder.delta_builds > 0
            assert h.builder.full_builds > 1

    def test_clos_random_sequence(self):
        edges = fabric_edges(
            pods=2, planes=2, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=3
        )
        h = DeltaHarness(
            edges, "rsw0_0", {"rsw1_2": [PFXS[0]], "rsw0_2": [PFXS[1]]}
        )
        rng = random.Random(17)
        links = list(edges)
        for _ in range(10):
            before = h.ls.version
            apply_weight_event(rng, h.dbs, h.ls, links)
            if h.ls.version == before:
                continue
            h.step()
        assert h.builder.delta_builds > 0

    def test_batched_events_accumulate_columns(self):
        # several qualifying events between rebuilds: the accumulated
        # changed-column set must describe the union
        h = DeltaHarness(
            grid_edges(4), "g0_0", {"g3_3": [PFXS[0]], "g0_3": [PFXS[1]]}
        )
        set_metric(h.dbs, h.ls, "g3_2", "g3_3", 7)
        h.solver.poll_device_delta(h.als)  # solve event 1, delta pends
        set_metric(h.dbs, h.ls, "g2_3", "g3_3", 7)
        set_metric(h.dbs, h.ls, "g0_2", "g0_3", 5)
        assert h.step() is True
        assert h.builder.delta_builds == 1

    def test_increase_then_decrease_same_link(self):
        h = DeltaHarness(
            [("a", "b", 1), ("b", "c", 1), ("c", "d", 1), ("a", "d", 9)],
            "a",
            {"d": [PFXS[0]], "c": [PFXS[1]]},
        )
        used = []
        for metric in (8, 1):  # invalidation pass, then warm decrease
            set_metric(h.dbs, h.ls, "b", "c", metric)
            used.append(h.step())
        assert used == [True, True]

    def test_partition_flap_and_heal_deletes_and_restores(self):
        edges = [
            ("a", "b", 1), ("b", "c", 1), ("c", "a", 1),
            ("c", "x", 2),  # bridge
            ("x", "y", 1), ("y", "z", 1), ("z", "x", 1),
        ]
        h = DeltaHarness(edges, "a", {"z": [PFXS[0]], "b": [PFXS[1]]})
        far = IpPrefix(PFXS[0])
        assert far in h.db.unicast_entries
        # both directions of the bridge go down: far side unreachable
        set_adj_overload(h.dbs, h.ls, "c", "x", True)
        set_adj_overload(h.dbs, h.ls, "x", "c", True)
        assert h.step() is True  # remote flap rides the delta path
        assert far not in h.db.unicast_entries
        assert IpPrefix(PFXS[1]) in h.db.unicast_entries
        set_adj_overload(h.dbs, h.ls, "c", "x", False)
        set_adj_overload(h.dbs, h.ls, "x", "c", False)
        assert h.step() is True
        assert far in h.db.unicast_entries

    def test_node_overload_toggle_takes_full_path(self):
        # a transit-mask change cannot be described by changed D columns
        # alone: the solver must refuse the delta and the full path serves
        h = DeltaHarness(
            grid_edges(3), "g0_0", {"g2_2": [PFXS[0]], "g0_2": [PFXS[1]]}
        )
        for overloaded in (True, False):
            h.dbs["g1_1"] = dataclasses.replace(
                h.dbs["g1_1"], is_overloaded=overloaded
            )
            h.ls.update_adjacency_database(h.dbs["g1_1"])
            assert h.step() is False
        assert h.builder.delta_builds == 0

    def test_event_incident_to_me_takes_full_path(self):
        # my own out-link metric is a route input no distance column
        # reflects (the nexthop triangle's weight column)
        h = DeltaHarness(
            grid_edges(3), "g0_0", {"g2_2": [PFXS[0]]}
        )
        set_metric(h.dbs, h.ls, "g0_0", "g0_1", 4)
        assert h.step() is False

    def test_patch_slots_overflow_takes_full_path(self, monkeypatch):
        import openr_tpu.solver.tpu as tpu_mod

        monkeypatch.setattr(tpu_mod, "_PATCH_SLOTS", 0)
        h = DeltaHarness(
            [("a", "b", 1), ("b", "c", 1), ("c", "d", 1)],
            "a",
            {"d": [PFXS[0]]},
        )
        set_metric(h.dbs, h.ls, "b", "c", 6)  # overflows the 0-slot budget
        assert h.step() is False
        assert h.builder.delta_builds == 0

    def test_prefix_advertisement_change_rides_dirty_set(self):
        # a prefix event with no topology change: Decision feeds the dirty
        # prefixes explicitly; no solve delta pends, but the partial path
        # still serves it (changed_nodes is empty, not None)
        h = DeltaHarness(
            grid_edges(3), "g0_0", {"g2_2": [PFXS[0]]}
        )
        dirty = h.ps.update_prefix_database(
            PrefixDatabase(
                "g0_2", [PrefixEntry(IpPrefix(PFXS[1]))], area="0"
            )
        )
        assert dirty
        assert h.step(dirty_prefixes=dirty) is True
        assert IpPrefix(PFXS[1]) in h.db.unicast_entries
        # withdrawal deletes through the same path
        dirty = h.ps.update_prefix_database(
            PrefixDatabase("g0_2", [], area="0")
        )
        assert h.step(dirty_prefixes=dirty) is True
        assert IpPrefix(PFXS[1]) not in h.db.unicast_entries

    def test_force_full_drains_pending_delta(self):
        # a forced-full rebuild must consume the accumulated delta so a
        # stale column set never rides into a later event
        h = DeltaHarness(grid_edges(3), "g0_0", {"g2_2": [PFXS[0]]})
        set_metric(h.dbs, h.ls, "g1_2", "g2_2", 8)
        assert h.step(force_full=True) is False
        set_metric(h.dbs, h.ls, "g1_2", "g2_2", 1)
        assert h.step() is True  # re-armed, next event is delta-served


class TestTransferBudget:
    """ISSUE 6 acceptance: a warm single-link-flap event transfers
    O(changes) host<->device bytes — bounded by the changed columns'
    compaction bucket, never by n_pad."""

    def test_single_link_warm_event_d2h_is_o_changes(self):
        from openr_tpu.ops.graph import _next_bucket

        side = 12  # 144 nodes
        h = DeltaHarness(
            grid_edges(side),
            "g0_0",
            {f"g{side - 1}_{side - 1}": [PFXS[0]]},
        )
        solve = h.solver._solves[("0", "g0_0")][1]
        s_pad, n_pad = solve.d.shape
        full_mirror_bytes = s_pad * n_pad * 4
        d2h_before = solve.d2h_bytes
        extracts_before = solve.delta_extracts
        cols_before = solve.delta_columns
        # bump both far-corner in-edges (one leaves the other ECMP leg
        # equal-cost, changing nothing): exactly one column moves
        corner = f"g{side - 1}_{side - 1}"
        set_metric(h.dbs, h.ls, f"g{side - 2}_{side - 1}", corner, 9)
        set_metric(h.dbs, h.ls, f"g{side - 1}_{side - 2}", corner, 9)
        assert h.step() is True
        assert solve.delta_extracts == extracts_before + 1
        xfer = solve.d2h_bytes - d2h_before
        # the whole event's copy-back (count scalar + compacted columns +
        # nexthop rows) fits the bucket bound and is far below the mirror
        num = solve.delta_columns - cols_before
        cap = _next_bucket(num, minimum=8)
        l_pad = _next_bucket(
            max(len(solve._nh_link_arrays()[0]), 1), minimum=8
        )
        assert num < n_pad // 4
        assert xfer <= 4 + cap * (4 + 4 * s_pad + l_pad)
        assert xfer < full_mirror_bytes // 4
        # and the route build consumed the patched mirror: no full fetch
        assert solve.d2h_bytes - d2h_before == xfer

    def test_patched_mirror_matches_cold_fetch(self):
        h = DeltaHarness(
            grid_edges(6), "g0_0", {"g5_5": [PFXS[0]], "g0_5": [PFXS[1]]}
        )
        set_metric(h.dbs, h.ls, "g4_5", "g5_5", 7)
        assert h.step() is True
        warm = h.solver._solves[("0", "g0_0")][1]
        cold = TpuSpfSolver("g0_0")
        cold.build_route_db("g0_0", h.als, h.ps)
        cold_solve = cold._solves[("0", "g0_0")][1]
        np.testing.assert_array_equal(warm.d, cold_solve.d)


class TestApplyRouteDelta:
    def test_apply_is_diff_inverse(self):
        me, announcers = "g0_0", {
            "g2_2": [PFXS[0]], "g0_2": [PFXS[1]], "g1_1": [PFXS[2]]
        }
        ls_old = build_ls(grid_edges(3))
        old = SpfSolver(me).build_route_db(
            me, {"0": ls_old}, make_prefix_state(announcers)
        )
        edges_new = [
            (a, b, 9 if (a, b) == ("g1_2", "g2_2") else w)
            for a, b, w in grid_edges(3)
        ]
        new = SpfSolver(me).build_route_db(
            me,
            {"0": build_ls(edges_new)},
            make_prefix_state({"g2_2": [PFXS[0]], "g1_1": [PFXS[2]]}),
        )
        folded = apply_route_delta(old, get_route_delta(new, old))
        assert_route_db_equal(new, folded)
        assert get_route_delta(folded, new).empty()

    def test_unchanged_entries_are_shared(self):
        old = DecisionRouteDb()
        new = apply_route_delta(old, DecisionRouteUpdate())
        assert new.unicast_entries == {} and new.mpls_entries == {}


class TestSupervisorDeltaFaultDomain:
    """Breaker trips and shadow audits must force the full path."""

    def _inputs(self):
        edges = grid_edges(3)
        ls = build_ls(edges)
        ps = make_prefix_state({"g2_2": [PFXS[0]], "g0_2": [PFXS[1]]})
        return "g0_0", {"0": ls}, ps

    def test_poll_gated_while_breaker_open(self):
        me, als, ps = self._inputs()
        sup = SolverSupervisor(
            TpuSpfSolver(me), SpfSolver(me), SupervisorConfig()
        )
        sup.build_route_db(me, als, ps)
        sup.state = OPEN
        assert sup.poll_device_delta(als) is None

    def test_poll_fault_classified_and_degrades(self):
        me, als, ps = self._inputs()
        sup = SolverSupervisor(
            TpuSpfSolver(me), SpfSolver(me), SupervisorConfig()
        )
        sup.build_route_db(me, als, ps)

        def boom(_als):
            raise RuntimeError("DEVICE_LOST: chip went away")

        sup.primary.poll_device_delta = boom
        assert sup.poll_device_delta(als) is None
        assert sup.counters["decision.spf.solver_failures.device_loss"] == 1

    def test_verify_route_delta_self_heals_mismatch(self):
        me, als, ps = self._inputs()
        samples = []
        sup = SolverSupervisor(
            TpuSpfSolver(me),
            SpfSolver(me),
            SupervisorConfig(audit_interval=1),
            log_sample_fn=samples.append,
        )
        full = sup.build_route_db(me, als, ps)
        corrupted = DecisionRouteDb(
            unicast_entries=dict(
                list(full.unicast_entries.items())[:-1]  # drop one route
            ),
            mpls_entries=dict(full.mpls_entries),
        )
        corrected = sup.verify_route_delta(corrupted, me, als, ps)
        assert corrected is not None
        assert_route_db_equal(full, corrected)
        assert sup.counters["decision.spf.delta_audit_mismatches"] == 1
        assert any(
            s.get("event") == "ROUTE_DELTA_AUDIT_MISMATCH" for s in samples
        )

    def test_verify_route_delta_clean_db_passes(self):
        me, als, ps = self._inputs()
        sup = SolverSupervisor(
            TpuSpfSolver(me),
            SpfSolver(me),
            SupervisorConfig(audit_interval=1),
        )
        full = sup.build_route_db(me, als, ps)
        assert sup.verify_route_delta(full, me, als, ps) is None
        assert sup.counters["decision.spf.delta_audit_runs"] == 1
        assert "decision.spf.delta_audit_mismatches" not in sup.counters


class TestAdjacencyToMeQualification:
    """Unit suite for the narrowed direct-neighbor refusal (ISSUE 7): a
    neighbor's update forces the full path only when its adjacencies TO ME
    actually changed — far-side-only updates stay delta-eligible."""

    @staticmethod
    def db(node, adjs):
        from openr_tpu.types import AdjacencyDatabase

        return AdjacencyDatabase(this_node_name=node, adjacencies=adjs)

    @staticmethod
    def adj(other, **kw):
        from openr_tpu.types import Adjacency

        return Adjacency(
            other_node_name=other, if_name=f"if-b-{other}", **kw
        )

    def check(self, prior_adjs, new_adjs):
        from openr_tpu.decision.decision import _adjacencies_to_me_changed

        prior = self.db("b", prior_adjs) if prior_adjs is not None else None
        return _adjacencies_to_me_changed(prior, self.db("b", new_adjs), "a")

    def test_far_side_only_change_does_not_force_full(self):
        before = [self.adj("a", metric=1), self.adj("c", metric=1)]
        after = [self.adj("a", metric=1), self.adj("c", metric=7)]
        assert self.check(before, after) is False

    def test_metric_to_me_forces_full(self):
        before = [self.adj("a", metric=1), self.adj("c", metric=1)]
        after = [self.adj("a", metric=4), self.adj("c", metric=1)]
        assert self.check(before, after) is True

    def test_overload_and_nexthop_to_me_force_full(self):
        before = [self.adj("a", metric=1)]
        assert self.check(
            before, [self.adj("a", metric=1, is_overloaded=True)]
        ) is True
        assert self.check(
            before, [self.adj("a", metric=1, nexthop_v6="fe80::b")]
        ) is True

    def test_adjacency_to_me_added_or_removed_forces_full(self):
        assert self.check([self.adj("c")], [self.adj("c"), self.adj("a")])
        assert self.check([self.adj("c"), self.adj("a")], [self.adj("c")])

    def test_first_advertisement_with_adj_to_me_is_structural(self):
        assert self.check(None, [self.adj("a")]) is True

    def test_first_advertisement_without_adj_to_me_is_not(self):
        assert self.check(None, [self.adj("c")]) is False

    def test_rtt_timestamp_churn_is_ignored(self):
        # fields the route build never consumes must not poison the delta
        before = [self.adj("a", rtt=100, timestamp=1), self.adj("c")]
        after = [self.adj("a", rtt=900, timestamp=2), self.adj("c")]
        assert self.check(before, after) is False


class TestDecisionDeltaPath:
    """End to end through Decision: a qualifying remote flap must be served
    by the delta route build and emit the same update the full path would."""

    @pytest.mark.parametrize("event", ["metric", "withdrawn"])
    def test_remote_metric_flap_uses_delta_build(self, event):
        """A remote metric bump, or a remote adjacency that leaves the
        LSDB (a patch of the slots the compiled graph keeps for it)."""
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
                    debounce_min=0.005,
                    debounce_max=0.02,
                ),
                RQueue(kv_q),
                route_q,
            )
            reader = route_q.get_reader()
            decision.start()
            edges = [("a", "b", 1), ("b", "c", 1), ("c", "d", 1), ("d", "e", 1)]
            dbs = build_adj_dbs(edges)
            pub = Publication(area="0")
            for db in dbs.values():
                pub.key_vals[adj_key(db.this_node_name)] = Value(
                    1, db.this_node_name, serializer.dumps(db)
                )
            pub.key_vals[prefix_key("e")] = Value(
                1, "e", serializer.dumps(
                    PrefixDatabase("e", [PrefixEntry(IpPrefix(PFXS[0]))])
                )
            )
            kv_q.push(pub)
            await asyncio.wait_for(reader.get(), 10)
            assert decision.counters.get(
                "decision.route_build_delta_runs", 0
            ) == 0  # first build is full
            # remote event on c->d — c is not adjacent to me, so the
            # batch qualifies at the Decision layer too
            dbs["c"] = dataclasses.replace(
                dbs["c"],
                adjacencies=[
                    dataclasses.replace(adj, metric=5)
                    if adj.other_node_name == "d"
                    else adj
                    for adj in dbs["c"].adjacencies
                    if adj.other_node_name != "d" or event == "metric"
                ],
            )
            pub2 = Publication(area="0")
            pub2.key_vals[adj_key("c")] = Value(
                2, "c", serializer.dumps(dbs["c"])
            )
            kv_q.push(pub2)
            delta = await asyncio.wait_for(reader.get(), 10)
            assert decision.counters["decision.route_build_delta_runs"] == 1
            assert decision.counters["decision.spf.graph_recompiles"] == 0
            if event == "metric":
                routes = {e.prefix: e for e in delta.unicast_routes_to_update}
                assert IpPrefix(PFXS[0]) in routes
                entry = routes[IpPrefix(PFXS[0])]
                assert {nh.metric for nh in entry.nexthops} == {8}
                assert decision.counters["decision.spf.graph_links_patched"] == 0
            else:  # e is cut off: its route goes, by the delta build
                assert delta.unicast_routes_to_delete == [IpPrefix(PFXS[0])]
                assert decision.counters["decision.spf.graph_links_patched"] == 1
            # the maintained route_db matches a from-scratch oracle build
            ls = LinkState("0")
            for db in dbs.values():
                ls.update_adjacency_database(db)
            oracle = SpfSolver("a").build_route_db(
                "a", {"0": ls}, decision.prefix_state
            )
            assert_route_db_equal(oracle, decision.route_db)
            decision.stop()

        loop = asyncio.new_event_loop()
        try:
            loop.run_until_complete(asyncio.wait_for(body(), 30))
        finally:
            loop.close()

    def test_neighbor_far_side_change_stays_on_delta_path(self):
        """The narrowed refusal (ISSUE 7 satellite): my direct neighbor b
        re-advertises, but only its FAR-side link b->c changed — the
        adjacency to me is byte-identical. Decision used to force a full
        rebuild for any update containing an adjacency to me; it must now
        stay on the delta path and still match the from-scratch oracle.
        A follow-up update that touches b's adjacency TO me must still
        take the full path."""
        import asyncio

        from openr_tpu.decision import Decision, DecisionConfig
        from openr_tpu.messaging import ReplicateQueue, RQueue, RWQueue
        from openr_tpu.types import Publication, Value, adj_key, prefix_key
        from openr_tpu.utils import serializer

        def bump(dbs, node, metrics, version):
            dbs[node] = dataclasses.replace(
                dbs[node],
                adjacencies=[
                    dataclasses.replace(
                        adj, metric=metrics.get(adj.other_node_name,
                                                adj.metric)
                    )
                    for adj in dbs[node].adjacencies
                ],
            )
            pub = Publication(area="0")
            pub.key_vals[adj_key(node)] = Value(
                version, node, serializer.dumps(dbs[node])
            )
            return pub

        async def body():
            kv_q = RWQueue()
            route_q = ReplicateQueue()
            decision = Decision(
                DecisionConfig(
                    my_node_name="a",
                    solver_backend="tpu",
                    debounce_min=0.005,
                    debounce_max=0.02,
                ),
                RQueue(kv_q),
                route_q,
            )
            reader = route_q.get_reader()
            decision.start()
            edges = [("a", "b", 1), ("b", "c", 1), ("c", "d", 1)]
            dbs = build_adj_dbs(edges)
            pub = Publication(area="0")
            for db in dbs.values():
                pub.key_vals[adj_key(db.this_node_name)] = Value(
                    1, db.this_node_name, serializer.dumps(db)
                )
            pub.key_vals[prefix_key("d")] = Value(
                1, "d", serializer.dumps(
                    PrefixDatabase("d", [PrefixEntry(IpPrefix(PFXS[0]))])
                )
            )
            kv_q.push(pub)
            await asyncio.wait_for(reader.get(), 10)

            def oracle():
                ls = LinkState("0")
                for db in dbs.values():
                    ls.update_adjacency_database(db)
                return SpfSolver("a").build_route_db(
                    "a", {"0": ls}, decision.prefix_state
                )

            # b is MY neighbor; only its far-side link b->c changes
            kv_q.push(bump(dbs, "b", {"c": 5}, 2))
            delta = await asyncio.wait_for(reader.get(), 10)
            assert decision.counters["decision.route_build_delta_runs"] == 1
            routes = {e.prefix: e for e in delta.unicast_routes_to_update}
            assert {nh.metric for nh in routes[IpPrefix(PFXS[0])].nexthops} \
                == {7}
            assert_route_db_equal(oracle(), decision.route_db)

            # the same batch shape, but b also touches its adjacency TO
            # me: the narrowed qualification must still refuse the delta
            # (route-affecting far-side change rides along so an update
            # is emitted either way)
            kv_q.push(bump(dbs, "b", {"a": 3, "c": 2}, 3))
            await asyncio.wait_for(reader.get(), 10)
            assert decision.counters["decision.route_build_delta_runs"] == 1
            assert_route_db_equal(oracle(), decision.route_db)
            decision.stop()

        loop = asyncio.new_event_loop()
        try:
            loop.run_until_complete(asyncio.wait_for(body(), 30))
        finally:
            loop.close()


class TestDeltaUnderLfa:
    """DeltaPath with `compute_lfa_paths` on (the ISSUE 12 carry-over):
    with an APSP-capable solver the builder no longer force-disables — the
    RFC 5286 inequality's only input beyond the announcer columns is the
    ME column, which the solver poisons via poll_device_delta; randomized
    sequences must stay byte-identical to the full rebuild and the CPU
    oracle on both paths."""

    LFA_KW = {"compute_lfa_paths": True, "apsp_max_nodes": 4096}

    def test_grid_random_sequences_with_lfa(self):
        for seed in (5, 23, 41):
            h = DeltaHarness(
                grid_edges(4),
                "g0_0",
                {
                    "g3_3": [PFXS[0]],
                    "g0_3": [PFXS[1]],
                    "g2_1": [PFXS[2]],
                    "g1_2": [PFXS[3]],
                },
                solver_kwargs=self.LFA_KW,
            )
            rng = random.Random(seed)
            links = list(grid_edges(4))
            for _ in range(14):
                before = h.ls.version
                apply_weight_event(rng, h.dbs, h.ls, links)
                if h.ls.version == before:
                    continue
                h.step()
            # the delta path must have actually served under LFA — the
            # historical behavior was an unconditional force-full
            assert h.builder.delta_builds > 0, seed
            assert h.builder.full_builds > 1, seed

    def test_clos_random_sequence_with_lfa(self):
        edges = fabric_edges(
            pods=2, planes=2, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=3
        )
        h = DeltaHarness(
            edges,
            "rsw0_0",
            {"rsw1_2": [PFXS[0]], "rsw0_2": [PFXS[1]]},
            solver_kwargs=self.LFA_KW,
        )
        rng = random.Random(17)
        links = list(edges)
        for _ in range(10):
            before = h.ls.version
            apply_weight_event(rng, h.dbs, h.ls, links)
            if h.ls.version == before:
                continue
            h.step()
        assert h.builder.delta_builds > 0

    def test_me_column_change_forces_full_under_lfa(self):
        # dist(neighbor, me) feeds EVERY destination's LFA threshold: an
        # event that moves the me column must refuse the delta even though
        # it qualifies under the plain rules (not sourced at me)
        h = DeltaHarness(
            grid_edges(4),
            "g0_0",
            {"g3_3": [PFXS[0]]},
            solver_kwargs=self.LFA_KW,
        )
        set_metric(h.dbs, h.ls, "g0_1", "g0_0", 9)  # far-side edge INTO me
        assert h.step() is False  # full path, still byte-identical
        # a remote event that leaves the me column alone rides the delta
        set_metric(h.dbs, h.ls, "g3_2", "g3_3", 7)
        assert h.step() is True

    def test_lfa_without_apsp_keeps_force_full(self):
        h = DeltaHarness(
            grid_edges(4),
            "g0_0",
            {"g3_3": [PFXS[0]]},
            solver_kwargs={"compute_lfa_paths": True},  # apsp off
        )
        set_metric(h.dbs, h.ls, "g3_2", "g3_3", 7)
        assert h.step() is False
        assert h.builder.delta_builds == 0

    def test_delta_vs_full_parity_includes_lfa_nexthops(self):
        # LFA widens nexthop sets beyond the shortest-path DAG; a stale
        # threshold would show as a missing/excess alternate. Drive a
        # sequence that flips an alternate in and out of qualification.
        h = DeltaHarness(
            [
                ("a", "b", 1),
                ("b", "d", 1),
                ("a", "c", 2),
                ("c", "d", 2),
            ],
            "a",
            {"d": [PFXS[0]]},
            solver_kwargs=self.LFA_KW,
        )
        entry = h.db.unicast_entries[IpPrefix(PFXS[0])]
        assert len(entry.nexthops) == 2  # b on the SP, c as the LFA
        set_metric(h.dbs, h.ls, "c", "d", 9)  # c no longer loop-free
        h.step()
        entry = h.db.unicast_entries[IpPrefix(PFXS[0])]
        nh_nodes = {nh.neighbor_node for nh in entry.nexthops}
        oracle = SpfSolver("a", compute_lfa_paths=True).build_route_db(
            "a", h.als, h.ps
        )
        assert nh_nodes == {
            nh.neighbor_node
            for nh in oracle.unicast_entries[IpPrefix(PFXS[0])].nexthops
        }


class TestChangelogPastItsCap:
    """The served path over more graph-changelog entries than the log
    holds: a reader that keeps up loses none, so no warm metric change
    turns into a graph recompile, a cold solve and a full route build,
    and neither does a remote link that leaves the LSDB."""

    N = 6
    EVENTS = 1100  # x 4 entries: the log passes LinkState._GRAPH_LOG_CAP

    def _swap(self, h, restore, raise_):
        """One metric swap, 4 changelog entries: the link that carried the
        high metric goes back to 1 and another is raised, both directions."""
        for link, metric in ((restore, 1), (raise_, 5)):
            set_metric(h.dbs, h.ls, link[0], link[1], metric)
            set_metric(h.dbs, h.ls, link[1], link[0], metric)

    @pytest.mark.parametrize(
        "structure_at, new_link",
        [(None, False), (550, False), (550, True)],
        ids=["None", "550", "550-new-link"],
    )
    def test_metric_swaps_stay_on_delta_path(self, structure_at, new_link):
        """At event 550 a remote link leaves the LSDB: the snapshot has its
        slots, so that is a patch to INF, a warm solve and DeltaPath like
        every other event. A link the snapshot never held (`new_link`) is
        the one recompile, cold solve and full build of its run."""
        me = "g0_0"
        edges = grid_edges(self.N)
        h = DeltaHarness(
            edges,
            me,
            {
                f"g{i}_{j}": [f"10.{i}.{j}.0/24"]
                for i in range(self.N)
                for j in range(self.N)
                if (i, j) != (0, 0)
            },
        )
        gone = ("g3_3", "g3_4")  # withdrawn at `structure_at`
        diagonal = ("g3_3", "g4_4", 1)  # the link no snapshot has seen
        links = [
            (a, b) for a, b, _ in edges if me not in (a, b) and (a, b) != gone
        ]
        solve = h.solver._solves[("0", me)][1]
        link_edges = solve.graph.link_edges
        name = "decision.spf.graph_recompiles"
        patched = "decision.spf.graph_links_patched"
        # both there from the first sync
        assert h.solver.counters[name] == h.solver.counters[patched] == 0
        raised = links[-1]
        self._swap(h, raised, raised)  # 2 of the 4 change nothing
        assert h.step() is True
        log_pos = h.ls.graph_log_pos
        for k in range(self.EVENTS):
            nxt = links[(7 * k) % len(links)]
            self._swap(h, raised, nxt)
            raised = nxt
            recompiles = k == structure_at and new_link
            if k == structure_at:
                h.dbs[gone[0]] = dataclasses.replace(
                    h.dbs[gone[0]],
                    adjacencies=[
                        adj
                        for adj in h.dbs[gone[0]].adjacencies
                        if adj.other_node_name != gone[1]
                    ],
                )
                h.ls.update_adjacency_database(h.dbs[gone[0]])
            if recompiles:
                for node, db in build_adj_dbs([diagonal]).items():
                    h.dbs[node] = dataclasses.replace(
                        h.dbs[node],
                        adjacencies=h.dbs[node].adjacencies + db.adjacencies,
                    )
                    h.ls.update_adjacency_database(h.dbs[node])
            builds = (h.builder.delta_builds, h.builder.full_builds)
            h.db, _, used = h.builder.build(me, h.als, h.ps, h.db)
            assert used is not recompiles, k
            assert (h.builder.delta_builds, h.builder.full_builds) == (
                builds[0] + used,
                builds[1] + (not used),
            )
            # a patched graph keeps its parent's link_edges, a recompiled
            # one has its own
            assert (solve.graph.link_edges is not link_edges) is recompiles, k
            link_edges = solve.graph.link_edges
            if k == structure_at:  # the event itself, not only the last
                assert_route_db_equal(
                    SpfSolver(me).build_route_db(me, h.als, h.ps), h.db
                )
        assert h.ls.graph_log_pos - log_pos >= 4 * self.EVENTS
        assert h.ls.graph_log_pos > LinkState._GRAPH_LOG_CAP
        want = int(new_link)
        assert solve.graph_recompiles == want
        assert h.solver.counters[name] == want
        # the withdrawal was absorbed, unless its refresh ended in the
        # diagonal's recompile
        want_patched = int(structure_at is not None and not new_link)
        assert solve.graph_links_patched == want_patched
        assert h.solver.counters[patched] == want_patched
        assert h.builder.full_builds == 1 + want  # the first build, always
        assert_route_db_equal(
            SpfSolver(me).build_route_db(me, h.als, h.ps), h.db
        )


def _every_node_announces(edges):
    nodes = sorted({n for a, b, _ in edges for n in (a, b)})
    return {n: [f"10.{i // 256}.{i % 256}.0/24"] for i, n in enumerate(nodes)}


def _count_syncs(monkeypatch, solver):
    """Calls of `solver._sync_spf_counters`, as a one-element list."""
    calls = [0]
    fold = solver._sync_spf_counters

    def counted(solve):
        calls[0] += 1
        fold(solve)

    monkeypatch.setattr(solver, "_sync_spf_counters", counted)
    return calls


class TestCounterSyncs:
    """ISSUE 30: a route build folds the solve's statistics into the
    counters after the solve, at the end of the poll and at the end of
    the build: a constant number of times, whatever the number of
    prefixes and of distance reads."""

    @pytest.mark.parametrize("fabric", ["fabric", "grid"])
    def test_full_build_syncs_a_constant_number_of_times(
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
            ls = build_ls(edges)
            ps = make_prefix_state(_every_node_announces(edges))
            solver = TpuSpfSolver(me)
            calls = _count_syncs(monkeypatch, solver)
            builder = DeltaRouteBuilder(solver)
            db, _, used = builder.build(me, {"0": ls}, ps, None, force_full=True)
            assert not used and len(db.unicast_entries) == len(dbs) - 1
            # the solve, the poll's end, build_route_db's end, the build's
            assert calls[0] == solver.counters["decision.spf.counter_syncs"]
            per_size.append((len(dbs), calls[0]))
        (small, syncs_small), (large, syncs_large) = per_size
        assert large >= 4 * small
        assert syncs_small == syncs_large == 4

    def test_delta_build_syncs_three_times_and_an_idle_one_twice(
        self, monkeypatch
    ):
        side = 6
        h = DeltaHarness(
            grid_edges(side), "g0_0", _every_node_announces(grid_edges(side))
        )
        calls = _count_syncs(monkeypatch, h.solver)
        corner = f"g{side - 1}_{side - 1}"
        set_metric(h.dbs, h.ls, f"g{side - 2}_{side - 1}", corner, 9)
        set_metric(h.dbs, h.ls, f"g{side - 1}_{side - 2}", corner, 9)
        db, _, used = h.builder.build(h.me, h.als, h.ps, h.db)
        assert used
        assert calls[0] == 3  # the solve, the poll's end, the build's end
        # no LSDB change (a prefix event): no solve, so the poll and the
        # build's end alone
        _, _, used = h.builder.build(
            h.me, h.als, h.ps, db, dirty_prefixes={IpPrefix("10.0.3.0/24")}
        )
        assert used
        assert calls[0] == 5

    def test_counters_after_a_cold_and_a_warm_build_are_the_parents(self):
        """What a cold build and a warm delta build leave in the
        supervised solver's registry, pinned to what the parent of
        ISSUE 30 (e0791a6, a sync on every distance read) left on the same
        script: the lazy mirror fetch's bytes, sync and phase among it."""
        side, me = 6, "g0_0"
        edges = grid_edges(side)
        dbs = build_adj_dbs(edges)
        ls = build_ls(edges)
        ps = make_prefix_state(
            {n: [f"10.{i}.0.0/16"] for i, n in enumerate(sorted(dbs))}
        )
        sup = SolverSupervisor(
            TpuSpfSolver(me), SpfSolver(me), SupervisorConfig()
        )
        builder = DeltaRouteBuilder(sup, {})
        als = {"0": ls}

        def left():
            out = {
                name: sup.counters.get(f"decision.spf.{name}")
                for name in (
                    "device_syncs", "device_to_host_bytes",
                    "host_to_device_bytes", "delta_columns", "delta_bytes",
                    "full_solves", "incremental_solves", "graph_recompiles",
                    "traces_recorded",
                )
            }
            for name, hist in sup._ensure_histograms().items():
                if name.startswith("decision.spf."):
                    out[name[len("decision.spf."):]] = hist.count
            return out

        db, _, used = builder.build(me, als, ps, None, force_full=True, build=1)
        assert not used
        assert left() == {
            "device_syncs": 2, "device_to_host_bytes": 2048,
            "host_to_device_bytes": 1248, "delta_columns": None,
            "delta_bytes": None, "full_solves": 1,
            "incremental_solves": None, "graph_recompiles": 0,
            "traces_recorded": 1, "solve_ms": 1, "solve_cold_ms": 1,
            "phase.prepare_ms": 1, "phase.h2d_ms": 1, "phase.relax_ms": 1,
            "phase.d2h_ms": 1,
        }
        corner = f"g{side - 1}_{side - 1}"
        set_metric(dbs, ls, f"g{side - 2}_{side - 1}", corner, 7)
        set_metric(dbs, ls, f"g{side - 1}_{side - 2}", corner, 7)
        _, _, used = builder.build(me, als, ps, db, build=2)
        assert used
        assert left() == {
            "device_syncs": 8, "device_to_host_bytes": 2404,
            "host_to_device_bytes": 2624, "delta_columns": 1,
            "delta_bytes": 356, "full_solves": 1, "incremental_solves": 1,
            "graph_recompiles": 0, "traces_recorded": 2, "solve_ms": 2,
            "solve_cold_ms": 1, "solve_warm_ms": 1, "delta_extract_ms": 1,
            "phase.refresh_ms": 1, "phase.prepare_ms": 2, "phase.h2d_ms": 2,
            "phase.relax_ms": 2, "phase.delta_extract_ms": 1,
            "phase.mirror_patch_ms": 1, "phase.d2h_ms": 1,
        }
        assert sup.counters["decision.spf.counter_syncs"] == 7


# ---------------------------------------------------------------------------
# The plural seam (ISSUE 38): a build's prefixes asked for together
# ---------------------------------------------------------------------------

SEAM_COUNTERS = (
    "decision.no_route_to_prefix",
    "decision.skipped_unicast_route",
    "decision.incompatible_forwarding_type",
    "decision.missing_loopback_addr",
)
TABLE_ROUTES = "decision.route_build_table_routes"
TABLE_READS = "decision.route_build_table_reads"
GENERIC_ROUTES = "decision.route_build_generic_routes"


class _Adverts(dict):
    """(node, area) -> the entries it advertises there, gathered before
    any PrefixDatabase is made (one replaces the one before it)."""

    def prefix_state(self):
        ps = PrefixState()
        for (node, area), entries in self.items():
            ps.update_prefix_database(PrefixDatabase(node, entries, area=area))
        return ps


def _advertise(ps, node, entries, area="0"):
    ps.setdefault((node, area), []).extend(entries)


def _every_node(dbs, ps, v6=True, area="0"):
    for i, node in enumerate(sorted(dbs)):
        entries = [PrefixEntry(IpPrefix(f"10.{i // 256}.{i % 256}.0/24"))]
        if v6:
            entries.append(PrefixEntry(IpPrefix(f"fc00:{i:x}::/64")))
        _advertise(ps, node, entries, area)


def _win_if_present(value):
    from openr_tpu.types import CompareType, MetricEntity, MetricVector

    return MetricVector(
        version=1,
        metrics=(
            MetricEntity(
                id=10, priority=10, op=CompareType.WIN_IF_PRESENT,
                metric=(value,),
            ),
        ),
    )


def _seam_fabric():
    edges = fabric_edges(
        3, planes=2, ssw_per_plane=2, fsw_per_pod=2, rsw_per_pod=3
    )
    return "rsw0_0", build_adj_dbs(edges)


def _seam_grid():
    return "g0_0", build_adj_dbs(grid_edges(4))


def _seam_wan():
    from openr_tpu.topology import wan_edges

    return "w3", build_adj_dbs(wan_edges(24, degree=4, seed=11))


def _seam_anycast(dbs, ps):
    # two announcers at equal distance (3 and 3), three at unequal
    # (2, 4, 6), two of three at the nearest distance, v4 and v6
    for node in ("g0_3", "g3_0"):
        _advertise(ps, node, [
            PrefixEntry(IpPrefix("10.200.0.0/16")),
            PrefixEntry(IpPrefix("fc00:200::/48")),
        ])
    for node in ("g1_1", "g2_2", "g3_3"):
        _advertise(ps, node, [PrefixEntry(IpPrefix("10.201.0.0/16"))])
    for node in ("g0_2", "g2_0", "g3_3"):
        _advertise(ps, node, [PrefixEntry(IpPrefix("fc00:202::/48"))])


def _seam_unreachable(dbs, ps):
    # an announcer the graph does not hold, alone and beside one it holds
    _advertise(ps, "ghost", [
        PrefixEntry(IpPrefix("10.210.0.0/16")),
        PrefixEntry(IpPrefix("10.211.0.0/16")),
    ])
    _advertise(ps, "g2_2", [PrefixEntry(IpPrefix("10.211.0.0/16"))])


def _seam_island(dbs, ps):
    # announcers the graph holds and nothing reaches
    for node in ("island_a", "island_b"):
        _advertise(ps, node, [PrefixEntry(IpPrefix("10.212.0.0/16"))])
    _advertise(ps, "island_a", [PrefixEntry(IpPrefix("10.213.0.0/16"))])
    _advertise(ps, "g1_3", [PrefixEntry(IpPrefix("10.213.0.0/16"))])


def _seam_drained(dbs, ps):
    # a drained announcer alone keeps its route; beside a healthy one,
    # nearer and smaller by name, it loses the route and stays the best
    # entry's; all drained, the nearest of them
    _advertise(ps, "g1_1", [
        PrefixEntry(IpPrefix("10.220.0.0/16")),
        PrefixEntry(IpPrefix("10.221.0.0/16")),
        PrefixEntry(IpPrefix("10.222.0.0/16")),
    ])
    _advertise(ps, "g3_3", [PrefixEntry(IpPrefix("10.221.0.0/16"))])
    _advertise(ps, "g2_1", [PrefixEntry(IpPrefix("10.222.0.0/16"))])


def _seam_mine(dbs, ps):
    _advertise(ps, "g0_0", [PrefixEntry(IpPrefix("10.230.0.0/16"))])
    _advertise(ps, "g2_2", [PrefixEntry(IpPrefix("10.230.0.0/16"))])


def _seam_other_classes(dbs, ps):
    """BGP, mixed-type, KSP2 and SR_MPLS prefixes among plain ones."""
    from openr_tpu.types import (
        PrefixForwardingAlgorithm,
        PrefixForwardingType,
        PrefixType,
    )

    for node, value in (("g3_3", 7), ("g0_3", 9)):
        _advertise(ps, node, [
            PrefixEntry(
                IpPrefix("10.240.0.0/16"), type=PrefixType.BGP,
                mv=_win_if_present(value),
            ),
            PrefixEntry(
                IpPrefix(f"192.168.0.{value}/32"), type=PrefixType.LOOPBACK
            ),
        ])
    # a BGP announcer without a loopback: no route, and a counter
    _advertise(ps, "g2_3", [PrefixEntry(
        IpPrefix("10.241.0.0/16"), type=PrefixType.BGP, mv=_win_if_present(1)
    )])
    # mixed-type: skipped, and counted
    _advertise(ps, "g1_2", [PrefixEntry(IpPrefix("10.242.0.0/16"))])
    _advertise(ps, "g2_1", [PrefixEntry(
        IpPrefix("10.242.0.0/16"), type=PrefixType.BGP, mv=_win_if_present(1)
    )])
    _advertise(ps, "g3_2", [
        PrefixEntry(
            IpPrefix("10.243.0.0/16"),
            forwarding_type=PrefixForwardingType.SR_MPLS,
            forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
        ),
        PrefixEntry(
            IpPrefix("10.244.0.0/16"),
            forwarding_type=PrefixForwardingType.SR_MPLS,
        ),
        # KSP2 over IP forwarding: incompatible, and counted
        PrefixEntry(
            IpPrefix("10.245.0.0/16"),
            forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
        ),
    ])
    # one announcer IP, one SR_MPLS: the minimum is IP, one by one
    _advertise(ps, "g1_3", [PrefixEntry(IpPrefix("10.246.0.0/16"))])
    _advertise(ps, "g3_1", [PrefixEntry(
        IpPrefix("10.246.0.0/16"),
        forwarding_type=PrefixForwardingType.SR_MPLS,
    )])


def _seam_two_areas(dbs, ps):
    # announced in my area and in one that does not hold me
    _advertise(ps, "g2_2", [PrefixEntry(IpPrefix("10.250.0.0/16"))], "0")
    _advertise(ps, "g2_2", [PrefixEntry(IpPrefix("10.250.0.0/16"))], "1")
    _advertise(ps, "far_b", [PrefixEntry(IpPrefix("10.251.0.0/16"))], "1")


def _island_links():
    return build_adj_dbs([("island_a", "island_b", 1)])


# name -> (network, what else is advertised, extra adjacency databases,
# drained nodes, solver options, whether a second area stands beside mine)
SEAM_CASES = {
    "fabric": (_seam_fabric, None, None, (), {}, False),
    "grid": (_seam_grid, None, None, (), {}, False),
    "wan": (_seam_wan, None, None, (), {}, False),
    "anycast": (_seam_grid, _seam_anycast, None, (), {}, False),
    "unreachable": (_seam_grid, _seam_unreachable, None, (), {}, False),
    "island": (_seam_grid, _seam_island, _island_links, (), {}, False),
    "drained": (
        _seam_grid, _seam_drained, None, ("g1_1", "g2_1"), {}, False,
    ),
    "mine": (_seam_grid, _seam_mine, None, (), {}, False),
    "other_classes": (_seam_grid, _seam_other_classes, None, (), {}, False),
    "v4_off": (
        _seam_grid, _seam_anycast, None, (), {"enable_v4": False}, False,
    ),
    "lfa": (
        _seam_grid, _seam_anycast, None, (), {"compute_lfa_paths": True},
        False,
    ),
    "two_areas": (_seam_grid, _seam_two_areas, None, ("g3_3",), {}, True),
}


def _seam_network(case):
    network, extra, extra_links, drained, solver_kw, second_area = (
        SEAM_CASES[case]
    )
    me, dbs = network()
    ps = _Adverts()
    _every_node(dbs, ps)
    if extra is not None:
        extra(dbs, ps)
    if extra_links is not None:
        dbs.update(extra_links())
    ls = LinkState("0")
    for node, db in dbs.items():
        if node in drained and not second_area:
            db = dataclasses.replace(db, is_overloaded=True)
        ls.update_adjacency_database(db)
    als = {"0": ls}
    if second_area:
        # an area that does not hold my node: its say on who is drained
        # counts, and a prefix announced there too goes one by one
        other = LinkState("1")
        for db in build_adj_dbs(
            [("far_a", "far_b", 1), ("far_b", "g2_2", 1), ("g3_3", "far_a", 1)],
            area="1",
        ).values():
            if db.this_node_name in drained:
                db = dataclasses.replace(db, is_overloaded=True)
            other.update_adjacency_database(db)
        als["1"] = other
        _advertise(ps, "g3_3", [PrefixEntry(IpPrefix("10.252.0.0/16"))])
        _advertise(ps, "g1_1", [PrefixEntry(IpPrefix("10.252.0.0/16"))])
    return me, als, ps.prefix_state(), solver_kw


def _moved(solver, before):
    return {
        name: solver.counters.get(name, 0) - before.get(name, 0)
        for name in SEAM_COUNTERS + (TABLE_ROUTES, GENERIC_ROUTES)
    }


class TestPluralSeam:
    """`build_unicast_routes` against a loop of `build_unicast_route` on
    the same backend and against the CPU oracle on the same LSDB: the same
    entries in the same order, the same counters bumped as often."""

    @pytest.mark.parametrize("case", sorted(SEAM_CASES))
    def test_batch_equals_one_by_one_and_the_oracle(self, case):
        me, als, ps, solver_kw = _seam_network(case)
        pairs = list(ps.prefixes.items())

        together = TpuSpfSolver(me, **solver_kw)
        before = dict(together.counters)
        got = {}
        together.build_unicast_routes(got, me, pairs, als, ps)
        together.sync_counters(als)
        got_moved = _moved(together, before)

        singly = TpuSpfSolver(me, **solver_kw)
        before = dict(singly.counters)
        want = {}
        for prefix, prefix_entries in pairs:
            singly.build_unicast_route(
                want, me, prefix, prefix_entries, als, ps
            )
        singly.sync_counters(als)

        oracle_solver = SpfSolver(me, **solver_kw)
        oracle = {}
        oracle_solver.build_unicast_routes(oracle, me, pairs, als, ps)

        assert list(got) == list(want) == list(oracle)
        for prefix, entry in want.items():
            for other in (got[prefix], oracle[prefix]):
                assert other == entry, prefix
                assert other.best_area == entry.best_area, prefix
                assert other.best_prefix_entry is entry.best_prefix_entry
            assert got[prefix].nexthops is not entry.nexthops
        assert got_moved == _moved(singly, before)
        for name in SEAM_COUNTERS:
            assert got_moved[name] == oracle_solver.counters.get(name, 0), name
        # the cases are what they say: routes came out, and the counters
        # they are about moved
        assert got
        if case in ("unreachable", "island"):
            assert got_moved["decision.no_route_to_prefix"] == 1
        if case == "v4_off":
            assert got_moved["decision.skipped_unicast_route"] > 16
        if case == "other_classes":
            assert got_moved["decision.skipped_unicast_route"] == 1
            assert got_moved["decision.incompatible_forwarding_type"] == 1
            assert got_moved["decision.missing_loopback_addr"] == 1
        # one read for all the plain prefixes, and one for each prefix
        # that went one by one and reached the table (the BGP prefix with
        # a loopback and the IP / SR_MPLS one; the prefix of two areas);
        # none with LFA on
        one_by_one = {"other_classes": 2, "two_areas": 1}.get(case, 0)
        reads = together.counters[TABLE_READS]
        assert reads == (0 if case == "lfa" else 1 + one_by_one)
        if case != "lfa":
            assert singly.counters[TABLE_READS] > reads

    def test_drained_announcers_are_dropped_unless_all_are(self):
        me, als, ps, _ = _seam_network("drained")
        got = {}
        TpuSpfSolver(me).build_unicast_routes(
            got, me, ps.prefixes.items(), als, ps
        )

        def toward(prefix):
            entry = got[IpPrefix(prefix)]
            return (
                {nh.metric for nh in entry.nexthops},
                ps.prefixes[IpPrefix(prefix)],
                entry.best_prefix_entry,
            )

        # alone: the drained announcer keeps its route
        metrics, _, _ = toward("10.220.0.0/16")
        assert metrics == {2}
        # beside healthy g3_3 (distance 6): the route goes there, and the
        # best entry stays the smallest reachable name's, drained g1_1's
        metrics, adverts, best = toward("10.221.0.0/16")
        assert metrics == {6} and best is adverts["g1_1"]["0"]
        # both drained: the nearest of them
        metrics, _, _ = toward("10.222.0.0/16")
        assert metrics == {2}

    def test_policy_rewrites_one_entry_and_leaves_its_siblings(self):
        me, als, ps, _ = _seam_network("fabric")
        solver = TpuSpfSolver(me)
        plain = SpfSolver(me).build_route_db(me, als, ps)
        # a route over every up-link, as every other pod's racks have
        victim = max(
            plain.unicast_entries,
            key=lambda p: len(plain.unicast_entries[p].nexthops),
        )

        def policy(entry):
            if entry.prefix == victim:
                entry.nexthops.clear()

        db, _, _ = DeltaRouteBuilder(solver).build(
            me, als, ps, None, force_full=True, policy_fn=policy
        )
        assert db.unicast_entries[victim].nexthops == set()
        wide = plain.unicast_entries[victim].nexthops
        siblings = [
            prefix
            for prefix, entry in plain.unicast_entries.items()
            if entry.nexthops == wide and prefix != victim
        ]
        assert siblings
        for prefix in siblings:
            assert db.unicast_entries[prefix].nexthops == wide, prefix
        table = solver._solves[("0", me)][1].next_hop_table()
        assert frozenset(wide) in set(table.unicast_sets.values())

    def test_a_delta_build_asks_the_table_once_for_its_prefixes(self):
        edges = [("a", "b", 1), ("b", "c", 1), ("c", "d", 1), ("a", "d", 9)]
        h = DeltaHarness(
            edges, "a", {"b": [PFXS[0]], "c": [PFXS[1]], "d": [PFXS[2]]}
        )
        set_metric(h.dbs, h.ls, "b", "c", 8)  # c and d move: two columns
        before = dict(h.solver.counters)
        assert h.step()
        moved = _moved(h.solver, before)
        reads = h.solver.counters[TABLE_READS] - before[TABLE_READS]
        # unicast and label route of every changed column: the labels'
        # reads one each, the prefixes' one together
        assert moved[TABLE_ROUTES] == 4
        assert reads == 2 + 1

    def test_a_delta_build_keeps_the_entries_that_changed_after_the_policy(self):
        edges = [("a", "b", 1), ("b", "c", 1), ("c", "d", 1), ("a", "d", 9)]
        dbs = build_adj_dbs(edges)
        ls = LinkState("0")
        for db in dbs.values():
            ls.update_adjacency_database(db)
        als = {"0": ls}
        ps = make_prefix_state(
            {"b": [PFXS[0]], "c": [PFXS[1]], "d": [PFXS[2], PFXS[3]]}
        )
        emptied, kept, moved, gone = (IpPrefix(p) for p in PFXS)

        def policy(entry):
            if entry.prefix == emptied:
                entry.nexthops.clear()

        builder = DeltaRouteBuilder(TpuSpfSolver("a"))
        db, _, _ = builder.build(
            "a", als, ps, None, force_full=True, policy_fn=policy
        )
        assert db.unicast_entries[emptied].nexthops == set()
        set_metric(dbs, ls, "c", "d", 3)  # d's column moves, c's does not
        ps.update_prefix_database(
            PrefixDatabase("d", [PrefixEntry(moved)], area="0")
        )
        new_db, update, used = builder.build(
            "a", als, ps, db, dirty_prefixes={emptied, kept, gone},
            policy_fn=policy,
        )
        assert used
        # rebuilt: four prefixes; changed: one; withdrawn: one. The
        # policy's entry was emptied again before it was compared
        assert [e.prefix for e in update.unicast_routes_to_update] == [moved]
        assert update.unicast_routes_to_delete == [gone]
        assert new_db.unicast_entries[emptied] is db.unicast_entries[emptied]
        assert new_db.unicast_entries[kept] is db.unicast_entries[kept]
        assert {nh.metric for nh in new_db.unicast_entries[moved].nexthops} == {5}
