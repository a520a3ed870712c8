"""Differentiable-TE service acceptance + fault-domain suite (ISSUE 7).

The tier-1 acceptance criterion lives here: on the deterministic congested
2-pod Clos fixture, `te-optimize` must propose integer weights whose
hard-SPF routing STRICTLY reduces max link utilization vs the initial
uniform weights — verified independently by replaying the proposed changes
onto the compiled graph and re-scoring with the exact-ECMP hard model.
The fault tests drive the `te.optimize` seam through SolverSupervisor:
an injected device fault degrades the optimization to the CPU backend
(identical proposal, `degraded: true` report) without crashing.
"""

import numpy as np
import pytest

from openr_tpu.lsdb import LinkState
from openr_tpu.ops.graph import compile_graph
from openr_tpu.solver import (
    SolverSupervisor,
    SpfSolver,
    SupervisorConfig,
    TpuSpfSolver,
)
from openr_tpu.te import (
    TeService,
    build_demand_scenarios,
    congested_clos_fixture,
    hard_max_util,
    te_edge_arrays,
    uniform_demand_spec,
)
from openr_tpu.testing.faults import injected
from openr_tpu.topology import build_adj_dbs, grid_edges


def build_ls(edges, area="0", **kwargs):
    ls = LinkState(area)
    for db in build_adj_dbs(edges, area=area, **kwargs).values():
        ls.update_adjacency_database(db)
    return ls


def apply_changes(graph, w0_int, changes):
    """Replay a report's proposed weight_changes onto the edge arrays —
    the operator's `breeze lm set-link-metric` step, done by hand."""
    w = w0_int.copy()
    applied = 0
    for change in changes:
        for link, (fwd, rev) in graph.link_edges.items():
            for pos, node in ((fwd, link.n1), (rev, link.n2)):
                if (
                    node == change["node"]
                    and link.other_node_name(node) == change["neighbor"]
                    and link.iface_from_node(node) == change["iface"]
                ):
                    assert int(w[pos]) == change["metric_before"]
                    w[pos] = change["metric_after"]
                    applied += 1
    assert applied == len(changes), "every proposed change must map back"
    return w


class TestAcceptance:
    def test_clos_fixture_strictly_reduces_max_util(self):
        """The acceptance criterion: skewed elephant demand on the 2-pod
        Clos, uniform initial weights — the proposal must strictly reduce
        the hard-SPF max link utilization, re-verified from scratch."""
        edges, spec = congested_clos_fixture()
        ls = build_ls(edges)
        svc = TeService("l0_0", {"0": ls})
        report = svc.optimize({"demands": spec, "steps": 60, "seed": 0})

        assert report["improved"] is True
        assert report["optimized_max_util"] < report["initial_max_util"]
        assert report["weight_changes"], "an improvement implies changes"
        assert report["degraded"] is False

        # independent re-verification under exact SPF + fractional ECMP
        graph = compile_graph(ls)
        src_e, dst_e, w0, up = te_edge_arrays(graph)
        demands, caps, _ = build_demand_scenarios(graph, spec)
        w0_int = np.rint(w0).astype(np.int64)
        initial = max(
            hard_max_util(w0_int, demands[k], caps, src_e, dst_e, up,
                          graph.n)
            for k in range(demands.shape[0])
        )
        w_best = apply_changes(graph, w0_int, report["weight_changes"])
        optimized = max(
            hard_max_util(w_best, demands[k], caps, src_e, dst_e, up,
                          graph.n)
            for k in range(demands.shape[0])
        )
        assert initial == pytest.approx(report["initial_max_util"])
        assert optimized == pytest.approx(report["optimized_max_util"])
        assert optimized < initial
        # the fixture's designed optimum: the 3-way split of the elephant
        assert optimized == pytest.approx(2.0)

        # counters + histogram recorded through the mixins
        assert svc.counters["decision.te.optimize_runs"] == 1
        assert svc.counters["decision.te.improved_last"] == 1
        assert svc.histograms["decision.te.solve_ms"].count == 1

    def test_report_shape_and_top_links(self):
        edges, spec = congested_clos_fixture()
        svc = TeService("l0_0", {"0": build_ls(edges)})
        report = svc.optimize({"demands": spec, "steps": 30})
        for key in (
            "node", "area", "nodes", "links", "scenarios", "steps",
            "backend", "degraded", "improved", "initial_max_util",
            "optimized_max_util", "max_util_delta", "weight_changes",
            "top_links", "solve_ms",
        ):
            assert key in report, key
        # the congested express link leads the initial hot-link table
        hottest = report["top_links"]["initial"][0]
        assert {hottest["src"], hottest["dst"]} == {"l0_0", "l1_0"}
        assert hottest["util"] == pytest.approx(6.0)
        assert report["max_util_delta"] < 0

    def test_uniform_default_demands_when_no_spec(self):
        # no demand file: the what-if sweep runs over the uniform prior
        svc = TeService("g0_0", {"0": build_ls(grid_edges(3))})
        report = svc.optimize({"steps": 8})
        assert report["scenarios"] == 1
        assert report["initial_max_util"] > 0

    def test_empty_topology_is_a_request_error(self):
        svc = TeService("a", {"0": LinkState("0")})
        with pytest.raises(ValueError):
            svc.optimize({})
        assert svc.counters["decision.te.optimize_errors"] == 1

    def test_unknown_area_is_a_request_error(self):
        svc = TeService("a", {"0": build_ls([("a", "b", 1)])})
        with pytest.raises(ValueError):
            svc.optimize({"area": "nope"})

    def test_drained_node_carries_no_transit_or_demand(self):
        import dataclasses

        # drain the only transit node of a line: the optimization must see
        # a topology where b's out-edges are down and its demands zeroed
        edges = [("a", "b", 1), ("b", "c", 1)]
        dbs = build_adj_dbs(edges)
        dbs["b"] = dataclasses.replace(dbs["b"], is_overloaded=True)
        ls = LinkState("0")
        for db in dbs.values():
            ls.update_adjacency_database(db)
        svc = TeService("a", {"0": ls})
        report = svc.optimize(
            {"demands": {"demands": [["a", "c", 5.0], ["a", "b", 1.0]]},
             "steps": 4}
        )
        # a->c traffic is unroutable without b's transit and the a->b
        # demand is zeroed (a drained node is neither source nor sink of
        # TE traffic): nothing loads any link, and no change can help
        assert report["initial_max_util"] == pytest.approx(0.0)
        assert report["improved"] is False
        assert report["weight_changes"] == []


class TestScenarios:
    def test_spec_parsing_capacities_and_spread(self):
        graph = compile_graph(build_ls(grid_edges(3)))
        spec = {
            "demands": [["g0_0", "g2_2", 4.0], ["ghost", "g0_0", 9.0]],
            "capacities": {"default": 2.0, "links": [["g0_0", "g0_1", 8.0]]},
            "scenarios": 3,
            "scenario_spread": 0.25,
        }
        demands, caps, scenarios = build_demand_scenarios(graph, spec, seed=1)
        assert scenarios == 3 and demands.shape[0] == 3
        i, j = graph.node_index["g0_0"], graph.node_index["g2_2"]
        assert demands[0, i, j] == pytest.approx(4.0)
        assert demands.sum() == pytest.approx(
            demands[:, i, j].sum()
        ), "unknown node rows are dropped"
        # scenario k>0 scales origin rows inside [1-spread, 1+spread]
        assert demands[1, i, j] != demands[0, i, j]
        assert 3.0 <= demands[1, i, j] <= 5.0
        # capacities: default everywhere, the overridden link both ways
        a, b = graph.node_index["g0_0"], graph.node_index["g0_1"]
        for e in range(graph.e):
            expected = (
                8.0
                if {int(graph.src[e]), int(graph.dst[e])} == {a, b}
                else 2.0
            )
            assert caps[e] == pytest.approx(expected)

    def test_scenarios_deterministic_by_seed(self):
        graph = compile_graph(build_ls(grid_edges(3)))
        spec = uniform_demand_spec(list(graph.names))
        spec["scenarios"] = 4
        d1, _, _ = build_demand_scenarios(graph, spec, seed=7)
        d2, _, _ = build_demand_scenarios(graph, spec, seed=7)
        d3, _, _ = build_demand_scenarios(graph, spec, seed=8)
        np.testing.assert_array_equal(d1, d2)
        assert not np.array_equal(d1, d3)


class TestMeshSharding:
    def test_scenario_batch_shards_over_mesh(self):
        """Scenario sweeps ride the SPF source-batch sharding scheme: the
        [B, N, N] demand tensor is row-sharded over the mesh 'batch' axis
        (B=3 pads to the 4-way axis with masked zero-demand scenarios)
        and the optimization still finds the fixture's improvement."""
        from openr_tpu.parallel import resolve_mesh

        mesh = resolve_mesh((4, 2))  # conftest forces 8 host devices
        edges, spec = congested_clos_fixture()
        spec = dict(spec)
        spec["scenarios"] = 3
        spec["scenario_spread"] = 0.2
        svc = TeService("l0_0", {"0": build_ls(edges)}, mesh=mesh)
        report = svc.optimize({"demands": spec, "steps": 40, "seed": 0})
        assert report["scenarios"] == 3
        assert report["improved"] is True
        assert report["optimized_max_util"] < report["initial_max_util"]


class TestFaultDomain:
    def make_supervised(self, me, area_ls, samples=None, **cfg_kw):
        sup = SolverSupervisor(
            TpuSpfSolver(me),
            SpfSolver(me),
            SupervisorConfig(**cfg_kw),
            log_sample_fn=(samples.append if samples is not None else None),
        )
        return TeService(
            me, area_ls, solver=sup,
            log_sample_fn=(samples.append if samples is not None else None),
        ), sup

    def test_injected_fault_degrades_to_cpu_without_crashing(self):
        """The ISSUE acceptance fault test: a persistent device fault at
        the te.optimize seam must yield the identical improving proposal
        from the CPU backend, marked degraded — never an exception."""
        edges, spec = congested_clos_fixture()
        samples = []
        svc, sup = self.make_supervised(
            "l0_0", {"0": build_ls(edges)}, samples=samples, max_attempts=2
        )
        with injected() as inj:
            inj.arm("te.optimize", times=None)  # persistent device fault
            report = svc.optimize({"demands": spec, "steps": 40, "seed": 0})
            assert inj.fired("te.optimize") >= 1
        assert report["degraded"] is True
        assert report["backend"] == "cpu"
        # the degraded path runs the identical optimization: still a
        # strict improvement on the fixture
        assert report["improved"] is True
        assert report["optimized_max_util"] < report["initial_max_util"]
        assert svc.counters["decision.te.fallback_runs"] == 1
        # the fault fed the shared breaker's failure accounting
        assert sup.counters["decision.spf.solver_failures"] >= 1
        assert any(
            s._values.get("event") == "TE_OPTIMIZE_DEGRADED"
            for s in samples
        )

    def test_transient_fault_is_retried_in_call(self):
        edges, spec = congested_clos_fixture()
        svc, sup = self.make_supervised(
            "l0_0", {"0": build_ls(edges)}, max_attempts=3
        )
        with injected() as inj:
            inj.arm("te.optimize", times=1)  # heals on the retry
            report = svc.optimize({"demands": spec, "steps": 20})
        assert report["degraded"] is False
        assert sup.counters["decision.spf.solver_retries"] >= 1

    def test_open_breaker_serves_fallback_immediately(self):
        edges, spec = congested_clos_fixture()
        svc, sup = self.make_supervised(
            "l0_0", {"0": build_ls(edges)}, failure_threshold=1,
            max_attempts=1,
        )
        with injected() as inj:
            inj.arm("te.optimize", times=None)
            first = svc.optimize({"demands": spec, "steps": 10})
            fired_once = inj.fired("te.optimize")
            second = svc.optimize({"demands": spec, "steps": 10})
            assert inj.fired("te.optimize") == fired_once, (
                "an open breaker must not re-dispatch to the device"
            )
        assert first["degraded"] and second["degraded"]
        assert svc.counters["decision.te.fallback_runs"] == 2

    def test_unsupervised_service_still_degrades(self):
        # no supervisor attached (cpu-backend Decision): the plain
        # try/except fallback path serves, degraded is still reported
        edges, spec = congested_clos_fixture()
        svc = TeService("l0_0", {"0": build_ls(edges)})
        with injected() as inj:
            inj.arm("te.optimize", times=None)
            report = svc.optimize({"demands": spec, "steps": 20})
        assert report["degraded"] is True
        assert report["improved"] is True


class TestDecisionIntegration:
    def make_decision(self, edges, me, backend="tpu"):
        from openr_tpu.decision import Decision, DecisionConfig
        from openr_tpu.messaging import ReplicateQueue, RQueue, RWQueue

        decision = Decision(
            DecisionConfig(my_node_name=me, solver_backend=backend),
            RQueue(RWQueue()),
            ReplicateQueue(),
        )
        ls = decision.area_link_states["0"]
        for db in build_adj_dbs(edges).values():
            ls.update_adjacency_database(db)
        return decision

    def test_run_te_optimize_through_decision(self):
        edges, spec = congested_clos_fixture()
        decision = self.make_decision(edges, "l0_0")
        report = decision.run_te_optimize(
            {"demands": spec, "steps": 40, "seed": 0}
        )
        assert report["improved"] is True
        assert report["node"] == "l0_0"
        # TE counters land in Decision's monitor-registered dicts
        assert decision.counters["decision.te.optimize_runs"] == 1
        assert "decision.te.solve_ms" in decision.histograms
        # the service is built once and reused
        svc = decision._te_service
        decision.run_te_optimize({"demands": spec, "steps": 4})
        assert decision._te_service is svc
        assert decision.counters["decision.te.optimize_runs"] == 2

    def test_decision_level_fault_degrades(self):
        edges, spec = congested_clos_fixture()
        decision = self.make_decision(edges, "l0_0")
        with injected() as inj:
            inj.arm("te.optimize", times=None)
            report = decision.run_te_optimize(
                {"demands": spec, "steps": 20}
            )
        assert report["degraded"] is True
        assert report["improved"] is True
        # the TE fault fed the same breaker the SPF solves use
        assert decision.solver.counters[
            "decision.spf.solver_failures"
        ] >= 1
