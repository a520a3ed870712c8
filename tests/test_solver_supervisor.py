"""Solver fault domain: supervised solves, circuit breaker + CPU fallback,
probe-driven recovery with hysteresis, and the warm-state shadow audit —
every degraded path driven by the deterministic fault injector
(openr_tpu/testing/faults.py), no real device errors required."""

import numpy as np
import pytest

from openr_tpu.lsdb import LinkState, PrefixState
from openr_tpu.monitor import Watchdog
from openr_tpu.solver import SolverSupervisor, SpfSolver, SupervisorConfig, TpuSpfSolver
from openr_tpu.solver.supervisor import (
    CLOSED,
    FAULT_COMPILE,
    FAULT_DEADLINE,
    FAULT_DEVICE_LOSS,
    FAULT_DEVICE_OOM,
    FAULT_RUNTIME,
    HALF_OPEN,
    OPEN,
    SolveDeadlineExceeded,
    classify_solver_error,
)
from openr_tpu.testing.faults import FaultInjected, FaultInjector, injected
from openr_tpu.topology import build_adj_dbs, grid_edges
from openr_tpu.types import IpPrefix, PrefixDatabase, PrefixEntry


class FakeClock:
    def __init__(self) -> None:
        self.t = 100.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def build_ls(edges, area="0", **kwargs):
    ls = LinkState(area)
    for db in build_adj_dbs(edges, area=area, **kwargs).values():
        ls.update_adjacency_database(db)
    return ls


def make_prefix_state(announcers, area="0"):
    ps = PrefixState()
    for node, pfxs in announcers.items():
        ps.update_prefix_database(
            PrefixDatabase(
                node, [PrefixEntry(IpPrefix(p)) for p in pfxs], area=area
            )
        )
    return ps


def assert_route_db_equal(db_a, db_b):
    assert db_a is not None and db_b is not None
    assert set(db_a.unicast_entries) == set(db_b.unicast_entries)
    for prefix, entry in db_a.unicast_entries.items():
        assert entry.nexthops == db_b.unicast_entries[prefix].nexthops, prefix
    assert set(db_a.mpls_entries) == set(db_b.mpls_entries)
    for label, entry in db_a.mpls_entries.items():
        assert entry.nexthops == db_b.mpls_entries[label].nexthops, label


def make_supervisor(me="g0_0", clock=None, watchdog=None, samples=None,
                    **cfg_kw):
    cfg = SupervisorConfig(**cfg_kw)
    return SolverSupervisor(
        TpuSpfSolver(me),
        SpfSolver(me),
        cfg,
        watchdog=watchdog,
        log_sample_fn=(samples.append if samples is not None else None),
        clock=clock or FakeClock(),
    )


EDGES = grid_edges(3)
ANNOUNCERS = {"g2_2": ["10.1.0.0/16"], "g0_2": ["10.2.0.0/16"]}


def solve_inputs():
    return "g0_0", {"0": build_ls(EDGES)}, make_prefix_state(ANNOUNCERS)


def oracle_db():
    me, states, ps = solve_inputs()
    return SpfSolver(me).build_route_db(me, states, ps)


class TestClassification:
    def test_deadline(self):
        assert classify_solver_error(SolveDeadlineExceeded("x")) == (
            FAULT_DEADLINE
        )

    def test_device_loss_by_message(self):
        assert classify_solver_error(
            RuntimeError("DEVICE_LOST: chip 3 went away")
        ) == FAULT_DEVICE_LOSS

    def test_messages_jax_0_9_and_libtpu_raise(self):
        """The exact wording jax 0.9.0 / libtpu 0.0.34 produce (chip probe
        and dead-backend runs, PR 21) lands in the right bucket."""
        # runtime allocation past HBM: a plain ValueError on the chip
        assert classify_solver_error(
            ValueError(
                "RESOURCE_EXHAUSTED: Error allocating device buffer: "
                "Attempting to allocate 4.00G. That was not possible. "
                "There are 3.75G free.; (0x0x0_HBM0)"
            )
        ) == FAULT_DEVICE_OOM
        # a primitive with no TPU lowering
        assert classify_solver_error(
            NotImplementedError(
                "MLIR translation rule for primitive 'eig' not found for "
                "platform tpu"
            )
        ) == FAULT_COMPILE
        # a handle used after its buffer was donated
        assert classify_solver_error(
            RuntimeError("Array has been deleted with shape=float32[1024].")
        ) == FAULT_RUNTIME
        # a backend that is not there: both wordings mention neither
        # "device lost" nor a loss code, one of them mentions "compile"
        assert classify_solver_error(
            RuntimeError(
                "UNAVAILABLE: TPU backend setup/compile error (Unavailable)."
            )
        ) == FAULT_DEVICE_LOSS
        assert classify_solver_error(
            RuntimeError(
                "Unable to initialize backend 'tpu': UNKNOWN: TPU "
                "initialization failed: No jellyfish device found."
            )
        ) == FAULT_DEVICE_LOSS

    def test_compile_by_message_and_type(self):
        assert classify_solver_error(
            RuntimeError("XLA compile failed: out of registers")
        ) == FAULT_COMPILE
        assert classify_solver_error(TypeError("bad avals")) == FAULT_COMPILE

    def test_chained_cause_is_searched(self):
        try:
            try:
                raise RuntimeError("device is lost")
            except RuntimeError as inner:
                raise ValueError("wrapper") from inner
        except ValueError as exc:
            assert classify_solver_error(exc) == FAULT_DEVICE_LOSS

    def test_unknown_defaults_to_runtime(self):
        assert classify_solver_error(RuntimeError("boom")) == FAULT_RUNTIME
        assert classify_solver_error(FaultInjected("p")) == FAULT_RUNTIME


class TestSupervisedSolve:
    def test_clean_path_serves_primary(self):
        sup = make_supervisor()
        db = sup.build_route_db(*solve_inputs())
        assert_route_db_equal(db, oracle_db())
        assert sup.state == CLOSED
        assert sup.counters["decision.spf.fallback_active"] == 0
        assert "decision.spf.fallback_solves" not in sup.counters

    def test_retry_within_call_heals_transient_fault(self):
        sup = make_supervisor(failure_threshold=5, max_attempts=2)
        with injected() as inj:
            inj.arm("solver.tpu.solve", times=1)
            db = sup.build_route_db(*solve_inputs())
        assert_route_db_equal(db, oracle_db())
        assert sup.state == CLOSED
        assert sup.consecutive_failures == 0  # success reset the streak
        assert sup.counters["decision.spf.solver_retries"] == 1
        assert sup.counters["decision.spf.solver_failures"] == 1
        assert sup.counters["decision.spf.solver_failures.runtime"] == 1

    def test_exhausted_retries_serve_fallback_without_trip(self):
        sup = make_supervisor(failure_threshold=10, max_attempts=2)
        with injected() as inj:
            inj.arm("solver.tpu.solve", times=None)
            db = sup.build_route_db(*solve_inputs())
        assert_route_db_equal(db, oracle_db())
        assert sup.state == CLOSED  # below threshold: breaker still closed
        assert sup.counters["decision.spf.fallback_solves"] == 1

    def test_deadline_overrun_counts_but_serves_result(self):
        clock = FakeClock()
        watchdog = Watchdog()
        sup = make_supervisor(
            clock=clock,
            watchdog=watchdog,
            solve_deadline_s=0.0,  # every real solve overruns a 0s budget
            failure_threshold=10,
        )
        # make elapsed strictly positive under the fake clock
        def ticking():
            clock.advance(1.0)
            return clock.t

        sup._clock = ticking
        sup._probe_backoff._clock = ticking
        db = sup.build_route_db(*solve_inputs())
        assert_route_db_equal(db, oracle_db())  # slow-but-correct is served
        assert sup.counters["decision.spf.solver_failures.deadline"] == 1
        assert watchdog.slow_sections.get("decision") == 1
        assert sup.state == CLOSED


class TestCircuitBreaker:
    def test_persistent_failure_trips_to_cpu_fallback_and_probe_recovers(
        self,
    ):
        """Acceptance: injected persistent TPU failure → oracle-identical
        routes via CPU fallback, fallback_active reads 1; a successful
        probe streak restores the TPU path (reads 0)."""
        clock = FakeClock()
        samples = []
        sup = make_supervisor(
            clock=clock,
            samples=samples,
            failure_threshold=2,
            max_attempts=1,
            probe_interval_s=5.0,
            probe_successes_to_close=2,
        )
        with injected() as inj:
            inj.arm("solver.tpu.solve", times=None)  # persistent device fault
            db1 = sup.build_route_db(*solve_inputs())  # failure 1
            assert sup.state == CLOSED
            db2 = sup.build_route_db(*solve_inputs())  # failure 2 → trip
            assert sup.state == OPEN
            db3 = sup.build_route_db(*solve_inputs())  # served while open
        for db in (db1, db2, db3):
            assert_route_db_equal(db, oracle_db())
        assert sup.counters["decision.spf.fallback_active"] == 1
        assert sup.counters["decision.spf.breaker_trips"] == 1
        assert sup.counters["decision.spf.solver_failures"] == 2
        assert sup.health()["degraded"] is True
        assert any(
            s.get("event") == "SOLVER_BREAKER_TRIPPED" for s in samples
        )
        # the warm state was invalidated on trip
        assert sup.primary.counters[
            "decision.spf.warm_state_invalidations"
        ] >= 1

        # device healed (no injector): probes with hysteresis restore it
        clock.advance(5.0)
        assert sup.maybe_probe()
        assert sup.state == HALF_OPEN  # 1 of 2 successes: still degraded
        assert sup.health()["degraded"] is True
        clock.advance(5.0)
        assert sup.maybe_probe()
        assert sup.state == CLOSED
        assert sup.counters["decision.spf.fallback_active"] == 0
        assert sup.health()["degraded"] is False
        assert sup.counters["decision.spf.probe_successes"] == 2
        assert any(
            s.get("event") == "SOLVER_BREAKER_CLOSED" for s in samples
        )
        # and the primary serves again, identically
        db4 = sup.build_route_db(*solve_inputs())
        assert_route_db_equal(db4, oracle_db())
        # db1 (retry exhausted), db2 (trip), db3 (open) — and no more
        # after the breaker closed
        assert sup.counters["decision.spf.fallback_solves"] == 3

    def test_probe_failure_resets_streak_and_backs_off(self):
        clock = FakeClock()
        sup = make_supervisor(
            clock=clock,
            failure_threshold=1,
            max_attempts=1,
            probe_interval_s=5.0,
            probe_successes_to_close=2,
        )
        with injected() as inj:
            inj.arm("solver.tpu.solve", times=None)
            sup.build_route_db(*solve_inputs())
            assert sup.state == OPEN
            clock.advance(5.0)
            assert sup.maybe_probe()  # probe fails too
            assert sup.state == OPEN
            assert sup.probe_streak == 0
            assert sup.counters["decision.spf.probe_failures"] == 1
            # backoff gates the next probe: not due immediately
            clock.advance(1.0)
            assert not sup.probe_due()
        # flapping device: one success then a failure never closes
        clock.advance(60.0)
        assert sup.maybe_probe()
        assert sup.state == HALF_OPEN
        with injected() as inj:
            inj.arm("solver.tpu.solve", times=None)
            clock.advance(5.0)
            assert sup.maybe_probe()
            assert sup.state == OPEN
            assert sup.probe_streak == 0

    def test_opportunistic_probe_from_solve_path(self):
        # loop-less embeddings recover without the background task: the
        # solve path itself runs due probes
        clock = FakeClock()
        sup = make_supervisor(
            clock=clock,
            failure_threshold=1,
            max_attempts=1,
            probe_interval_s=5.0,
            probe_successes_to_close=1,
        )
        with injected() as inj:
            inj.arm("solver.tpu.solve", times=1)
            sup.build_route_db(*solve_inputs())
        assert sup.state == OPEN
        clock.advance(5.0)
        db = sup.build_route_db(*solve_inputs())  # probe runs, closes, but
        assert sup.state == CLOSED  # this event was already queued to
        assert_route_db_equal(db, oracle_db())  # whichever path served it

    def test_static_routes_flow_through_both_backends(self):
        from openr_tpu.types import NextHop

        sup = make_supervisor(failure_threshold=1, max_attempts=1)
        nh = NextHop(address="fe80::1", iface="lo")
        sup.push_static_routes_delta({100: {nh}}, set())
        delta = sup.process_static_route_updates()
        assert delta is not None and delta.mpls_routes_to_update
        # fallback ingested the same static state in lockstep
        assert sup.fallback.static_mpls_routes == (
            sup.primary.static_mpls_routes
        )


def _device_lost(point):
    return RuntimeError(f"device is lost at {point}")


class TestPartialMeshDegradation:
    """The degradation ladder (docs/Robustness.md): device-loss streaks
    shrink the solver mesh over surviving chips; the CPU oracle is the
    LAST rung, reached only when no viable mesh remains."""

    def make_meshed_supervisor(self, mesh, samples=None, **cfg_kw):
        # threshold 1: every failed build reaches a ladder/trip decision
        cfg_kw.setdefault("failure_threshold", 1)
        cfg_kw.setdefault("max_attempts", 1)
        return SolverSupervisor(
            TpuSpfSolver("g0_0", mesh=mesh),
            SpfSolver("g0_0"),
            SupervisorConfig(**cfg_kw),
            log_sample_fn=(samples.append if samples is not None else None),
            clock=FakeClock(),
        )

    def test_device_loss_degrades_mesh_instead_of_tripping(self):
        samples = []
        sup = self.make_meshed_supervisor((2, 2), samples=samples)
        with injected() as inj:
            inj.arm("solver.tpu.solve", times=1, exc=_device_lost)
            db = sup.build_route_db(*solve_inputs())  # fails -> takes a rung
        assert_route_db_equal(db, oracle_db())  # this event served degraded
        assert sup.state == CLOSED  # breaker never opened
        assert sup.consecutive_failures == 0  # streak reset by the rung
        assert sup.counters["decision.spf.mesh_degradations"] == 1
        assert sup.counters["decision.spf.mesh_devices"] == 2
        assert dict(sup.primary.mesh.shape) == {"batch": 1, "graph": 2}
        assert "decision.spf.breaker_trips" not in sup.counters
        assert any(
            s.get("event") == "SOLVER_MESH_DEGRADED" for s in samples
        )
        # the primary serves the next event on the smaller mesh
        db2 = sup.build_route_db(*solve_inputs())
        assert_route_db_equal(db2, oracle_db())
        assert sup.counters.get("decision.spf.fallback_solves", 0) == 1

    def test_ladder_walks_to_cpu_when_no_mesh_remains(self):
        """Persistent device loss: (1, 2) -> (1, 1) -> no rung below a
        single device -> the breaker finally trips to the oracle."""
        sup = self.make_meshed_supervisor((1, 2))
        with injected() as inj:
            inj.arm("solver.tpu.solve", times=None, exc=_device_lost)
            db = sup.build_route_db(*solve_inputs())  # rung: (1, 1)
            assert sup.state == CLOSED
            assert dict(sup.primary.mesh.shape) == {"batch": 1, "graph": 1}
            db = sup.build_route_db(*solve_inputs())  # no rung left: trip
            assert sup.state == OPEN
            db = sup.build_route_db(*solve_inputs())  # served while open
        assert_route_db_equal(db, oracle_db())
        assert sup.counters["decision.spf.mesh_degradations"] == 1
        assert sup.counters["decision.spf.breaker_trips"] == 1
        assert sup.health()["mesh_degradations"] == 1
        assert sup.health()["solver_mesh"] == {"batch": 1, "graph": 1}

    def test_non_device_loss_faults_skip_the_ladder(self):
        """A compile/runtime streak trips straight to the oracle — a
        smaller mesh cannot heal a lowering bug."""
        sup = self.make_meshed_supervisor((2, 2))
        with injected() as inj:
            inj.arm("solver.tpu.solve", times=None)  # runtime kind
            sup.build_route_db(*solve_inputs())
        assert sup.state == OPEN
        assert "decision.spf.mesh_degradations" not in sup.counters
        assert dict(sup.primary.mesh.shape) == {"batch": 2, "graph": 2}

    def test_knob_disables_the_ladder(self):
        sup = self.make_meshed_supervisor((2, 2), mesh_degrade=False)
        with injected() as inj:
            inj.arm("solver.tpu.solve", times=None, exc=_device_lost)
            sup.build_route_db(*solve_inputs())
        assert sup.state == OPEN
        assert "decision.spf.mesh_degradations" not in sup.counters

    def test_meshless_primary_trips_as_before(self):
        sup = make_supervisor(failure_threshold=1, max_attempts=1)
        with injected() as inj:
            inj.arm("solver.tpu.solve", times=None, exc=_device_lost)
            sup.build_route_db(*solve_inputs())
        assert sup.state == OPEN
        assert sup.health()["solver_mesh"] is None


class TestWarmStateAudit:
    def _corrupt(self, solve):
        """Perturb one warm D entry (device + host mirror) — the injected
        warm-state corruption of the acceptance criteria."""
        import jax.numpy as jnp

        d = np.array(solve.d)
        d[0, d.shape[1] // 2] += 3
        solve._d_host = d
        solve._d_dev = jnp.asarray(d)

    def test_corruption_caught_within_n_events_and_healed(self):
        """Acceptance: a perturbed D entry is caught by the shadow audit
        within N events, increments decision.spf.audit_mismatches, and the
        forced cold re-solve restores oracle-identical routes."""
        samples = []
        sup = make_supervisor(samples=samples, audit_interval=2)
        me, states, ps = solve_inputs()
        ls = states["0"]

        db = sup.build_route_db(me, states, ps)  # event 1: no audit yet
        assert sup.counters.get("decision.spf.audit_runs", 0) == 0

        with injected() as inj:
            inj.arm("solver.tpu.warm_d", action=self._corrupt, times=1)
            # event 2: the warm solve lands corrupted, the every-2nd-event
            # audit catches it in the same rebuild and self-heals
            import dataclasses

            dbs = build_adj_dbs(EDGES)
            db_b = dbs["g1_1"]
            db_b = dataclasses.replace(
                db_b,
                adjacencies=[
                    dataclasses.replace(adj, metric=4)
                    for adj in db_b.adjacencies
                ],
            )
            ls.update_adjacency_database(db_b)
            db2 = sup.build_route_db(me, states, ps)

        assert sup.counters["decision.spf.audit_runs"] == 1
        assert sup.counters["decision.spf.audit_mismatches"] >= 1
        assert sup.counters["decision.spf.audit_forced_cold_solves"] == 1
        assert any(
            s.get("event") == "WARM_STATE_AUDIT_MISMATCH" for s in samples
        )
        # the re-served routes are oracle-identical despite the corruption
        oracle = SpfSolver(me).build_route_db(me, states, ps)
        assert_route_db_equal(db2, oracle)
        # and the next solve's warm state is clean again
        db3 = sup.build_route_db(me, states, ps)
        assert_route_db_equal(db3, oracle)
        assert sup.counters["decision.spf.audit_mismatches"] >= 1

    def test_clean_audit_reports_nothing(self):
        sup = make_supervisor(audit_interval=1)
        for _ in range(3):
            sup.build_route_db(*solve_inputs())
        assert sup.counters["decision.spf.audit_runs"] == 3
        assert "decision.spf.audit_mismatches" not in sup.counters

    def test_audit_direct_on_solver(self):
        # the TpuSpfSolver-level audit API: detects a direct perturbation
        tpu = TpuSpfSolver("g0_0")
        me, states, ps = solve_inputs()
        tpu.build_route_db(me, states, ps)
        assert tpu.audit_warm_state() == []
        (_, solve), = tpu._solves.values()
        self._corrupt(solve)
        (record,) = tpu.audit_warm_state()
        assert record["entries"] == 1
        assert record["max_abs_delta"] == 3
        tpu.invalidate_warm_state()
        assert tpu._solves == {}
        assert tpu.counters["decision.spf.warm_state_invalidations"] == 1


class TestDecisionIntegration:
    def test_decision_tpu_backend_is_supervised_by_default(self):
        from openr_tpu.decision import Decision, DecisionConfig
        from openr_tpu.messaging import ReplicateQueue, RQueue, RWQueue

        decision = Decision(
            DecisionConfig(my_node_name="a", solver_backend="tpu"),
            RQueue(RWQueue()),
            ReplicateQueue(),
        )
        assert isinstance(decision.solver, SolverSupervisor)
        health = decision.get_solver_health()
        assert health["degraded"] is False
        assert health["breaker_state"] == CLOSED

    def test_decision_cpu_backend_reports_unsupervised(self):
        from openr_tpu.decision import Decision, DecisionConfig
        from openr_tpu.messaging import ReplicateQueue, RQueue, RWQueue

        decision = Decision(
            DecisionConfig(my_node_name="a", solver_backend="cpu"),
            RQueue(RWQueue()),
            ReplicateQueue(),
        )
        health = decision.get_solver_health()
        assert health["degraded"] is False
        assert health["breaker_state"] == "unsupervised"

    def test_supervisor_counters_reach_decision_counters(self):
        import asyncio

        from openr_tpu.decision import Decision, DecisionConfig
        from openr_tpu.messaging import ReplicateQueue, RQueue, RWQueue
        from openr_tpu.testing.decision_harness import lsdb_publication

        async def body():
            kv_q = RWQueue()
            decision = Decision(
                DecisionConfig(
                    my_node_name="g0_0",
                    solver_backend="tpu",
                    solver_failure_threshold=1,
                    solver_max_attempts=1,
                    debounce_min=0.005,
                    debounce_max=0.02,
                ),
                RQueue(kv_q),
                ReplicateQueue(),
            )
            decision.start()
            try:
                with injected() as inj:
                    inj.arm("solver.tpu.solve", times=1)
                    kv_q.push(
                        lsdb_publication(
                            build_adj_dbs(EDGES).values(), ANNOUNCERS
                        )
                    )
                    deadline = asyncio.get_event_loop().time() + 10.0
                    while not decision.have_computed_routes:
                        assert (
                            asyncio.get_event_loop().time() < deadline
                        ), "no routes"
                        await asyncio.sleep(0.005)
            finally:
                task = decision._task
                decision.stop()
                if task is not None:
                    await asyncio.gather(task, return_exceptions=True)
            # the degraded flag is visible through Decision's counter sync
            assert decision.counters["decision.spf.fallback_active"] == 1
            assert decision.counters["decision.spf.solver_failures"] == 1
            assert decision.get_solver_health()["degraded"] is True

        asyncio.new_event_loop().run_until_complete(body())
