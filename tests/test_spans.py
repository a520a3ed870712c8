"""Convergence span tests: Span primitive semantics, monotonic-clock
immunity to wall-clock jumps, and the full KvStore→Decision→Fib trace pass
(ISSUE 2 acceptance: non-zero decision.spf.solve_ms and convergence.e2e_ms
after a link-flap sequence, warm vs cold solves distinguishable,
invalidation rounds populated on an increase event)."""

import asyncio
import time

from openr_tpu.monitor import SPAN_EVENT, Span
from openr_tpu.testing.decision_harness import (
    lsdb_publication,
    run_convergence_trace,
)
from openr_tpu.topology import build_adj_dbs, grid_edges
from openr_tpu.types import Value, adj_key
from openr_tpu.utils import serializer


def run(coro, timeout=120.0):
    async def body():
        return await asyncio.wait_for(coro, timeout)

    return asyncio.new_event_loop().run_until_complete(body())


class TestSpan:
    def test_marks_accumulate_stage_durations(self):
        span = Span("convergence")
        first = span.mark("decision.recv")
        second = span.mark("decision.debounce")
        assert first >= 0.0 and second >= 0.0
        durations = span.stage_durations_ms()
        assert list(durations) == ["decision.recv", "decision.debounce"]
        assert span.elapsed_ms() >= first + second

    def test_seeded_t0_predates_first_mark(self):
        t0 = time.monotonic() - 0.050
        span = Span("convergence", t0=t0)
        ms = span.mark("decision.recv")
        assert ms >= 50.0

    def test_wall_clock_jump_does_not_skew(self, monkeypatch):
        """Satellite: spans run on time.monotonic — a wall-clock step
        (NTP, manual date set) between marks must not leak into stage
        durations or e2e."""
        span = Span("convergence")
        monkeypatch.setattr(time, "time", lambda: 4e9)  # jump ~100 years
        ms = span.mark("decision.recv")
        assert ms < 10_000.0
        assert span.elapsed_ms() < 10_000.0

    def test_to_log_sample(self):
        span = Span("convergence")
        span.mark("decision.recv")
        span.mark("fib.program")
        sample = span.to_log_sample()
        assert sample.get("event") == SPAN_EVENT
        assert sample.get("span") == "convergence"
        assert sample.get("decision.recv_ms") >= 0.0
        assert sample.get("fib.program_ms") >= 0.0
        assert sample.get("total_ms") >= 0.0

    def test_explicit_ts_replays_past_marks(self):
        t0 = time.monotonic() - 0.100
        span = Span("convergence", t0=t0)
        first = span.mark("spark.neighbor_event", ts=t0)
        mid = span.mark("linkmonitor.adj_advertised", ts=t0 + 0.040)
        last = span.mark("kvstore.publish")
        assert first == 0.0
        assert 39.0 <= mid <= 41.0
        assert last >= 55.0  # ~60ms of real elapsed time remain

    def test_out_of_order_ts_clamps_to_previous_mark(self):
        span = Span("convergence")
        span.mark("a")
        behind = span.mark("b", ts=span.marks[0][1] - 1.0)
        assert behind == 0.0
        durations = span.stage_durations_ms()
        assert durations["b"] == 0.0
        assert span.marks[1][1] == span.marks[0][1]


class TestSpanSeeding:
    """Decision's span construction from pre-publish stages: exact
    monotonic span_stages on the origin node, wall-clock reconstruction
    (origin PerfEvents + flood hop trace) on remote nodes."""

    def _stages(self, span):
        return [stage for stage, _ in span.marks]

    def test_local_span_stages_prefix_the_span(self):
        from openr_tpu.decision.decision import _build_span
        from openr_tpu.types import Publication

        now = time.monotonic()
        pub = Publication(
            ts_monotonic=now,
            span_stages=[
                ("spark.neighbor_event", now - 0.050),
                ("linkmonitor.adj_advertised", now - 0.020),
            ],
        )
        span = _build_span(None, pub)
        assert self._stages(span) == [
            "spark.neighbor_event",
            "linkmonitor.adj_advertised",
            "kvstore.publish",
        ]
        durations = span.stage_durations_ms()
        assert durations["spark.neighbor_event"] == 0.0  # == t0
        assert 29.0 <= durations["linkmonitor.adj_advertised"] <= 31.0
        assert 19.0 <= durations["kvstore.publish"] <= 21.0

    def test_remote_span_reconstructed_from_wall_clock_traces(self):
        from openr_tpu.decision.decision import _build_span
        from openr_tpu.kvstore.store import (
            FLOOD_ORIGINATED_EVENT,
            FLOOD_RECEIVED_EVENT,
        )
        from openr_tpu.types import PerfEvent, PerfEvents, Publication

        now_wall = time.time() * 1e3
        value_perf = PerfEvents(
            [
                PerfEvent("n1", "NEIGHBOR_EVENT_RECVD", now_wall - 50.0),
                PerfEvent("n1", "ADJ_DB_ADVERTISED", now_wall - 40.0),
            ]
        )
        flood = PerfEvents(
            [
                PerfEvent("n1", FLOOD_ORIGINATED_EVENT, now_wall - 30.0),
                PerfEvent("n2", FLOOD_RECEIVED_EVENT, now_wall - 20.0),
                PerfEvent("n3", FLOOD_RECEIVED_EVENT, now_wall - 10.0),
            ]
        )
        pub = Publication(
            ts_monotonic=time.monotonic(), perf_events=flood
        )
        span = _build_span(value_perf, pub)
        assert self._stages(span) == [
            "spark.neighbor_event",
            "linkmonitor.adj_advertised",
            "kvstore.flood.origin",
            "kvstore.flood.hop1",
            "kvstore.flood.hop2",
            "kvstore.publish",
        ]
        durations = span.stage_durations_ms()
        # the 10ms wall-clock gaps survive the monotonic reconstruction
        for stage in (
            "linkmonitor.adj_advertised",
            "kvstore.flood.origin",
            "kvstore.flood.hop1",
            "kvstore.flood.hop2",
        ):
            assert 8.0 <= durations[stage] <= 12.0, (stage, durations)
        assert span.elapsed_ms() >= 45.0

    def test_no_stages_falls_back_to_publish_stamp(self):
        from openr_tpu.decision.decision import _build_span
        from openr_tpu.types import Publication

        now = time.monotonic()
        span = _build_span(None, Publication(ts_monotonic=now))
        assert self._stages(span) == ["kvstore.publish"]
        assert span.t0 == now


def _flap_publication(edges, metric, nodes=("g0_0", "g0_1"), version=2):
    """Publication re-announcing `nodes` adj dbs with the (g0_0, g0_1)
    link's metric set to `metric`."""
    flapped = [
        (a, b, metric) if {a, b} == {"g0_0", "g0_1"} else (a, b, m)
        for a, b, m in edges
    ]
    dbs = build_adj_dbs(flapped)
    pub = lsdb_publication([])
    for node in nodes:
        pub.key_vals[adj_key(node)] = Value(
            version, node, serializer.dumps(dbs[node])
        )
    return pub


class TestConvergenceTracePass:
    """Cold ingest + metric increase/decrease/increase flaps through the
    full Decision(tpu)→Fib pipeline, observability asserted end to end."""

    def _run(self):
        edges = grid_edges(4)
        base = lsdb_publication(
            build_adj_dbs(edges).values(), {"g3_3": ["10.0.0.0/24"]}
        )
        # increase → decrease → increase; the last event is an increase so
        # the invalidation_rounds_last gauge reflects a mark fixpoint run
        # (a decrease correctly writes 0 — its inc_idx is empty)
        flaps = [
            _flap_publication(edges, 5, version=2),
            _flap_publication(edges, 1, version=3),
            _flap_publication(edges, 7, version=4),
        ]
        return run(run_convergence_trace("g0_0", [base, *flaps]))

    def test_link_flap_sequence_histograms_and_counters(self):
        monitor, decision, fib = self._run()
        hists = monitor.get_histograms()

        # acceptance: non-zero solve + e2e latency distributions
        solve = hists["decision.spf.solve_ms"]
        assert solve["count"] >= 4
        assert solve["p50"] > 0.0 and solve["p99"] > 0.0
        e2e = hists["convergence.e2e_ms"]
        assert e2e["count"] == 4
        assert e2e["p50"] > 0.0 and e2e["p99"] > 0.0

        # warm vs cold solves distinguishable: one cold ingest, three warm
        # weight-patch flaps
        assert hists["decision.spf.solve_cold_ms"]["count"] >= 1
        assert hists["decision.spf.solve_warm_ms"]["count"] >= 3

        # per-stage histograms populated once per debounced rebuild
        assert hists["decision.debounce_ms"]["count"] == 4
        assert hists["decision.route_build_ms"]["count"] == 4
        assert hists["fib.program_ms"]["count"] == 4

        counters = monitor.get_counters()
        assert counters["decision.spf.incremental_solves"] == 3
        # the increase event ran the boolean invalidation-mark fixpoint
        assert counters["decision.spf.invalidation_rounds_last"] >= 1
        assert counters["decision.spf.rounds_last"] >= 1
        # profiling: traffic crossed the host-device link both ways and
        # the executable cache compiled at least the cold + warm solvers
        assert counters["decision.spf.host_to_device_bytes"] > 0
        assert counters["decision.spf.device_to_host_bytes"] > 0
        assert counters["decision.spf.compile_cache_misses"] >= 1
        assert counters["fib.convergence_spans"] == 4

    def test_span_log_samples_reach_monitor(self):
        monitor, decision, fib = self._run()
        traces = [
            s
            for s in monitor.get_event_logs()
            if s.get("event") == SPAN_EVENT
        ]
        assert len(traces) == 4
        for sample in traces:
            # the full stage chain is present and non-negative
            for stage in (
                "decision.recv_ms",
                "decision.debounce_ms",
                "decision.route_build_ms",
                "fib.recv_ms",
                "fib.program_ms",
                "total_ms",
            ):
                assert sample.get(stage) is not None, stage
                assert sample.get(stage) >= 0.0, stage
            # debounce waited at least roughly the configured minimum
            assert sample.get("total_ms") >= sample.get(
                "decision.debounce_ms"
            )
            # node_name auto-filled by the monitor drain
            assert sample.get("node_name") == "g0_0"


class TestCpuBackendSolveHistogram:
    """The CPU oracle backend reports decision.spf.solve_ms too, so the
    observability surface does not depend on the device backend."""

    def test_cpu_solver_times_spf(self):
        edges = grid_edges(3)
        base = lsdb_publication(
            build_adj_dbs(edges).values(), {"g2_2": ["10.1.0.0/24"]}
        )
        monitor, decision, fib = run(
            run_convergence_trace("g0_0", [base], backend="cpu")
        )
        hists = monitor.get_histograms()
        assert hists["decision.spf.solve_ms"]["count"] >= 1
        assert hists["convergence.e2e_ms"]["count"] == 1


class TestFullBuildStage:
    """ISSUE 30: `decision.full_build` is the host's work of a full route
    build. The poll before it resolves the area (refresh, solve, the
    whole mirror's fetch, each a phase of its own), so the stage holds no
    `decision.spf.phase.*` stage; a delta build does not enter it."""

    def _builds(self, monkeypatch):
        import dataclasses

        from openr_tpu.lsdb import LinkState, PrefixState
        from openr_tpu.monitor import spans
        from openr_tpu.solver import (
            DeltaRouteBuilder,
            SolverSupervisor,
            SpfSolver,
            SupervisorConfig,
            TpuSpfSolver,
        )
        from openr_tpu.types import IpPrefix, PrefixDatabase, PrefixEntry

        log = []

        class Recorded:
            def __init__(self, name, **kwargs):
                self.name = name

            def __enter__(self):
                log.append(("enter", self.name))

            def __exit__(self, *exc):
                log.append(("exit", self.name))

        monkeypatch.setattr(spans, "TraceAnnotation", Recorded)
        me, side = "g0_0", 5
        dbs = build_adj_dbs(grid_edges(side))
        ls = LinkState("0")
        ps = PrefixState()
        for i, (node, db) in enumerate(sorted(dbs.items())):
            ls.update_adjacency_database(db)
            ps.update_prefix_database(
                PrefixDatabase(
                    node, [PrefixEntry(IpPrefix(f"10.{i}.0.0/16"))], area="0"
                )
            )
        sup = SolverSupervisor(
            TpuSpfSolver(me), SpfSolver(me), SupervisorConfig()
        )
        hists = {}
        builder = DeltaRouteBuilder(sup, hists)
        als = {"0": ls}

        def move(a, b, metric):
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

        out = []
        db = None
        for k, event in enumerate(("first table", "far link", "own link")):
            if event == "far link":
                move("g3_4", "g4_4", 7)
                move("g4_3", "g4_4", 7)
            elif event == "own link":  # incident to me: no device delta
                move("g0_0", "g0_1", 4)
            del log[:]
            db, _, used = builder.build(me, als, ps, db, build=k + 1)
            out.append((used, list(log)))
        return out, hists

    def test_once_per_full_build_never_on_a_delta_build_no_phase_inside(
        self, monkeypatch
    ):
        builds, hists = self._builds(monkeypatch)
        assert [used for used, _ in builds] == [False, True, False]
        for used, log in builds:
            names = [name for what, name in log if what == "enter"]
            # stages tile: each is left before the next is entered
            assert [what for what, _ in log] == ["enter", "exit"] * len(names)
            if used:
                assert "decision.full_build" not in names
                assert names[-1] == "decision.delta_build"
                continue
            # the solve's phases and the mirror's fetch, then the build
            assert names.count("decision.full_build") == 1
            assert names[-1] == "decision.full_build"
            assert names[-2] == "decision.spf.phase.d2h"
            assert all(n.startswith("decision.spf.phase.") for n in names[:-1])
        assert hists["decision.full_build_ms"].count == 2
        assert hists["decision.delta_build_ms"].count == 1
