"""Solver flight recorder (ISSUE 13): per-solve SolveTraces with phase
timing on every solve (ISSUE 26), bounded per-area rings with exact eviction accounting,
fault-forensics dumps wired into the supervisor's trip/mismatch/deadline
paths, the ctrl/breeze read surfaces, and the on-demand profiling window
— every degraded path driven by the deterministic fault injector."""

import asyncio
import json
import threading

import numpy as np
import pytest

from openr_tpu.ctrl import CtrlClient, CtrlServer
from openr_tpu.lsdb import LinkState, PrefixState
from openr_tpu.monitor import Monitor
from openr_tpu.monitor.profiling import ProfileController
from openr_tpu.solver import (
    SolverSupervisor,
    SpfSolver,
    SupervisorConfig,
    TpuSpfSolver,
)
from openr_tpu.solver.flight_recorder import (
    FlightRecorder,
    PhaseClock,
    SolveTrace,
)
from openr_tpu.testing.faults import FaultInjector, injected
from openr_tpu.topology import build_adj_dbs, grid_edges
from openr_tpu.types import IpPrefix, PrefixDatabase, PrefixEntry


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


EDGES = grid_edges(3)
ANNOUNCERS = {"g2_2": ["10.1.0.0/16"], "g0_2": ["10.2.0.0/16"]}


def solve_inputs():
    return "g0_0", {"0": build_ls(EDGES)}, make_prefix_state(ANNOUNCERS)


def make_supervisor(samples=None, **cfg_kw):
    return SolverSupervisor(
        TpuSpfSolver("g0_0"),
        SpfSolver("g0_0"),
        SupervisorConfig(**cfg_kw),
        log_sample_fn=(samples.append if samples is not None else None),
    )


def flap(link_state: LinkState, n: int, metric: int) -> None:
    """One weight event: bump a far-side link metric so the warm path
    serves it (no adjacency incident to g0_0 moves)."""
    import dataclasses

    db = build_adj_dbs(EDGES)["g2_1"]
    db = dataclasses.replace(
        db,
        adjacencies=[
            dataclasses.replace(adj, metric=metric)
            if adj.other_node_name == "g2_2"
            else adj
            for adj in db.adjacencies
        ],
    )
    link_state.update_adjacency_database(db)


# line topology for delta-extraction tests: a far-edge metric move MUST
# change the distance columns (no alternate path can absorb it)
LINE = [("a", "b", 1), ("b", "c", 1), ("c", "d", 1)]


def line_inputs():
    return (
        "a",
        {"0": build_ls(LINE)},
        make_prefix_state({"d": ["10.9.0.0/16"]}),
    )


def line_flap(link_state: LinkState, metric: int) -> None:
    import dataclasses

    db = build_adj_dbs(LINE)["c"]
    db = dataclasses.replace(
        db,
        adjacencies=[
            dataclasses.replace(adj, metric=metric)
            if adj.other_node_name == "d"
            else adj
            for adj in db.adjacencies
        ],
    )
    link_state.update_adjacency_database(db)


# ---------------------------------------------------------------------------
# ring semantics + eviction accounting
# ---------------------------------------------------------------------------


class TestRingSemantics:
    def test_eviction_accounting_invariant(self):
        """recorded == retained + evicted, exactly, across overflow."""
        rec = FlightRecorder(ring_size=4, node="n")
        for i in range(11):
            rec.record(_trace(rec, area="0"))
        for i in range(3):
            rec.record(_trace(rec, area="1"))
        stats = rec.stats()
        assert stats["recorded"] == 14
        assert stats["retained"] == 4 + 3
        assert stats["evicted"] == 7
        assert stats["recorded"] == stats["retained"] + stats["evicted"]
        # per-area rings: area 0 kept its newest ring_size seqs
        seqs = [t["seq"] for t in rec.snapshot(area="0")]
        assert seqs == sorted(seqs) and len(seqs) == 4
        assert seqs[0] == 8  # 11 recorded, 4 retained -> oldest is #8

    def test_snapshot_last_n_is_global_order(self):
        rec = FlightRecorder(ring_size=8)
        for area in ("0", "1", "0"):
            rec.record(_trace(rec, area=area))
        last = rec.snapshot(last_n=2)
        assert [t["seq"] for t in last] == [2, 3]

    def test_solver_ring_records_every_solve(self):
        sup = make_supervisor(trace_ring_size=2)
        me, states, ps = solve_inputs()
        sup.build_route_db(me, states, ps)
        for i in range(4):
            flap(states["0"], i, 20 + i)
            sup.build_route_db(me, states, ps)
        stats = sup.recorder.stats()
        assert stats["recorded"] == 5
        assert stats["retained"] == 2  # ring bound enforced
        assert stats["evicted"] == 3
        # the ring/eviction accounting rides the counter registry
        assert sup.counters["decision.spf.traces_recorded"] == 5
        assert sup.counters["decision.spf.traces_evicted"] == 3


def _trace(rec: FlightRecorder, area: str = "0") -> SolveTrace:
    return SolveTrace(
        seq=rec.next_seq(),
        ts=0.0,
        area=area,
        node="n",
        event="solve",
        layout="sell",
        warm=False,
        solve_ms=1.0,
        rounds=1,
        invalidation_rounds=None,
        halo_exchanges=None,
        h2d_bytes=0,
        d2h_bytes=0,
        halo_bytes=0,
        delta_columns=None,
        compile_cache_misses=0,
        breaker_state="closed",
    )


# ---------------------------------------------------------------------------
# phase timing: every solve, tiled, and no seam waits for the device
# ---------------------------------------------------------------------------

# the phases inside `solve_ms`: `refresh` comes before the solve's own
# clock starts, `d2h` (the lazy mirror fetch) after it stopped
IN_SOLVE = ("prepare", "h2d", "relax", "delta_extract", "mirror_patch")


def in_solve_ms(trace):
    return sum(trace["phases"].get(p, 0.0) for p in IN_SOLVE)


class TestPhaseTiming:
    def test_every_solve_records_phase_split(self):
        sup = make_supervisor()
        me, states, ps = solve_inputs()
        sup.build_route_db(me, states, ps)
        (trace,) = sup.recorder.snapshot()
        assert "sampled" not in trace  # there is no other kind of solve
        assert trace["event"] == "solve"
        assert trace["layout"] == "sell"
        assert trace["warm"] is False
        # the cold solve splits into prepare/h2d/relax, and the full
        # route build that followed fetched the mirror (d2h)
        assert {"prepare", "h2d", "relax", "d2h"} == set(trace["phases"])
        assert all(v >= 0.0 for v in trace["phases"].values())
        assert in_solve_ms(trace) == pytest.approx(
            trace["solve_ms"], rel=0.02, abs=0.05
        )
        # phase histograms reached the decision.spf.* registry
        for name in (
            "decision.spf.phase.prepare_ms",
            "decision.spf.phase.h2d_ms",
            "decision.spf.phase.relax_ms",
        ):
            assert sup.histograms[name].count >= 1, name

    def test_warm_solve_phases_include_delta_extract(self):
        sup = SolverSupervisor(
            TpuSpfSolver("a"), SpfSolver("a"), SupervisorConfig()
        )
        me, states, ps = line_inputs()
        sup.build_route_db(me, states, ps)
        line_flap(states["0"], 5)
        sup.build_route_db(me, states, ps)
        warm = [t for t in sup.recorder.snapshot() if t["warm"]]
        assert warm, sup.recorder.snapshot()
        trace = warm[-1]
        assert trace["invalidation_rounds"] is not None
        assert trace["delta_columns"] is not None
        assert list(trace["phases"]) == [
            "refresh", "prepare", "h2d", "relax", "delta_extract",
            "mirror_patch",
        ]
        for phase in trace["phases"]:
            name = f"decision.spf.phase.{phase}_ms"
            assert sup.histograms[name].count >= 1, name

    def test_no_seam_waits_for_the_device(self, monkeypatch):
        """The probe-effect contract, now for every solve: a phase seam is
        two clock reads and an annotation. `block_until_ready` is never
        called on the solve path, cold or warm, and a seam takes no value
        it could wait for."""
        import inspect

        import jax.numpy as jnp

        array_type = type(jnp.arange(2))
        real = array_type.block_until_ready
        waited = []
        monkeypatch.setattr(
            array_type,
            "block_until_ready",
            lambda self: (waited.append(1), real(self))[1],
        )
        jnp.arange(2).block_until_ready()
        assert waited == [1]  # the patch is what a barrier would reach
        sup = make_supervisor()
        me, states, ps = solve_inputs()
        sup.build_route_db(me, states, ps)
        for i in range(3):
            flap(states["0"], i, 40 + i)
            sup.build_route_db(me, states, ps)
        traces = sup.recorder.snapshot()
        assert [t["warm"] for t in traces] == [False, True, True, True]
        assert all(t["phases"] for t in traces)
        assert waited == [1]
        assert list(inspect.signature(PhaseClock.enter).parameters) == [
            "self", "phase",
        ]

    def test_phases_tile_the_solve(self):
        """On every solve the phases inside `solve_ms` add up to it: no
        stretch of the solve is outside a phase, none is in two."""
        sup = SolverSupervisor(
            TpuSpfSolver("a"), SpfSolver("a"), SupervisorConfig()
        )
        me, states, ps = line_inputs()
        sup.build_route_db(me, states, ps)
        for metric in (5, 2, 9, 4):
            line_flap(states["0"], metric)
            sup.build_route_db(me, states, ps)
        traces = sup.recorder.snapshot()
        assert len(traces) == 5 and all(t["warm"] for t in traces[1:])
        for t in traces:
            assert set(IN_SOLVE) >= set(t["phases"]) - {"refresh", "d2h"}
            # within 2 %, or 50 us where the solve is that short
            assert in_solve_ms(t) == pytest.approx(
                t["solve_ms"], rel=0.02, abs=0.05
            ), t
        for t in traces[1:]:
            assert "refresh" in t["phases"] and "d2h" not in t["phases"]

    def test_every_trace_in_ring_and_dump_carries_phases(self):
        sup = make_supervisor()
        me, states, ps = solve_inputs()
        sup.build_route_db(me, states, ps)
        for i in range(20):  # past any sampling cadence there ever was
            flap(states["0"], i, 30 + i)
            sup.build_route_db(me, states, ps)
        traces = sup.recorder.snapshot()
        assert len(traces) == 21
        assert all({"prepare", "h2d", "relax"} <= set(t["phases"]) for t in traces)
        stats = sup.recorder.stats()
        assert "sample_every" not in stats and "sampled_solves" not in stats
        assert "decision.spf.traces_sampled" not in sup.counters
        dump = sup.recorder.dump("test")
        dumped = [t for ts in dump["traces"].values() for t in ts]
        assert dumped and all(t["phases"] for t in dumped)
        # every solve fed the histograms, not one in sixteen
        assert sup.histograms["decision.spf.phase.relax_ms"].count == 21

    def test_phase_clock_enter_ends_the_phase_in_progress(self, monkeypatch):
        """PhaseClock alone, with the profiler's annotation replaced: each
        phase is one annotation named like its histogram without `_ms`,
        tagged with the build, and left before the next is entered."""
        from openr_tpu.monitor import spans

        log = []

        class FakeAnnotation:
            def __init__(self, name, **kwargs):
                self.name, self.kwargs = name, kwargs

            def __enter__(self):
                log.append(("enter", self.name, self.kwargs))

            def __exit__(self, *exc):
                log.append(("exit", self.name, self.kwargs))

        monkeypatch.setattr(spans, "TraceAnnotation", FakeAnnotation)
        clock = PhaseClock(build=7)
        clock.enter("prepare")
        clock.enter("h2d")
        clock.enter("h2d")  # entered again: the times add up
        clock.stop()
        clock.stop()  # idempotent
        assert list(clock.phases) == ["prepare", "h2d"]
        assert all(ms >= 0.0 for ms in clock.phases.values())
        assert [(what, name) for what, name, _ in log] == [
            ("enter", "decision.spf.phase.prepare"),
            ("exit", "decision.spf.phase.prepare"),
            ("enter", "decision.spf.phase.h2d"),
            ("exit", "decision.spf.phase.h2d"),
            ("enter", "decision.spf.phase.h2d"),
            ("exit", "decision.spf.phase.h2d"),
        ]
        assert all(kwargs == {"build": 7} for _, _, kwargs in log)


class TestDeviceSyncs:
    """`decision.spf.device_syncs` moves by the host reads that block on a
    device value: counted here from the code's own reads."""

    def test_cold_and_warm_delta_path_events(self):
        sup = SolverSupervisor(
            TpuSpfSolver("a"), SpfSolver("a"), SupervisorConfig()
        )
        me, states, ps = line_inputs()
        sup.build_route_db(me, states, ps)
        assert sup.recorder.snapshot()[-1]["layout"] == "sell"
        # cold: the round count, then the full route build's mirror fetch
        assert sup.counters["decision.spf.device_syncs"] == 2
        # the first poll after a cold solve has no delta; it re-arms
        assert sup.poll_device_delta(states) is None
        line_flap(states["0"], 5)
        assert sup.poll_device_delta(states) == {"d"}  # DeltaPath
        # warm: invalidation rounds, rounds, changed-column count, and the
        # three extracted arrays; the mirror is patched, not fetched
        assert sup.counters["decision.spf.device_syncs"] == 2 + 6

    def test_a_warm_event_that_moves_no_column_reads_three_scalars(self):
        sup = make_supervisor()
        me, states, ps = solve_inputs()
        sup.build_route_db(me, states, ps)
        assert sup.poll_device_delta(states) is None  # re-arms the delta
        before = sup.counters["decision.spf.device_syncs"]
        # the 3x3 grid absorbs this far-side metric move: no distance
        # from g0_0 changes, so nothing is extracted
        flap(states["0"], 0, 40)
        assert sup.poll_device_delta(states) == set()
        assert sup.counters["decision.spf.device_syncs"] == before + 3


# ---------------------------------------------------------------------------
# forensics dumps (the fault-domain integration)
# ---------------------------------------------------------------------------


class TestForensics:
    def test_breaker_trip_dump_reconstructs_timeline(self, tmp_path):
        """The acceptance path: a clean solve, then an injected
        solver.tpu.solve fault streak trips the breaker; the dump
        referenced from SOLVER_BREAKER_TRIPPED holds the last-N traces —
        the clean solve WITH its per-phase split plus the classified
        fault records — and round-trips through JSON."""
        samples = []
        sup = make_supervisor(
            samples=samples,
            failure_threshold=2,
            max_attempts=1,
            forensics_dir=str(tmp_path),
        )
        me, states, ps = solve_inputs()
        sup.build_route_db(me, states, ps)  # clean solve, traced
        with injected() as inj:
            inj.arm("solver.tpu.solve", times=None)
            flap(states["0"], 0, 50)
            sup.build_route_db(me, states, ps)
            flap(states["0"], 1, 51)
            sup.build_route_db(me, states, ps)
        assert sup.state != "closed"
        trip = next(
            s for s in samples
            if s.get("event") == "SOLVER_BREAKER_TRIPPED"
        )
        forensics_id = trip.get("forensics_id")
        assert forensics_id
        dumped = next(
            s for s in samples
            if s.get("event") == "SOLVER_FORENSICS_DUMPED"
        )
        assert dumped.get("forensics_id") == forensics_id
        dump = next(
            d for d in sup.recorder.dumps if d["id"] == forensics_id
        )
        assert dump["reason"] == "breaker_trip"
        # per-phase timeline of the solves that led to the trip: the
        # clean solve's phase split survives in the dump
        events = [
            t for ts in dump["traces"].values() for t in ts
        ]
        clean = [t for t in events if t["event"] == "solve"]
        faults = [t for t in events if t["event"] == "fault"]
        assert clean and faults
        assert {"prepare", "h2d", "relax"} <= set(clean[0]["phases"])
        assert all(f["fault_kind"] == "runtime" for f in faults)
        assert all(f["breaker_state"] == "closed" for f in faults)
        # context rides along: config + counters + degrade-safe digest
        assert dump["solver_config"]["failure_threshold"] == 2
        assert "decision.spf.solver_failures" in dump["counters"]
        assert "mesh_shape" in dump["mesh_digest"]
        # JSON round-trip, and the artifact landed on disk
        assert json.loads(json.dumps(dump, sort_keys=True))["id"] == (
            forensics_id
        )
        path = tmp_path / f"{forensics_id}.json"
        assert path.exists()
        on_disk = json.loads(path.read_text())
        assert on_disk["reason"] == "breaker_trip"
        assert on_disk["traces"].keys() == dump["traces"].keys()
        # counter + health surfaces
        assert sup.counters["decision.spf.forensics_dumps"] >= 1
        health = sup.health()
        assert health["forensics"]["last_id"] == forensics_id
        assert health["traces"]["recorded"] == sup.recorder.recorded

    def test_deadline_overrun_dumps(self):
        samples = []
        sup = make_supervisor(
            samples=samples,
            solve_deadline_s=0.0,  # every real solve overruns
            failure_threshold=100,
        )
        me, states, ps = solve_inputs()
        db = sup.build_route_db(me, states, ps)
        assert db is not None  # slow-but-correct still serves
        assert sup.recorder.last_dump_reason == "deadline"
        assert any(
            s.get("event") == "SOLVER_FORENSICS_DUMPED"
            and s.get("reason") == "deadline"
            for s in samples
        )

    def test_audit_mismatch_dump_references_id(self):
        samples = []
        sup = make_supervisor(samples=samples, audit_interval=1)
        me, states, ps = solve_inputs()

        def corrupt(solve):
            solve.d  # materialize the host mirror
            solve._d_host[0, 1] += 7

        with injected(FaultInjector()) as inj:
            inj.arm("solver.tpu.warm_d", times=1, action=corrupt)
            sup.build_route_db(me, states, ps)
        mism = next(
            s for s in samples
            if s.get("event") == "WARM_STATE_AUDIT_MISMATCH"
        )
        assert mism.get("forensics_id")
        assert sup.recorder.last_dump_reason == "audit_mismatch"

    def test_dump_index_is_bounded(self):
        rec = FlightRecorder(max_dumps=2)
        ids = [rec.dump(f"r{i}")["id"] for i in range(5)]
        assert [d["id"] for d in rec.dumps] == ids[-2:]
        assert rec.forensics_stats()["dumps"] == 5


# ---------------------------------------------------------------------------
# ctrl + breeze + metrics surfaces
# ---------------------------------------------------------------------------


def run(coro, timeout=15.0):
    async def body():
        return await asyncio.wait_for(coro, timeout)

    return asyncio.new_event_loop().run_until_complete(body())


class _RecorderDecision:
    """Decision stand-in delegating the flight-recorder surfaces to a
    real supervised solver (the shapes the ctrl server serializes)."""

    def __init__(self, sup):
        self.sup = sup

    def get_solver_health(self):
        return self.sup.health()

    def get_solve_traces(self, area=None, last_n=None):
        rec = self.sup.recorder
        return {
            "enabled": True,
            "traces": rec.snapshot(area=area, last_n=last_n),
            "stats": rec.stats(),
            "forensics": rec.dump_summaries(),
        }


class TestCtrlSurfaces:
    def _sup_with_history(self):
        sup = make_supervisor()
        me, states, ps = solve_inputs()
        sup.build_route_db(me, states, ps)
        flap(states["0"], 0, 60)
        sup.build_route_db(me, states, ps)
        return sup

    def test_get_solve_traces_over_the_wire(self):
        sup = self._sup_with_history()

        async def body():
            server = CtrlServer(
                "n1", port=0, decision=_RecorderDecision(sup)
            )
            port = await server.start()
            client = await CtrlClient("127.0.0.1", port).connect()
            report = await client.call("getSolveTraces", last_n=1)
            assert report["enabled"] is True
            assert len(report["traces"]) == 1
            assert report["traces"][0]["warm"] is True
            assert report["stats"]["recorded"] == 2
            health = await client.call("getSolverHealth")
            assert health["solve_ms_last"] is not None
            assert health["traces"]["recorded"] == 2
            assert "forensics" in health
            await client.close()
            await server.stop()

        run(body())

    def test_phase_histograms_ride_get_metrics(self):
        sup = self._sup_with_history()

        async def body():
            monitor = Monitor("n1")
            monitor.register_module("decision", sup)
            server = CtrlServer("n1", port=0, monitor=monitor)
            port = await server.start()
            client = await CtrlClient("127.0.0.1", port).connect()
            text = await client.call("getMetricsText")
            assert "openr_decision_spf_phase_relax_ms_count" in text
            assert "openr_decision_spf_phase_h2d_ms_count" in text
            assert "openr_decision_spf_traces_recorded" in text
            # the same bytes over the plain HTTP scrape handler
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", port
            )
            writer.write(b"GET /metrics HTTP/1.0\r\n\r\n")
            await writer.drain()
            raw = await reader.read()
            writer.close()
            assert b"openr_decision_spf_phase_relax_ms_count" in raw
            await client.close()
            await server.stop()

        run(body())

    def test_decision_get_solve_traces_disabled_without_recorder(self):
        from openr_tpu.decision import Decision, DecisionConfig
        from openr_tpu.messaging import ReplicateQueue

        decision = Decision(
            DecisionConfig(my_node_name="n1", solver_backend="cpu"),
            ReplicateQueue().get_reader(),
            ReplicateQueue(),
        )
        report = decision.get_solve_traces()
        assert report["enabled"] is False and report["traces"] == []
        health = decision.get_solver_health()
        assert health["breaker_state"] == "unsupervised"
        assert "solve_ms_last" in health

    def test_start_profile_is_admission_guarded(self):
        from openr_tpu.streaming import AdmissionController

        assert AdmissionController().guards("startProfile")


class TestBreezeCli:
    @pytest.fixture
    def ctrl_endpoint(self):
        started = threading.Event()
        state = {}
        sup = make_supervisor()
        me, states, ps = solve_inputs()
        sup.build_route_db(me, states, ps)
        sup.recorder.dump("breaker_trip")

        def run_server():
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            server = CtrlServer(
                "cli-node", port=0, decision=_RecorderDecision(sup)
            )
            state["loop"] = loop
            state["port"] = loop.run_until_complete(server.start())
            started.set()
            loop.run_forever()
            loop.run_until_complete(server.stop())
            loop.close()

        thread = threading.Thread(target=run_server, daemon=True)
        thread.start()
        assert started.wait(10)
        yield "127.0.0.1", state["port"]
        state["loop"].call_soon_threadsafe(state["loop"].stop)
        thread.join(timeout=10)

    def test_solve_traces_renders_table(self, ctrl_endpoint, capsys):
        from openr_tpu.cli.breeze import main as breeze_main

        host, port = ctrl_endpoint
        rc = breeze_main(
            ["--host", host, "--port", str(port),
             "decision", "solve-traces"]
        )
        assert rc == 0
        out = capsys.readouterr().out
        assert "flight recorder: 1 recorded" in out
        assert "sell" in out or "bf" in out
        assert "forensics dumps:" in out
        assert "breaker_trip" in out

    def test_solve_traces_json(self, ctrl_endpoint, capsys):
        from openr_tpu.cli.breeze import main as breeze_main

        host, port = ctrl_endpoint
        rc = breeze_main(
            ["--host", host, "--port", str(port),
             "decision", "solve-traces", "--json"]
        )
        assert rc == 0
        data = json.loads(capsys.readouterr().out)
        assert data["enabled"] is True
        assert data["stats"]["recorded"] == 1

    def test_profile_window_over_the_wire(
        self, ctrl_endpoint, capsys, tmp_path, monkeypatch
    ):
        # the ctrl server runs in-process: stub the profiler backend so
        # this test pins the RPC/CLI plumbing without paying a real
        # capture's process-wide RSS (the real backend is exercised in a
        # subprocess by TestProfileController)
        import jax

        calls = []
        monkeypatch.setattr(
            jax.profiler, "start_trace", lambda d: calls.append(d)
        )
        monkeypatch.setattr(
            jax.profiler, "stop_trace", lambda: calls.append("stop")
        )
        from openr_tpu.cli.breeze import main as breeze_main

        host, port = ctrl_endpoint
        out_dir = str(tmp_path / "prof")
        rc = breeze_main(
            ["--host", host, "--port", str(port), "decision",
             "profile", "--seconds", "0.2", "--out", out_dir]
        )
        assert rc == 0
        out = capsys.readouterr().out
        assert "profiling window open" in out
        assert calls == [out_dir]
        # drain the window past its deadline so the status poll closes it
        # (the bounded-window contract over the wire)
        import time as _time

        _time.sleep(0.35)
        rc = breeze_main(
            ["--host", host, "--port", str(port),
             "decision", "profile-status"]
        )
        assert rc == 0
        status = json.loads(capsys.readouterr().out)
        assert status["active"] is False  # bounded: the window closed
        assert status["windows"] == 1
        assert calls == [out_dir, "stop"]


# ---------------------------------------------------------------------------
# profiling window state machine
# ---------------------------------------------------------------------------


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestProfileController:
    def test_window_is_bounded_and_single_flight(
        self, tmp_path, monkeypatch
    ):
        calls = []
        import jax

        monkeypatch.setattr(
            jax.profiler, "start_trace", lambda d: calls.append(("start", d))
        )
        monkeypatch.setattr(
            jax.profiler, "stop_trace", lambda: calls.append(("stop",))
        )
        clock = _FakeClock()
        ctl = ProfileController(clock=clock)
        out = str(tmp_path / "prof")
        status = ctl.start(out_dir=out, seconds=2.0)
        assert status["started"] is True and status["active"] is True
        # second start refused while active
        again = ctl.start(out_dir=out, seconds=2.0)
        assert again["started"] is False
        assert "already active" in again["error"]
        # deadline passes: any status poll closes the window
        clock.t = 2.5
        status = ctl.status()
        assert status["active"] is False
        assert calls == [("start", out), ("stop",)]
        # a fresh window may start now
        assert ctl.start(out_dir=out, seconds=1.0)["started"] is True

    def test_duration_clamped(self, tmp_path, monkeypatch):
        import jax

        monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        ctl = ProfileController(clock=_FakeClock())
        status = ctl.start(out_dir=str(tmp_path), seconds=10_000)
        assert status["seconds"] == 600.0

    def test_degrade_safe_when_profiler_unavailable(
        self, tmp_path, monkeypatch
    ):
        import jax

        def boom(_):
            raise RuntimeError("profiler backend unavailable")

        monkeypatch.setattr(jax.profiler, "start_trace", boom)
        ctl = ProfileController()
        status = ctl.start(out_dir=str(tmp_path), seconds=1.0)
        assert status["started"] is False
        assert "unavailable" in status["error"]
        assert ctl.status()["active"] is False
        assert "unavailable" in ctl.status()["last_error"]

    def test_real_cpu_window_writes_trace_dir(self, tmp_path):
        """Degrade-safe contract on the real CPU backend: a tiny window
        either captures a TensorBoard dir or reports in-band. Runs in a
        SUBPROCESS: a real profiler capture permanently grows process
        RSS, which would poison the watchdog memory-limit tests sharing
        this pytest process."""
        import subprocess
        import sys

        out = str(tmp_path / "prof")
        script = (
            "import os; os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "import json, sys\n"
            "from openr_tpu.monitor.profiling import ProfileController\n"
            "import jax.numpy as jnp\n"
            f"ctl = ProfileController()\n"
            f"status = ctl.start(out_dir={out!r}, seconds=30.0)\n"
            "if not status['started']:\n"
            "    assert status['error']  # reported, not raised\n"
            "    print(json.dumps({'captured': False})); sys.exit(0)\n"
            "(jnp.arange(16) * 3).block_until_ready()\n"
            "ctl.stop()\n"
            "assert ctl.status()['active'] is False\n"
            f"assert os.path.isdir({out!r})\n"
            "print(json.dumps({'captured': True}))\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True,
            timeout=240,
            text=True,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        result = json.loads(proc.stdout.strip().splitlines()[-1])
        assert result["captured"] in (True, False)
