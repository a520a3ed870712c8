"""Shared Decision parity harness.

One implementation of "feed the same publication to Decision(backend=X) and
Decision(backend=Y), compare the emitted route deltas" used by both the
driver dry-run (__graft_entry__._dryrun_daemon_path) and the test suite
(tests/test_tpu_solver_mesh.py) — so Decision startup/shutdown or
Publication-shape changes have one place to land.
"""

from __future__ import annotations

import asyncio
import time
from typing import Iterable, List, Optional, Tuple

from openr_tpu.decision import Decision, DecisionConfig
from openr_tpu.messaging import ReplicateQueue, RQueue, RWQueue
from openr_tpu.types import (
    IpPrefix,
    PrefixDatabase,
    PrefixEntry,
    Publication,
    Value,
    adj_key,
    prefix_key,
)
from openr_tpu.utils import serializer


def lsdb_publication(
    adj_dbs: Iterable, announcers: Optional[dict] = None, area: str = "0"
) -> Publication:
    """One KvStore publication carrying full adjacency databases plus
    per-node prefix announcements ({node: [prefix_str, ...]})."""
    pub = Publication(area=area)
    for db in adj_dbs:
        pub.key_vals[adj_key(db.this_node_name)] = Value(
            1, db.this_node_name, serializer.dumps(db)
        )
    for node, pfxs in (announcers or {}).items():
        pdb = PrefixDatabase(
            node, [PrefixEntry(IpPrefix(p)) for p in pfxs]
        )
        pub.key_vals[prefix_key(node)] = Value(
            1, node, serializer.dumps(pdb)
        )
    return pub


async def decision_route_delta(
    my_node: str,
    publication: Publication,
    backend: str,
    mesh: Optional[tuple] = None,
    timeout: float = 30.0,
):
    """Boot a Decision, push one publication, await + return the emitted
    route delta, and shut the module down cleanly (task awaited)."""
    kv_q: RWQueue = RWQueue()
    route_q: ReplicateQueue = ReplicateQueue()
    decision = Decision(
        DecisionConfig(
            my_node_name=my_node,
            solver_backend=backend,
            solver_mesh=mesh,
            debounce_min=0.005,
            debounce_max=0.02,
        ),
        RQueue(kv_q),
        route_q,
    )
    reader = route_q.get_reader()
    decision.start()
    try:
        kv_q.push(publication)
        return await asyncio.wait_for(reader.get(), timeout)
    finally:
        task = decision._task
        decision.stop()
        if task is not None:
            await asyncio.gather(task, return_exceptions=True)


def assert_route_delta_equal(a, b) -> Tuple[int, int]:
    """Compare two DecisionRouteUpdates; returns (n_unicast, n_mpls)."""
    a_uni = {e.prefix: e for e in a.unicast_routes_to_update}
    b_uni = {e.prefix: e for e in b.unicast_routes_to_update}
    assert a_uni == b_uni, "unicast route delta mismatch"
    a_mpls = {e.label: e for e in a.mpls_routes_to_update}
    b_mpls = {e.label: e for e in b.mpls_routes_to_update}
    assert a_mpls == b_mpls, "mpls route delta mismatch"
    assert sorted(a.unicast_routes_to_delete) == sorted(
        b.unicast_routes_to_delete
    )
    assert sorted(a.mpls_routes_to_delete) == sorted(b.mpls_routes_to_delete)
    return len(a_uni), len(a_mpls)


async def run_convergence_trace(
    my_node: str,
    publications: Iterable[Publication],
    backend: str = "tpu",
    mesh: Optional[tuple] = None,
    timeout: float = 30.0,
):
    """Full KvStore→Decision→Fib observability pass.

    Boots Decision(backend) and a dryrun Fib wired by the route queue plus
    a Monitor aggregating both (the daemon's registration layout), stamps
    and pushes each publication the way KvStore.flood_publication does, and
    waits for Fib to close that event's convergence span before pushing the
    next — each publication MUST change routes or this times out. Returns
    (monitor, decision, fib) with the modules stopped but their counters,
    histograms and the monitor's event-log ring intact for assertions.
    """
    from openr_tpu.fib import Fib, FibConfig
    from openr_tpu.monitor import Monitor
    from openr_tpu.platform import MockFibHandler

    kv_q: RWQueue = RWQueue()
    route_q: ReplicateQueue = ReplicateQueue()
    log_q: ReplicateQueue = ReplicateQueue()
    decision = Decision(
        DecisionConfig(
            my_node_name=my_node,
            solver_backend=backend,
            solver_mesh=mesh,
            debounce_min=0.005,
            debounce_max=0.02,
        ),
        RQueue(kv_q),
        route_q,
    )
    fib = Fib(
        FibConfig(my_node_name=my_node, dryrun=True, cold_start_duration=0.0),
        MockFibHandler(),
        route_q.get_reader(),
        log_sample_fn=log_q.push,
    )
    monitor = Monitor(my_node, log_q.get_reader())
    monitor.register_module("decision", decision)
    monitor.register_module("fib", fib)
    monitor.start()
    decision.start()
    fib.start()
    loop = asyncio.get_running_loop()
    try:
        done = 0
        for pub in publications:
            pub.ts_monotonic = time.monotonic()
            kv_q.push(pub)
            done += 1
            deadline = loop.time() + timeout
            while (
                fib.histograms.get("convergence.e2e_ms") is None
                or fib.histograms["convergence.e2e_ms"].count < done
            ):
                if loop.time() > deadline:
                    raise TimeoutError(
                        f"publication {done} produced no convergence span"
                    )
                await asyncio.sleep(0.005)
        # let the monitor drain the emitted CONVERGENCE_TRACE samples
        deadline = loop.time() + timeout
        while len(monitor.get_event_logs()) < done:
            if loop.time() > deadline:
                raise TimeoutError("monitor did not drain span log samples")
            await asyncio.sleep(0.005)
    finally:
        tasks: List[asyncio.Task] = [
            t for t in (decision._task, *fib._tasks) if t is not None
        ]
        fib.stop()
        decision.stop()
        monitor.stop()
        if tasks:
            await asyncio.gather(*tasks, return_exceptions=True)
    return monitor, decision, fib


def _fib_table(handler) -> dict:
    """dest -> frozenset of (address, iface) actually programmed."""
    from openr_tpu.platform import FIB_CLIENT_OPENR

    return {
        dest: frozenset((nh.address, nh.iface) for nh in route.nexthops)
        for dest, route in handler.unicast_routes.get(
            FIB_CLIENT_OPENR, {}
        ).items()
    }


def run_fault_smoke() -> dict:
    """FAULT_SMOKE tier-1 smoke: a short Decision(tpu)→Fib flap sequence
    with one injected solver failure and one injected fib-program failure,
    asserting convergence completes DEGRADED — the supervised tpu stack's
    programmed FIB stays identical to an unfaulted CPU-oracle stack fed
    the same publications, while the breaker serves from the fallback and
    Fib recovers through its dirty-marking + full-resync path.

    Topology size comes from FAULT_SMOKE_SIDE (grid side, default 3) so CI
    can scale it; returns a summary dict of the degraded-path evidence.
    """
    import os

    from openr_tpu.fib import Fib, FibConfig
    from openr_tpu.platform import MockFibHandler
    from openr_tpu.testing.faults import FaultInjector, injected
    from openr_tpu.topology import build_adj_dbs, grid_edges

    side = int(os.environ.get("FAULT_SMOKE_SIDE", "3"))
    edges = grid_edges(side)
    far = f"g{side - 1}_{side - 1}"
    announcers = {far: ["10.1.0.0/24"], f"g0_{side - 1}": ["10.2.0.0/24"]}

    def build_stack(backend, handler, **decision_kw):
        kv_q: RWQueue = RWQueue()
        route_q: ReplicateQueue = ReplicateQueue()
        decision = Decision(
            DecisionConfig(
                my_node_name="g0_0",
                solver_backend=backend,
                debounce_min=0.005,
                debounce_max=0.02,
                **decision_kw,
            ),
            RQueue(kv_q),
            route_q,
        )
        fib = Fib(
            FibConfig(
                my_node_name="g0_0",
                dryrun=False,
                cold_start_duration=0.0,
                backoff_min=0.002,
                backoff_max=0.05,
                backoff_seed=0,
            ),
            handler,
            route_q.get_reader(),
        )
        return kv_q, decision, fib

    async def body() -> dict:
        tpu_handler = MockFibHandler()
        cpu_handler = MockFibHandler()
        # one injected solver failure with failure_threshold=1: the very
        # first device solve trips the breaker and the event converges
        # via the CPU fallback — degraded, never wrong
        kv_tpu, dec_tpu, fib_tpu = build_stack(
            "tpu",
            tpu_handler,
            solver_failure_threshold=1,
            solver_max_attempts=1,
            solver_probe_interval_s=3600.0,  # no probe flips mid-smoke
        )
        kv_cpu, dec_cpu, fib_cpu = build_stack("cpu", cpu_handler)

        with injected(FaultInjector(seed=1)) as inj:
            inj.arm("solver.tpu.solve", times=1)
            inj.arm(
                "fib.program",
                times=1,
                when=lambda ctx: ctx is fib_tpu,  # spare the oracle stack
            )
            for module in (dec_tpu, fib_tpu, dec_cpu, fib_cpu):
                module.start()
            loop = asyncio.get_running_loop()

            async def converge(timeout=20.0):
                deadline = loop.time() + timeout
                while True:
                    t_tpu, t_cpu = _fib_table(tpu_handler), _fib_table(
                        cpu_handler
                    )
                    if (
                        t_tpu
                        and t_tpu == t_cpu
                        and fib_tpu.has_synced_fib
                        and not fib_tpu._sync_scheduled
                    ):
                        return t_tpu
                    if loop.time() > deadline:
                        raise TimeoutError(
                            f"fault smoke did not converge: "
                            f"tpu={sorted(map(str, t_tpu))} "
                            f"cpu={sorted(map(str, t_cpu))}"
                        )
                    await asyncio.sleep(0.005)

            try:
                dbs = build_adj_dbs(edges)
                kv_tpu.push(lsdb_publication(dbs.values(), announcers))
                kv_cpu.push(lsdb_publication(dbs.values(), announcers))
                table1 = await converge()

                # flap: bump one spine link's metric and republish the
                # two endpoint adj dbs (the incremental event path)
                flapped = [
                    (a, b, 7 if (a, b) == ("g0_0", "g0_1") else m)
                    for a, b, m in edges
                ]
                dbs2 = build_adj_dbs(flapped)
                flap_pub = lsdb_publication(
                    [dbs2["g0_0"], dbs2["g0_1"]]
                )
                kv_tpu.push(flap_pub)
                kv_cpu.push(flap_pub)
                table2 = await converge()
            finally:
                tasks = [
                    t
                    for t in (
                        dec_tpu._task,
                        dec_cpu._task,
                        *fib_tpu._tasks,
                        *fib_cpu._tasks,
                    )
                    if t is not None
                ]
                for module in (fib_tpu, fib_cpu, dec_tpu, dec_cpu):
                    module.stop()
                if tasks:
                    await asyncio.gather(*tasks, return_exceptions=True)

            health = dec_tpu.get_solver_health()
            summary = {
                "converged": bool(table1) and bool(table2),
                "routes_programmed": len(table2),
                "solver_faults_fired": inj.fired("solver.tpu.solve"),
                "fib_faults_fired": inj.fired("fib.program"),
                "fallback_active": health["fallback_active"],
                "breaker_state": health["breaker_state"],
                "solver_failures": dec_tpu.solver.counters.get(
                    "decision.spf.solver_failures", 0
                ),
                "fib_program_failures": fib_tpu.counters.get(
                    "fib.thrift.failure.add_del_route", 0
                ),
                "fib_sync_calls": fib_tpu.counters.get(
                    "fib.sync_fib_calls", 0
                ),
            }
        assert summary["solver_faults_fired"] == 1, summary
        assert summary["fib_faults_fired"] == 1, summary
        assert summary["fallback_active"] == 1, summary
        assert summary["fib_program_failures"] >= 1, summary
        assert summary["converged"], summary
        return summary

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(body())
    finally:
        loop.close()


# stage-duration keys every node's flap span must carry (the spark→fib
# chain; flood-hop stages are topology-dependent and checked separately)
TRACE_SMOKE_STAGES = (
    "spark.neighbor_event_ms",
    "linkmonitor.adj_advertised_ms",
    "kvstore.publish_ms",
    "decision.recv_ms",
    "decision.debounce_ms",
    "decision.route_build_ms",
    "fib.recv_ms",
    "fib.program_ms",
)


def run_trace_smoke() -> dict:
    """TRACE_SMOKE tier-1 smoke (the observability sibling of
    run_fault_smoke): an N-node line-topology emulator run
    (TRACE_SMOKE_NODES, default 5) converges, one link flaps, and the
    network-wide trace substrate must hold up end to end —

      - every node finishes a COMPLETE spark→fib convergence span
        (locally-stamped monotonic stages on the flap endpoints,
        flood-reconstructed stages on remote nodes);
      - flood hop counts match topology distance on the line (node i
        receives the flap origin's publication after exactly i-1 hops);
      - the aggregated report (VirtualNetwork.convergence_report, the
        `breeze perf report` math) carries sane network-wide percentiles
        with slowest-hop attribution.

    Returns a summary dict of the evidence.
    """
    import os

    from openr_tpu.monitor.report import aggregate_convergence_reports
    from openr_tpu.testing.wrapper import VirtualNetwork, wait_until

    n = max(3, int(os.environ.get("TRACE_SMOKE_NODES", "5")))

    def complete_span(report: dict) -> bool:
        return any(
            all(span.get(stage) is not None for stage in TRACE_SMOKE_STAGES)
            for span in report["spans"]
        )

    async def body() -> dict:
        net = VirtualNetwork()
        for i in range(n):
            net.add_node(f"n{i}", loopback_prefix=f"10.{i}.0.0/24")
        await net.start_all()
        for i in range(n - 1):
            net.connect(f"n{i}", f"if{i}r", f"n{i + 1}", f"if{i + 1}l")

        def converged() -> bool:
            for i in range(n):
                got = set(net.wrappers[f"n{i}"].programmed_prefixes())
                want = {f"10.{j}.0.0/24" for j in range(n) if j != i}
                if not want.issubset(got):
                    return False
            return True

        try:
            await wait_until(converged, timeout=60.0)

            # the flap: sever n0–n1; n1's adjacency withdrawal floods down
            # the line and every node reprograms (withdraws 10.0.0.0/24)
            net.fail_link("n0", "if0r", "n1", "if1l")

            def withdrawn() -> bool:
                for i in range(1, n):
                    got = net.wrappers[f"n{i}"].programmed_prefixes()
                    if "10.0.0.0/24" in got:
                        return False
                return True

            await wait_until(withdrawn, timeout=60.0)
            # spans finish asynchronously of route state: poll the monitor
            # rings until every node shows a complete spark→fib span
            await wait_until(
                lambda: all(complete_span(r) for r in net.node_reports()),
                timeout=30.0,
            )

            reports = {r["node"]: r for r in net.node_reports()}
            hop_evidence = {}
            for i in range(2, n):
                node = f"n{i}"
                hops = [
                    f["hop_count"]
                    for f in reports[node]["floods"]
                    if f.get("origin") == "n1"
                ]
                assert (i - 1) in hops, (node, sorted(set(hops)))
                hop_evidence[node] = i - 1
                # remote nodes measured per-hop flood latency
                assert any(
                    f.get("hop_ms") is not None
                    for f in reports[node]["floods"]
                ), node

            agg = aggregate_convergence_reports(reports.values())
        finally:
            await net.stop_all()

        assert agg["nodes"] == n, agg
        assert agg["spans_total"] >= n, agg
        e2e = agg["e2e_ms"]
        assert 0.0 < e2e["p50"] <= e2e["p95"] <= e2e["max"], e2e
        assert agg["slowest_stage"] is not None, agg
        assert agg["flood"]["received"] > 0, agg
        assert agg["flood"]["hop_count_max"] >= n - 2, agg
        for stage in ("decision.route_build", "fib.program"):
            assert stage in agg["stages"], sorted(agg["stages"])
        return {
            "nodes": n,
            "spans_total": agg["spans_total"],
            "e2e_p50_ms": e2e["p50"],
            "e2e_p95_ms": e2e["p95"],
            "e2e_max_ms": e2e["max"],
            "slowest_stage": agg["slowest_stage"],
            "flood_received": agg["flood"]["received"],
            "flood_duplicate_ratio": agg["flood"]["duplicate_ratio"],
            "hop_evidence": hop_evidence,
        }

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(body())
    finally:
        loop.close()


def run_decision_backend_parity(
    my_node: str,
    publication: Publication,
    mesh: Optional[tuple],
) -> Tuple[int, int]:
    """Decision(tpu, mesh) vs Decision(cpu) on one publication; returns
    (n_unicast, n_mpls) on success, raises AssertionError on divergence.
    Creates and closes its own event loop (callers are sync entry points).
    """

    async def body():
        cpu = await decision_route_delta(my_node, publication, "cpu")
        tpu = await decision_route_delta(
            my_node, publication, "tpu", mesh=mesh
        )
        return assert_route_delta_equal(cpu, tpu)

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(body())
    finally:
        loop.close()


# the flap batch's fixed settings: one value at every call
_FLAP_BATCH_BACKEND = "cpu"
# pinned SPF debounce window, so a wave does not carry 10-250 ms of
# timer jitter
_FLAP_BATCH_DEBOUNCE_MS = (10.0, 50.0)


def run_flap_batch(
    nodes: int,
    flaps: int,
    subscribers: int,
    inproc_subscribers: int,
    codec: str = "mixed",
    churn_keys: int = 0,
    churn_value_bytes: int = 4096,
) -> dict:
    """A flap batch served to a subscriber cohort, judged by the fleet
    observer — the scale leg of the soak round (testing/soak.py
    `run_soak_round`, docs/Streaming.md "Fan-out at scale").

    A `nodes`-node line topology converges, then the middle link fails
    and restores `flaps` times while the fan-out serves:

      - `subscribers` (>= 1) `subscribeKvStore` streams over the nodes'
        real ctrl sockets, spread round-robin; `codec` picks their frame
        codec: "json", "binary", or "mixed" (alternating);
      - `inproc_subscribers` in-process subscribers per the same spread
        (testing/fanout.py — the cohort half the fd limit forbids as
        sockets), reported separately;
      - the first socket subscriber (on n0, label "stalled") throttled
        into overflow→resync through the `ctrl.stream.deliver` fault
        point: slow-client isolation under load;
      - `churn_keys` originations of `churn_value_bytes` each riding
        every wave (flooded area-wide), so the frames are LSDB-sized
        publications and not bare adjacency deltas.

    Admission control stays live: the per-node subscriber cap is sized
    for the cohort plus headroom. The fleet observer (openr_tpu/fleet)
    is attached over the real ctrl sockets for the whole batch; the
    summary's `fleet_findings_by_kind` maps each finding kind to the
    nodes it fired on, so a caller can check that a
    `stream_backpressure` breach is attributable to n0 alone.

    Every event's spark→fib convergence span lands in the per-node
    monitor rings and is folded by `VirtualNetwork.convergence_report()`.
    The stream meters (`ctrl.stream.encode_*` / `deliver_*`) are
    reported as deltas over the flap window: subscription-time snapshot
    encodes are set-up, not serving."""
    from openr_tpu.ctrl.client import CtrlClient
    from openr_tpu.fleet import FleetConfig, FleetObserver
    from openr_tpu.testing.fanout import InprocFanout
    from openr_tpu.testing.faults import FaultInjector, injected
    from openr_tpu.testing.wrapper import VirtualNetwork, wait_until

    assert subscribers >= 1, "the stalled subscriber is a socket subscriber"
    n = max(3, nodes)
    mid = n // 2
    mid_link = (f"n{mid}", f"if{mid}r", f"n{mid + 1}", f"if{mid + 1}l")

    async def body() -> dict:
        overrides: dict = {
            "decision_config": {
                "solver_backend": _FLAP_BATCH_BACKEND,
                "debounce_min_ms": _FLAP_BATCH_DEBOUNCE_MS[0],
                "debounce_max_ms": _FLAP_BATCH_DEBOUNCE_MS[1],
            },
            "stream_config": {
                "max_subscribers": (
                    (subscribers + inproc_subscribers) // n + 64
                )
            },
        }
        net = VirtualNetwork()
        for i in range(n):
            net.add_node(
                f"n{i}",
                loopback_prefix=f"10.{i}.0.0/24",
                config_overrides=overrides,
            )
        await net.start_all()
        for i in range(n - 1):
            net.connect(f"n{i}", f"if{i}r", f"n{i + 1}", f"if{i + 1}l")

        counts = {"frames": 0, "deltas": 0, "resyncs": 0, "snapshots": 0}
        stalled_kinds: list = []
        sub_tasks: list = []
        sub_clients: list = []
        inproc_cohorts: list = []

        def _sub_codec(i: int) -> str:
            if codec == "mixed":
                return "binary" if i % 2 else "json"
            return codec

        async def watch(client, label, sub_codec) -> None:
            # decode=False: the watchers read every frame off the socket
            # but skip payload parsing (at 2048 watchers on one box the
            # consumer-side json.loads otherwise dominates the wall clock)
            try:
                async for frame in client.subscribe(
                    "subscribeKvStore",
                    decode=False,
                    area="0",
                    client=label,
                    codec=sub_codec,
                ):
                    counts["frames"] += 1
                    kind = frame.get("type")
                    if label == "stalled":
                        stalled_kinds.append(kind)
                    if kind == "delta":
                        counts["deltas"] += 1
                    elif kind == "resync":
                        counts["resyncs"] += 1
                    elif kind == "snapshot":
                        counts["snapshots"] += 1
            except Exception:
                pass

        def read_stream_meters() -> dict:
            """Fleet-wide stream meter totals (docs/Streaming.md)."""
            t = {
                "encode_ms": 0.0,
                "encode_frames": 0,
                "encode_bytes": 0,
                "deliver_ms": 0.0,
                "deliver_bytes": 0,
                "deliveries": 0,
                "classes": 0,
                "class_hits": 0,
            }
            for wrapper in net.wrappers.values():
                sm = wrapper.daemon.stream_manager
                hist = sm.histograms.get("ctrl.stream.encode_ms")
                if hist is not None:
                    t["encode_ms"] += hist.sum
                    t["encode_frames"] += hist.count
                dhist = sm.histograms.get("ctrl.stream.deliver_ms")
                if dhist is not None:
                    t["deliver_ms"] += dhist.sum
                for key, counter in (
                    ("encode_bytes", "ctrl.stream.encode_bytes"),
                    ("deliver_bytes", "ctrl.stream.deliver_bytes"),
                    ("deliveries", "ctrl.stream.delivered"),
                    ("classes", "ctrl.stream.encode_classes"),
                    ("class_hits", "ctrl.stream.encode_class_hits"),
                ):
                    t[key] += sm.counters.get(counter, 0)
            return t

        async def start_subscribers() -> None:
            wrappers = list(net.wrappers.values())
            for i in range(subscribers):
                wrapper = wrappers[i % len(wrappers)]
                client = await CtrlClient(
                    "127.0.0.1", wrapper.ctrl_port
                ).connect()
                sub_clients.append(client)
                label = "stalled" if i == 0 else "cohort"
                sub_tasks.append(
                    asyncio.get_running_loop().create_task(
                        watch(client, label, _sub_codec(i))
                    )
                )

        def start_inproc() -> None:
            wrappers = list(net.wrappers.values())
            base, extra = divmod(inproc_subscribers, len(wrappers))
            for i, wrapper in enumerate(wrappers):
                count = base + (1 if i < extra else 0)
                if not count:
                    continue
                cohort = InprocFanout(
                    wrapper.daemon, count, codec=_sub_codec(i)
                )
                cohort.attach()
                cohort.start()
                inproc_cohorts.append(cohort)

        def converged() -> bool:
            for i in range(n):
                got = set(net.wrappers[f"n{i}"].programmed_prefixes())
                want = {f"10.{j}.0.0/24" for j in range(n) if j != i}
                if not want.issubset(got):
                    return False
            return True

        def partitioned() -> bool:
            # after the mid link fails, the left side withdraws the
            # rightmost prefix (and vice versa)
            left = net.wrappers["n0"].programmed_prefixes()
            right = net.wrappers[f"n{n - 1}"].programmed_prefixes()
            return (
                f"10.{n - 1}.0.0/24" not in left
                and "10.0.0.0/24" not in right
            )

        churn_wave = 0

        def churn() -> None:
            nonlocal churn_wave
            if not churn_keys:
                return
            churn_wave += 1
            kv = net.wrappers["n0"].daemon.kvstore
            pad = (f"wave{churn_wave}:".encode() * (
                churn_value_bytes // 6 + 1
            ))[:churn_value_bytes]
            for k in range(churn_keys):
                kv.set_key(
                    f"flapbatch:churn:{k}",
                    Value(
                        version=churn_wave,
                        originator_id="n0",
                        value=pad,
                    ),
                    area="0",
                )

        observer = None
        try:
            with injected(FaultInjector()) as inj:
                inj.arm(
                    "ctrl.stream.deliver",
                    times=None,
                    action=lambda sub: setattr(sub, "throttle_s", 0.3),
                    when=lambda sub: (
                        getattr(sub, "label", "") == "stalled"
                    ),
                )
                await wait_until(converged, timeout=60.0)
                await start_subscribers()
                # every socket subscriber has its snapshot before the
                # first flap: the initial dumps are private encodes
                # (set-up), kept out of the window's meters
                await wait_until(
                    lambda: counts["snapshots"] >= subscribers,
                    timeout=max(60.0, subscribers / 50.0),
                )
                # no snapshot wait: in-process subscribers register
                # directly on the manager, no initial dump rides their
                # queues (testing/fanout.py)
                start_inproc()
                observer = FleetObserver.for_network(
                    net, config=FleetConfig(scrape_interval_s=0.2)
                )
                await observer.start()

                meters0 = read_stream_meters()
                t_stream0 = time.perf_counter()
                for _ in range(max(1, flaps)):
                    net.fail_link(*mid_link)
                    churn()
                    await wait_until(partitioned, timeout=60.0)
                    net.restore_link(*mid_link)
                    churn()
                    await wait_until(converged, timeout=60.0)
                # no wait for the socket watchers to go quiet: the
                # stalled one trickles a frame per throttle period
                stream_elapsed = time.perf_counter() - t_stream0
                # deliveries race the last convergence check
                await asyncio.sleep(0.2)
                if inproc_cohorts:
                    # let the pump tasks finish the backlog before
                    # reading their stats; the deadline scales with the
                    # cohort (one core drains ~100k subscribers' final
                    # frames in tens of seconds)
                    def inproc_drained() -> bool:
                        return all(
                            not sub._frames and sub._resync_at is None
                            for cohort in inproc_cohorts
                            for sub in cohort.subs
                        )

                    await wait_until(
                        inproc_drained,
                        timeout=max(30.0, inproc_subscribers / 500.0),
                    )
                    for cohort in inproc_cohorts:
                        await cohort.stop()
                agg = net.convergence_report()
                meters1 = read_stream_meters()
                window = {k: meters1[k] - meters0[k] for k in meters1}
                node_resyncs: dict = {}
                for name, wrapper in net.wrappers.items():
                    resyncs = wrapper.daemon.stream_manager.counters.get(
                        "ctrl.stream.resyncs", 0
                    )
                    if resyncs:
                        node_resyncs[name] = resyncs
                await observer.stop()
                findings = list(observer.findings)
                tick = observer.histograms.get("fleet.tick_ms")
                scrape = observer.histograms.get("fleet.scrape_ms")
                fleet_stats = {
                    "fleet_ticks": tick.count if tick else 0,
                    "fleet_tick_ms": round(tick.avg, 4) if tick else 0.0,
                    "fleet_scrape_ms": (
                        round(scrape.avg, 4) if scrape else 0.0
                    ),
                    "fleet_scrapes": observer.counters.get(
                        "fleet.scrapes", 0
                    ),
                    "fleet_findings": len(findings),
                    # kind -> sorted node list: is a breach attributable?
                    "fleet_findings_by_kind": {
                        kind: sorted(
                            {f.node for f in findings if f.kind == kind}
                        )
                        for kind in sorted({f.kind for f in findings})
                    },
                }
                observer = None
        finally:
            if observer is not None:
                await observer.stop()
            for cohort in inproc_cohorts:
                if cohort._task is not None:
                    await cohort.stop()
            for task in sub_tasks:
                task.cancel()
            await asyncio.gather(*sub_tasks, return_exceptions=True)
            for client in sub_clients:
                await client.close()
            await net.stop_all()

        e2e = agg["e2e_ms"]
        encode_frames = window["encode_frames"]
        class_total = window["class_hits"] + window["classes"]
        summary = {
            "nodes": n,
            "flaps": max(1, flaps),
            "backend": _FLAP_BATCH_BACKEND,
            "spans_total": agg["spans_total"],
            "e2e_p50_ms": e2e["p50"],
            "e2e_p95_ms": e2e["p95"],
            "e2e_max_ms": e2e["max"],
            "stream_subscribers": subscribers,
            "stream_frames": counts["frames"],
            "stream_deltas": counts["deltas"],
            "stream_resyncs": counts["resyncs"],
            "stream_events_per_s": (
                counts["deltas"] / stream_elapsed
                if stream_elapsed > 0
                else 0.0
            ),
            "stream_codec": codec,
            "stream_encode_ms_total": round(window["encode_ms"], 3),
            "stream_encode_frames": encode_frames,
            "stream_encode_bytes": window["encode_bytes"],
            "stream_encode_classes": window["classes"],
            "stream_encode_class_hits": window["class_hits"],
            "stream_class_hit_rate": (
                round(window["class_hits"] / class_total, 6)
                if class_total
                else 0.0
            ),
            "stream_deliver_ms_total": round(window["deliver_ms"], 3),
            "stream_deliver_bytes": window["deliver_bytes"],
            "stream_deliveries": window["deliveries"],
            "stream_node_resyncs": node_resyncs,
            "stream_encode_us_per_frame": (
                round(window["encode_ms"] / encode_frames * 1e3, 3)
                if encode_frames
                else 0.0
            ),
            "stream_encode_share": (
                round((window["encode_ms"] / 1e3) / stream_elapsed, 6)
                if stream_elapsed > 0
                else 0.0
            ),
            "stream_stalled_kinds": sorted(set(stalled_kinds)),
            **{
                f"stream_inproc_{key}": sum(
                    c.stats[key] for c in inproc_cohorts
                )
                for key in ("subscribers", "frames", "resyncs", "bytes")
            },
            **fleet_stats,
        }
        return summary

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(body())
    finally:
        loop.close()
