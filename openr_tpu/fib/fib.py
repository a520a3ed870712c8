"""Fib module: consumes DecisionRouteUpdate deltas and programs them into a
platform FIB agent, with restart detection and full-resync recovery.

Behavioral port of openr/fib/Fib.{h,cpp}:
  - RouteState caches (Fib.h:183-207): unicast/mpls route maps, dirty
    prefix/label sets (link-down shrunk groups), dirtyRouteDb flag.
  - processRouteUpdates (Fib.cpp:303-352): drop doNotInstall routes, update
    caches, program the delta.
  - processInterfaceDb (Fib.cpp:355-484): on interface down, shrink ECMP
    groups to nexthops on still-up interfaces (delete route if none remain);
    on interface up, restore the full group for dirty routes.
  - updateRoutes (Fib.cpp:498-610): best-nexthop (min-metric) selection;
    skip delta when a full sync is pending; failure marks dirtyRouteDb and
    schedules debounced full sync with exponential backoff (8ms..4096ms,
    Fib.cpp:37-38).
  - syncRouteDb (Fib.cpp:612-672): syncFib/syncMplsFib full-state push,
    clears dirty sets on success.
  - keepAliveCheck (Fib.cpp:681-695): poll agent aliveSince; a change means
    agent restart → enforce full sync.
  - longestPrefixMatch + filtered route getters (Fib.cpp:157-299).
  - perf-event convergence logging (Fib.cpp:760-843): appends
    FIB_ROUTE_DB_RECVD / OPENR_FIB_ROUTES_PROGRAMMED, keeps a bounded
    perfDb_ ring, exports fib.convergence_time_ms; ordered-FIB mode persists
    the local programming time into KvStore under 'fibTime:<node>'.
"""

from __future__ import annotations

import asyncio
import logging
import statistics
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Set

from openr_tpu.messaging import QueueClosedError, RQueue
from openr_tpu.monitor.spans import (
    ACCOUNT_STAGE_PREFIX,
    GC_WATCH,
    account_line,
    stage,
    take_build_stages,
)
from openr_tpu.platform import FIB_CLIENT_OPENR, FibService
from openr_tpu.solver import DecisionRouteUpdate, RibMplsEntry
from openr_tpu.types import (
    InterfaceDatabase,
    IpPrefix,
    MplsActionCode,
    MplsRoute,
    NextHop,
    PerfEvents,
    UnicastRoute,
)
from openr_tpu.testing.faults import fault_point
from openr_tpu.utils import ExponentialBackoff
from openr_tpu.utils.counters import CountersMixin, HistogramsMixin
from openr_tpu.utils.ownership import owned_by

log = logging.getLogger(__name__)

# a finished span is slow where its total is over SLOW_FACTOR x the median
# total of the SLOW_WINDOW spans before it, once SLOW_MIN have finished
SLOW_FACTOR = 3.0
SLOW_WINDOW = 32
SLOW_MIN = 8

# Constants.h kPerfBufferSize / kConvergenceMaxDuration
PERF_BUFFER_SIZE = 10
CONVERGENCE_MAX_MS = 3000.0
FIB_TIME_MARKER = "fibTime:"  # Constants::kFibTimeMarker
# one LogSample per restart-failure forensics dump (stale-deadline flush,
# GR expiry mid-boot, resync divergence — docs/Monitoring.md event catalog)
FIB_RESTART_FORENSICS_DUMPED = "FIB_RESTART_FORENSICS_DUMPED"


def get_best_nexthops_unicast(nexthops: List[NextHop]) -> List[NextHop]:
    """Min-metric ECMP group (+ useNonShortestRoute KSP2 members).

    Reference: openr/common/Util.cpp getBestNextHopsUnicast:474-495.
    """
    if len(nexthops) <= 1:
        return list(nexthops)
    min_cost = min(nh.metric for nh in nexthops)
    return [
        nh
        for nh in nexthops
        if nh.metric == min_cost or nh.use_non_shortest_route
    ]


def get_best_nexthops_mpls(nexthops: List[NextHop]) -> List[NextHop]:
    """Min-metric MPLS group, preferring PHP over SWAP at equal cost.

    Reference: openr/common/Util.cpp getBestNextHopsMpls:497-535.
    """
    if len(nexthops) <= 1:
        return list(nexthops)
    min_cost = min(nh.metric for nh in nexthops)
    action = MplsActionCode.SWAP
    for nh in nexthops:
        if (
            nh.metric == min_cost
            and nh.mpls_action is not None
            and nh.mpls_action.action == MplsActionCode.PHP
        ):
            action = MplsActionCode.PHP
    return [
        nh
        for nh in nexthops
        if nh.metric == min_cost
        and nh.mpls_action is not None
        and nh.mpls_action.action == action
    ]


def longest_prefix_match(
    addr_prefix: str, unicast_routes: Dict[IpPrefix, UnicastRoute]
) -> Optional[IpPrefix]:
    """Longest-prefix match of 'addr' or 'addr/len' against the route table.

    Reference: openr/fib/Fib.cpp longestPrefixMatch:157-177.
    """
    import ipaddress

    if "/" not in addr_prefix:
        addr_prefix += (
            "/128" if ":" in addr_prefix else "/32"
        )
    net = ipaddress.ip_network(addr_prefix, strict=False)
    best: Optional[IpPrefix] = None
    best_len = -1
    for prefix in unicast_routes:
        db_net = prefix.network
        if db_net.version != net.version:
            continue
        if (
            best_len < db_net.prefixlen <= net.prefixlen
            and net.subnet_of(db_net)
        ):
            best_len = db_net.prefixlen
            best = prefix
    return best


@dataclass
class FibConfig:
    my_node_name: str
    dryrun: bool = False
    enable_segment_routing: bool = False
    enable_ordered_fib: bool = False
    # hold before the first full sync when no EOR gates it (Fib.cpp:73-76
    # coldStartDuration). 0.0 — the seed default — synced immediately and
    # wiped surviving agent routes before Decision had converged; the
    # daemon wires fib_config.cold_start_duration_s (default 1s) and
    # tests that want the old immediate sync pass 0.0 explicitly.
    cold_start_duration: float = 1.0
    # warm boot (docs/Fib.md): agent routes recovered at start are kept
    # forwarding as STALE until the first Decision route db reconciles
    # them; past this deadline the stale set is force-flushed with a
    # forensics dump (the restarted daemon never converged)
    stale_sweep_deadline_s: float = 300.0
    # restart-forensics artifact directory (shares the PR 13 flight-
    # recorder dump path/schema; None = in-memory dumps only)
    forensics_dir: Optional[str] = None
    keep_alive_interval: float = 30.0  # Constants::kKeepAliveCheckInterval
    backoff_min: float = 0.008  # Fib.cpp:37-38
    backoff_max: float = 4.096
    # decorrelated jitter on the full-sync retry schedule: when a fleet's
    # agents restart together, deterministic doubling re-synchronizes every
    # node's resync attempts into storms — jitter (utils/backoff.py)
    # decorrelates them. Seed is injectable for deterministic tests.
    backoff_jitter: bool = True
    backoff_seed: Optional[int] = None
    has_eor_time: bool = False  # eor_time_s set → Decision gates first sync


@dataclass
class _RouteState:
    """Fib.h:183-207 RouteState + the warm-boot stale sets."""

    unicast_routes: Dict[IpPrefix, UnicastRoute] = field(default_factory=dict)
    # every label Decision gave a route for, in the order they came. A
    # label in `_mpls_unread` has a value here that is out of date (None
    # where it never had one) until `mpls_routes` is read
    _mpls_routes: Dict[int, Optional[MplsRoute]] = field(default_factory=dict)
    _mpls_unread: Dict[int, RibMplsEntry] = field(default_factory=dict)
    has_routes_from_decision: bool = False
    dirty_prefixes: Set[IpPrefix] = field(default_factory=set)
    dirty_labels: Set[int] = field(default_factory=set)
    dirty_route_db: bool = False
    # warm boot: agent routes that survived a daemon restart, kept
    # forwarding until the first post-boot sync reconciles them
    # (Fib.cpp:612-672 stale-route sweep)
    stale_prefixes: Set[IpPrefix] = field(default_factory=set)
    stale_labels: Set[int] = field(default_factory=set)

    def has_stale(self) -> bool:
        return bool(self.stale_prefixes or self.stale_labels)

    def set_mpls_entry(self, entry: RibMplsEntry) -> None:
        """Decision's label route, kept as it came: sorting its next hops
        into an MplsRoute waits for a reader. With segment routing off an
        event has none, and a table entry (routes.LabelNextHops) never
        makes its next hops."""
        self._mpls_routes.setdefault(entry.label, None)
        self._mpls_unread[entry.label] = entry

    def pop_mpls_route(self, label: int) -> None:
        self._mpls_routes.pop(label, None)
        self._mpls_unread.pop(label, None)

    @property
    def mpls_routes(self) -> Dict[int, MplsRoute]:
        """The label routes as the agent would get them."""
        if self._mpls_unread:
            for label, entry in self._mpls_unread.items():
                self._mpls_routes[label] = entry.to_mpls_route()
            self._mpls_unread.clear()
        return self._mpls_routes

    def num_mpls_routes(self) -> int:
        return len(self._mpls_routes)


@owned_by("fib-loop")
class Fib(CountersMixin, HistogramsMixin):
    def __init__(
        self,
        config: FibConfig,
        fib_service: FibService,
        route_updates: RQueue,
        interface_updates: Optional[RQueue] = None,
        kvstore_client=None,
        log_sample_fn=None,
        loop: Optional[asyncio.AbstractEventLoop] = None,
    ) -> None:
        self.config = config
        self.fib_service = fib_service
        self.route_updates = route_updates
        self.interface_updates = interface_updates
        self.kvstore_client = kvstore_client
        # sink for finished convergence spans (monitor log-sample queue's
        # push in the daemon; None drops the CONVERGENCE_TRACE samples)
        self._log_sample_fn = log_sample_fn
        self._loop = loop

        self.route_state = _RouteState()
        self.interface_status_db: Dict[str, bool] = {}
        self.perf_db: List[PerfEvents] = []
        self._recent_perf_ts = 0
        self.has_synced_fib = False
        # one-shot per-delta programming delay (seconds), consumed before
        # the agent RPCs: the `fib.program` fault point's action hook sets
        # it to emulate a slow FIB agent deterministically — the same
        # throttle pattern as `ctrl.stream.deliver` (docs/Robustness.md);
        # the added latency lands in the span's fib.program stage
        self.program_throttle_s = 0.0
        import random as _random

        self._backoff = ExponentialBackoff(
            config.backoff_min,
            config.backoff_max,
            jitter=config.backoff_jitter,
            rng=(
                _random.Random(config.backoff_seed)
                if config.backoff_seed is not None
                else None
            ),
        )
        # single-slot semaphore serializing route programming across the
        # route-update and interface-update consumers (Fib.h:270)
        self._program_lock = asyncio.Lock()
        self._sync_scheduled = False
        self._sync_handle: Optional[asyncio.TimerHandle] = None
        self._tasks: List[asyncio.Task] = []
        # warm boot: stale-sweep deadline timer + the restart-convergence
        # anchor (the monotonic stamp of the previous incarnation's
        # restarting-hello flood; closing the first post-boot sync
        # observes restart.e2e_ms against it)
        self._stale_deadline_handle: Optional[asyncio.TimerHandle] = None
        self._restart_anchor_ts: Optional[float] = None
        self._forensics = None  # lazy FlightRecorder (PR 13 dump path)
        # total_ms of the last finished spans: what "slow" is measured by
        self._recent_totals_ms: Deque[float] = deque(maxlen=SLOW_WINDOW)
        self.counters: Dict[str, int] = {
            "convergence.slow_events": 0,
            "convergence.slow_events_unexplained": 0,
        }
        self.histograms: Dict = {}

    def loop(self) -> asyncio.AbstractEventLoop:
        return self._loop or asyncio.get_event_loop()

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def start(self) -> None:
        self._tasks.append(self.loop().create_task(self._boot()))

    async def _boot(self) -> None:
        """Warm-boot recovery, then the consumer loops.

        The agent's surviving route table is read BEFORE any programming
        can happen: recovered entries are marked stale and keep
        forwarding; the first full sync is then gated on Decision's
        initial converged route db (`has_eor_time`, or simply the first
        route update) and runs as a reconciliation diff instead of a
        wholesale replace (docs/Fib.md "Cold start, EOR and warm boot").
        Queued route updates wait in the reader until the recovery read
        finishes, so ordering is preserved."""
        await self._recover_agent_routes()
        if not self.config.has_eor_time:
            # no EOR gating: sync once the cold-start hold expires
            # (Fib.cpp:73-76). With a clean (empty) agent the sync is
            # allowed to run routeless — it wipes nothing; with recovered
            # stale routes it additionally waits for the first Decision
            # route db (or the stale-sweep deadline), never wiping a
            # forwarding table before the daemon has reconverged.
            if not self.route_state.has_stale():
                self.route_state.has_routes_from_decision = True
            self._schedule_sync(self.config.cold_start_duration)
        self._tasks.append(self.loop().create_task(self._consume_routes()))
        if self.interface_updates is not None:
            self._tasks.append(
                self.loop().create_task(self._consume_interfaces())
            )
        if not self.config.dryrun:
            self._tasks.append(self.loop().create_task(self._keep_alive()))

    def stop(self) -> None:
        for task in self._tasks:
            task.cancel()
        self._tasks.clear()
        if self._sync_handle is not None:
            self._sync_handle.cancel()
            self._sync_handle = None
        if self._stale_deadline_handle is not None:
            self._stale_deadline_handle.cancel()
            self._stale_deadline_handle = None

    async def _consume_routes(self) -> None:
        while True:
            try:
                delta = await self.route_updates.get()
            except (QueueClosedError, asyncio.CancelledError):
                return
            await self.process_route_updates(delta)

    async def _consume_interfaces(self) -> None:
        while True:
            try:
                if_db = await self.interface_updates.get()
            except (QueueClosedError, asyncio.CancelledError):
                return
            await self.process_interface_db(if_db)

    async def _keep_alive(self) -> None:
        while True:
            try:
                await asyncio.sleep(self.config.keep_alive_interval)
                await self.keep_alive_check()
            except asyncio.CancelledError:
                return
            except Exception:
                self._bump("fib.thrift.failure.keepalive")
                log.exception("fib keepalive failed")

    # ------------------------------------------------------------------
    # warm boot (graceful-restart resilience, docs/Robustness.md)
    # ------------------------------------------------------------------

    async def _recover_agent_routes(self) -> None:
        """Read the agent's surviving route table at start and mark every
        entry stale. The agent keeps forwarding on these through the
        daemon gap; the first reconciliation sync sweeps only the
        leftovers. A failed read (agent down, cold machine boot) is the
        clean cold start — nothing stale, nothing gated."""
        if self.config.dryrun:
            return
        try:
            unicast = await self.fib_service.get_route_table_by_client(
                FIB_CLIENT_OPENR
            )
            mpls: List[MplsRoute] = []
            if self.config.enable_segment_routing:
                mpls = await self.fib_service.get_mpls_route_table_by_client(
                    FIB_CLIENT_OPENR
                )
        except Exception:
            self._bump("fib.thrift.failure.route_dump")
            log.exception("warm-boot route recovery failed; cold start")
            return
        if not unicast and not mpls:
            return
        self.route_state.stale_prefixes = {r.dest for r in unicast}
        self.route_state.stale_labels = {r.top_label for r in mpls}
        self._bump("fib.warm_boots")
        counters = self._ensure_counters()
        counters["fib.warm_boot_routes"] = len(unicast) + len(mpls)
        log.info(
            "warm boot: %d unicast + %d mpls agent routes recovered as "
            "stale; first sync gated on Decision convergence",
            len(unicast),
            len(mpls),
        )
        self._stale_deadline_handle = self.loop().call_later(
            self.config.stale_sweep_deadline_s, self._stale_deadline_expired
        )

    def note_restart_anchor(self, ts_monotonic: float) -> None:
        """Arm the restart-convergence span: `ts_monotonic` is the stamp
        of the previous incarnation's restarting-hello flood (the restart
        harness carries it across the daemon gap). The first successful
        post-boot sync closes the span into `restart.e2e_ms`."""
        self._restart_anchor_ts = ts_monotonic

    def _note_sync_complete(self) -> None:
        """Bookkeeping after any successful full sync: the stale state is
        reconciled (sweep happened or there was nothing stale) and a
        pending restart span closes."""
        if self._stale_deadline_handle is not None:
            self._stale_deadline_handle.cancel()
            self._stale_deadline_handle = None
        self.route_state.stale_prefixes.clear()
        self.route_state.stale_labels.clear()
        if self._restart_anchor_ts is not None:
            self._observe(
                "restart.e2e_ms",
                (time.monotonic() - self._restart_anchor_ts) * 1e3,
            )
            self._restart_anchor_ts = None

    def _stale_deadline_expired(self) -> None:
        """Bounded staleness: Decision never converged within
        `stale_sweep_deadline_s` of the warm boot. Snapshot forensics,
        then force-flush — the sync runs with whatever (possibly empty)
        route db exists, sweeping every leftover stale route. Bounded
        blackholing beats forwarding into a topology that moved on."""
        self._stale_deadline_handle = None
        if not self.route_state.has_stale():
            return
        self._bump("fib.stale_deadline_flushes")
        self.dump_restart_forensics(
            "stale_deadline_flush",
            extra={
                "deadline_s": self.config.stale_sweep_deadline_s,
                "has_routes_from_decision": (
                    self.route_state.has_routes_from_decision
                ),
            },
        )
        log.warning(
            "stale-sweep deadline expired with %d unreconciled routes; "
            "force-flushing",
            len(self.route_state.stale_prefixes)
            + len(self.route_state.stale_labels),
        )
        self.route_state.has_routes_from_decision = True
        self.route_state.dirty_route_db = True
        self._schedule_sync(0.0)

    def dump_restart_forensics(self, reason: str, extra=None) -> Dict:
        """Snapshot a restart-failure forensics artifact through the
        PR 13 flight-recorder dump path (same schema/artifact flow as the
        solver fault domain): stale-deadline flushes dump here directly;
        the restart harness dumps GR-expiry-mid-boot and resync-
        divergence failures through the same seam. Emits one
        FIB_RESTART_FORENSICS_DUMPED LogSample carrying the dump id."""
        from openr_tpu.solver.flight_recorder import FlightRecorder

        if self._forensics is None:
            self._forensics = FlightRecorder(
                node=self.config.my_node_name,
                forensics_dir=self.config.forensics_dir,
            )
        context = {
            "stale_prefixes": sorted(
                str(p) for p in self.route_state.stale_prefixes
            )[:64],
            "stale_labels": sorted(self.route_state.stale_labels)[:64],
            "unicast_routes": len(self.route_state.unicast_routes),
            "has_synced_fib": self.has_synced_fib,
            **(extra or {}),
        }
        dump = self._forensics.dump(
            reason, counters=dict(self.counters), extra=context
        )
        self._bump("fib.forensics_dumps")
        if self._log_sample_fn is not None:
            from openr_tpu.monitor.monitor import LogSample

            sample = LogSample()
            sample.add_string("event", FIB_RESTART_FORENSICS_DUMPED)
            sample.add_string("reason", reason)
            sample.add_string("forensics_id", dump["id"])
            sample.add_int(
                "stale_routes",
                len(self.route_state.stale_prefixes)
                + len(self.route_state.stale_labels),
            )
            try:
                self._log_sample_fn(sample)
            except Exception:
                pass  # a closed monitor queue must never break shutdown
        return dump

    # ------------------------------------------------------------------
    # route update processing
    # ------------------------------------------------------------------

    async def process_route_updates(self, delta: DecisionRouteUpdate) -> None:
        """Fib.cpp:303-352."""
        self.route_state.has_routes_from_decision = True
        perf_events = delta.perf_events
        if isinstance(perf_events, PerfEvents):
            perf_events.add(self.config.my_node_name, "FIB_ROUTE_DB_RECVD")
        span = getattr(delta, "span", None)
        if span is not None:
            # Decision's `decision.route_build` mark -> taken up here: the
            # queue hop (with what `decision.emit` does after its mark)
            self._observe("fib.queue_wait_ms", span.mark("fib.recv"))

        # the update into Fib's own tables, before the first call to the
        # agent: tiles with fib.program, under the event's build
        with stage("fib.apply", self.histograms, getattr(span, "build", None)):
            unicast_to_update: List[UnicastRoute] = []
            for entry in delta.unicast_routes_to_update:
                if entry.do_not_install:
                    continue
                route = entry.to_unicast_route()
                self.route_state.unicast_routes[route.dest] = route
                self.route_state.dirty_prefixes.discard(route.dest)
                unicast_to_update.append(route)
            for mpls_entry in delta.mpls_routes_to_update:
                self.route_state.set_mpls_entry(mpls_entry)
                self.route_state.dirty_labels.discard(mpls_entry.label)
            mpls_to_update: List[MplsRoute] = []
            if self.config.enable_segment_routing:
                # the agent reads them: nobody else does in an event
                mpls_routes = self.route_state.mpls_routes
                mpls_to_update = [
                    mpls_routes[mpls_entry.label]
                    for mpls_entry in delta.mpls_routes_to_update
                ]
            for dest in delta.unicast_routes_to_delete:
                self.route_state.unicast_routes.pop(dest, None)
                self.route_state.dirty_prefixes.discard(dest)
            for label in delta.mpls_routes_to_delete:
                self.route_state.pop_mpls_route(label)
                self.route_state.dirty_labels.discard(label)

        self._bump("fib.process_route_db")
        await self._update_routes(
            unicast_to_update,
            list(delta.unicast_routes_to_delete),
            mpls_to_update,
            list(delta.mpls_routes_to_delete),
            perf_events,
            span=span,
        )

    async def process_interface_db(self, if_db: InterfaceDatabase) -> None:
        """Fast local reaction to link events: shrink/restore ECMP groups
        (Fib.cpp:355-484)."""
        self._bump("fib.process_interface_db")
        perf_events = if_db.perf_events
        if isinstance(perf_events, PerfEvents):
            perf_events.add(self.config.my_node_name, "FIB_INTF_DB_RECEIVED")
        for if_name, info in if_db.interfaces.items():
            self.interface_status_db[if_name] = info.is_up

        unicast_to_update: List[UnicastRoute] = []
        unicast_to_delete: List[IpPrefix] = []
        for dest, route in self.route_state.unicast_routes.items():
            valid = [
                nh
                for nh in route.nexthops
                if nh.iface is None
                or self.interface_status_db.get(nh.iface, False)
            ]
            prev_best = get_best_nexthops_unicast(list(route.nexthops))
            valid_best = get_best_nexthops_unicast(valid)
            if not valid_best:
                unicast_to_delete.append(dest)
                self.route_state.dirty_prefixes.add(dest)
            elif set(valid_best) != set(prev_best):
                unicast_to_update.append(UnicastRoute(dest, tuple(valid_best)))
                self.route_state.dirty_prefixes.add(dest)
            elif dest in self.route_state.dirty_prefixes:
                # interfaces back up: restore the full group
                unicast_to_update.append(route)
                self.route_state.dirty_prefixes.discard(dest)

        mpls_to_update: List[MplsRoute] = []
        mpls_to_delete: List[int] = []
        for label, mpls_route in self.route_state.mpls_routes.items():
            valid = [
                nh
                for nh in mpls_route.nexthops
                if nh.iface is None
                or self.interface_status_db.get(nh.iface, False)
            ]
            prev_best = get_best_nexthops_mpls(list(mpls_route.nexthops))
            valid_best = get_best_nexthops_mpls(valid)
            if not valid_best:
                mpls_to_delete.append(label)
                self.route_state.dirty_labels.add(label)
            elif set(valid_best) != set(prev_best):
                mpls_to_update.append(MplsRoute(label, tuple(valid_best)))
                self.route_state.dirty_labels.add(label)
            elif label in self.route_state.dirty_labels:
                mpls_to_update.append(mpls_route)
                self.route_state.dirty_labels.discard(label)

        await self._update_routes(
            unicast_to_update,
            unicast_to_delete,
            mpls_to_update,
            mpls_to_delete,
            perf_events,
        )

    # ------------------------------------------------------------------
    # programming
    # ------------------------------------------------------------------

    async def _update_routes(
        self,
        unicast_to_update: List[UnicastRoute],
        unicast_to_delete: List[IpPrefix],
        mpls_to_update: List[MplsRoute],
        mpls_to_delete: List[int],
        perf_events: Optional[PerfEvents],
        span=None,
    ) -> None:
        """Incremental delta programming (Fib.cpp:498-610)."""
        async with self._program_lock:
            self.update_global_counters()
            # the stretch fib.program_ms times, as a profiler stage
            with stage("fib.program", build=getattr(span, "build", None)):
                await self._program_delta(
                    unicast_to_update,
                    unicast_to_delete,
                    mpls_to_update,
                    mpls_to_delete,
                    perf_events,
                    span,
                )

    async def _program_delta(
        self,
        unicast_to_update: List[UnicastRoute],
        unicast_to_delete: List[IpPrefix],
        mpls_to_update: List[MplsRoute],
        mpls_to_delete: List[int],
        perf_events: Optional[PerfEvents],
        span,
    ) -> None:
        """_update_routes under the programming lock."""
        t0 = time.perf_counter()
        # best-nexthop (min-metric) groups actually get programmed
        unicast_best = [
            UnicastRoute(
                r.dest, tuple(get_best_nexthops_unicast(list(r.nexthops)))
            )
            for r in unicast_to_update
        ]
        mpls_best = [
            MplsRoute(
                r.top_label, tuple(get_best_nexthops_mpls(list(r.nexthops)))
            )
            for r in mpls_to_update
        ]

        if self.config.dryrun:
            self.log_perf_events(perf_events)
            self._finish_span(span, t0)
            return
        if self._sync_scheduled:
            return  # pending full sync subsumes this delta
        if self.route_state.dirty_route_db or not self.has_synced_fib:
            self._schedule_sync(0.0)
            return

        try:
            # named fault seam: injected programming failures ride the
            # exact dirty-marking + debounced-resync path a thrift
            # failure would (docs/Robustness.md)
            fault_point("fib.program", self)
            delay, self.program_throttle_s = self.program_throttle_s, 0.0
            if delay:
                await asyncio.sleep(delay)
            n = 0
            if unicast_to_delete:
                n += len(unicast_to_delete)
                await self.fib_service.delete_unicast_routes(
                    FIB_CLIENT_OPENR, unicast_to_delete
                )
            if unicast_best:
                n += len(unicast_best)
                await self.fib_service.add_unicast_routes(
                    FIB_CLIENT_OPENR, unicast_best
                )
            if self.config.enable_segment_routing and mpls_to_delete:
                n += len(mpls_to_delete)
                await self.fib_service.delete_mpls_routes(
                    FIB_CLIENT_OPENR, mpls_to_delete
                )
            if self.config.enable_segment_routing and mpls_best:
                n += len(mpls_best)
                await self.fib_service.add_mpls_routes(
                    FIB_CLIENT_OPENR, mpls_best
                )
            self._bump("fib.num_of_route_updates", n)
            self.route_state.dirty_route_db = False
            self.log_perf_events(perf_events)
            self._finish_span(span, t0)
        except Exception:
            self._bump("fib.thrift.failure.add_del_route")
            self.route_state.dirty_route_db = True
            log.exception("failed to program route delta; scheduling sync")
            self._schedule_sync(0.0)

    async def sync_route_db(self) -> bool:
        """Full-state push (Fib.cpp:612-672).

        Warm boot turns the first sync into a **reconciliation diff**:
        with stale (agent-recovered) routes outstanding, the desired
        routes are programmed as adds and only the stale leftovers —
        prefixes the agent still carries that Decision no longer wants —
        are deleted. The agent's forwarding table is never wholesale
        replaced, so it stays continuously non-empty through the
        reconvergence; `fib.stale_routes_swept` counts the sweep."""
        unicast = [
            UnicastRoute(
                r.dest, tuple(get_best_nexthops_unicast(list(r.nexthops)))
            )
            for r in self.route_state.unicast_routes.values()
        ]
        mpls: List[MplsRoute] = []
        if self.config.enable_segment_routing:
            # off, nothing is pushed, and no label route is read for it
            mpls = [
                MplsRoute(
                    r.top_label,
                    tuple(get_best_nexthops_mpls(list(r.nexthops))),
                )
                for r in self.route_state.mpls_routes.values()
            ]
        if self.config.dryrun:
            self._note_sync_complete()
            return True
        try:
            fault_point("fib.sync", self)
            self._bump("fib.sync_fib_calls")
            if self.route_state.has_stale():
                await self._reconcile_sync(unicast, mpls)
            else:
                await self.fib_service.sync_fib(FIB_CLIENT_OPENR, unicast)
                if self.config.enable_segment_routing:
                    await self.fib_service.sync_mpls_fib(
                        FIB_CLIENT_OPENR, mpls
                    )
            self.route_state.dirty_prefixes.clear()
            self.route_state.dirty_labels.clear()
            self.route_state.dirty_route_db = False
            self._note_sync_complete()
            return True
        except Exception:
            self._bump("fib.thrift.failure.sync_fib")
            self.route_state.dirty_route_db = True
            log.exception("failed to sync route db with fib agent")
            return False

    async def _reconcile_sync(
        self, unicast: List[UnicastRoute], mpls: List[MplsRoute]
    ) -> None:
        """The warm-boot sweep: add every desired route, delete exactly
        the stale leftovers. Raises propagate to sync_route_db's retry
        path with the stale sets intact (the sweep re-runs whole)."""
        desired_prefixes = {r.dest for r in unicast}
        leftover_prefixes = sorted(
            p
            for p in self.route_state.stale_prefixes
            if p not in desired_prefixes
        )
        if unicast:
            await self.fib_service.add_unicast_routes(
                FIB_CLIENT_OPENR, unicast
            )
        if leftover_prefixes:
            await self.fib_service.delete_unicast_routes(
                FIB_CLIENT_OPENR, leftover_prefixes
            )
        swept = len(leftover_prefixes)
        if self.config.enable_segment_routing:
            desired_labels = {r.top_label for r in mpls}
            leftover_labels = sorted(
                l
                for l in self.route_state.stale_labels
                if l not in desired_labels
            )
            if mpls:
                await self.fib_service.add_mpls_routes(FIB_CLIENT_OPENR, mpls)
            if leftover_labels:
                await self.fib_service.delete_mpls_routes(
                    FIB_CLIENT_OPENR, leftover_labels
                )
            swept += len(leftover_labels)
        self._bump("fib.restart_reconciles")
        if swept:
            self._bump("fib.stale_routes_swept", swept)
        log.info(
            "warm-boot reconciliation: %d routes programmed, %d stale "
            "leftovers swept",
            len(unicast) + len(mpls),
            swept,
        )

    def _schedule_sync(self, delay: float) -> None:
        """syncRouteDbDebounced (Fib.cpp:675-680): one pending sync max."""
        if self._sync_scheduled:
            return
        self._sync_scheduled = True
        self._sync_handle = self.loop().call_later(
            delay, lambda: self.loop().create_task(self._run_sync())
        )

    async def _run_sync(self) -> None:
        """syncRoutesTimer_ callback (Fib.cpp:48-62)."""
        async with self._program_lock:
            self._sync_scheduled = False
            self._sync_handle = None
            if not self.route_state.has_routes_from_decision:
                return
            if await self.sync_route_db():
                self.has_synced_fib = True
                self._backoff.report_success()
            else:
                self._backoff.report_error()
                self._schedule_sync(
                    self._backoff.get_time_remaining_until_retry()
                )

    async def keep_alive_check(self) -> None:
        """Agent-restart detection (Fib.cpp:681-695)."""
        # named fault seam, ctx=self: tests arm actions here to kill or
        # restart the stub agent exactly when the poll observes it
        fault_point("fib.keepalive", self)
        alive_since = await self.fib_service.alive_since()
        if getattr(self, "_latest_alive_since", None) not in (
            None,
            alive_since,
        ):
            log.warning("fib agent restarted; scheduling full sync")
            self.route_state.dirty_route_db = True
            self._backoff.report_success()
            self._schedule_sync(0.0)
        self._latest_alive_since = alive_since

    # ------------------------------------------------------------------
    # read APIs (OpenrCtrl surface)
    # ------------------------------------------------------------------

    def get_route_db(self) -> Dict[str, list]:
        return {
            "this_node_name": self.config.my_node_name,
            "unicast_routes": list(self.route_state.unicast_routes.values()),
            "mpls_routes": list(self.route_state.mpls_routes.values()),
        }

    def get_unicast_routes(
        self, prefixes: Optional[List[str]] = None
    ) -> List[UnicastRoute]:
        """All routes, or longest-prefix matches of the filters
        (Fib.cpp:233-281)."""
        if not prefixes:
            return list(self.route_state.unicast_routes.values())
        matched: Set[IpPrefix] = set()
        for prefix_str in prefixes:
            match = longest_prefix_match(
                prefix_str, self.route_state.unicast_routes
            )
            if match is not None:
                matched.add(match)
        return [
            self.route_state.unicast_routes[p] for p in sorted(matched)
        ]

    def get_mpls_routes(
        self, labels: Optional[List[int]] = None
    ) -> List[MplsRoute]:
        if not labels:
            return list(self.route_state.mpls_routes.values())
        label_set = set(labels)
        return [
            r
            for label, r in self.route_state.mpls_routes.items()
            if label in label_set
        ]

    def get_perf_db(self) -> List[PerfEvents]:
        return list(self.perf_db)

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------

    def update_global_counters(self) -> None:
        """Fib.cpp:735-758."""
        counters = self._ensure_counters()
        counters["fib.num_unicast_routes"] = len(
            self.route_state.unicast_routes
        )
        counters["fib.num_mpls_routes"] = self.route_state.num_mpls_routes()
        counters["fib.num_routes"] = (
            counters["fib.num_unicast_routes"]
            + counters["fib.num_mpls_routes"]
        )
        counters["fib.num_dirty_prefixes"] = len(
            self.route_state.dirty_prefixes
        )
        counters["fib.num_dirty_labels"] = len(self.route_state.dirty_labels)
        counters["fib.num_stale_routes"] = len(
            self.route_state.stale_prefixes
        ) + len(self.route_state.stale_labels)
        counters["fib.synced"] = 0 if self._sync_scheduled else 1

    def _finish_span(self, span, t0: float) -> None:
        """Close one convergence span after routes are programmed (or
        dryrun-accepted): programming latency and end-to-end
        publication→programmed latency land in this module's histograms,
        and the finished stage trace goes out as one CONVERGENCE_TRACE
        LogSample through the monitor queue. All math runs on the
        monotonic clock (Span/perf_counter) — wall-clock steps never skew
        these, unlike the PerfEvents-derived fib.convergence_time_ms.

        The sample carries the event's account beside the marks (build,
        `stage.<name>_ms`, `unstaged_ms`, `gc_full_ms`, Decision's notes
        on the build, `slow`: docs/Monitoring.md "The event's account").
        Slow is a total over SLOW_FACTOR x the median of the SLOW_WINDOW
        spans before it, once SLOW_MIN have finished; a slow span with no
        full collection inside and no compile in its build is unexplained,
        counted, and logged with its account."""
        self._observe("fib.program_ms", (time.perf_counter() - t0) * 1e3)
        if span is None:
            return
        span.mark("fib.program")
        end = span.marks[-1][1]
        total_ms = (end - span.t0) * 1e3
        self._observe("convergence.e2e_ms", total_ms)
        self._bump("fib.convergence_spans")
        # the event's account, closed where the event ends: the stages that
        # ran under its build, what no stage and no queue hop owns, and the
        # full collections that started inside it
        stages = (
            take_build_stages(span.build, end) if span.build is not None else []
        )
        unstaged_ms = span.unstaged_ms(stages)
        self._observe("convergence.unstaged_ms", unstaged_ms)
        gc_full_ms = GC_WATCH.full_pause_ms_between(span.t0, end)
        recent = self._recent_totals_ms
        slow = (
            len(recent) >= SLOW_MIN
            and total_ms > SLOW_FACTOR * statistics.median(recent)
        )
        recent.append(total_ms)
        unexplained = (
            slow and not gc_full_ms and not span.notes.get("compile_misses")
        )
        if slow:
            self._bump("convergence.slow_events")
        if unexplained:
            self._bump("convergence.slow_events_unexplained")
        if self._log_sample_fn is None and not unexplained:
            return
        sample = span.to_log_sample()
        sample.add_double("gc_full_ms", gc_full_ms)
        sample.add_double("unstaged_ms", unstaged_ms)
        sample.add_int("slow", slow)
        by_stage: Dict[str, float] = {}
        for name, lo, hi in stages:
            by_stage[name] = by_stage.get(name, 0.0) + (hi - lo) * 1e3
        for name, ms in by_stage.items():
            sample.add_double(f"{ACCOUNT_STAGE_PREFIX}{name}_ms", ms)
        if unexplained:
            log.warning(
                "slow event with no full collection and no compile in it: %s",
                account_line(sample.values()),
            )
        if self._log_sample_fn is not None:
            self._log_sample_fn(sample)

    def log_perf_events(self, perf_events: Optional[PerfEvents]) -> None:
        """Convergence measurement (Fib.cpp:760-843)."""
        if not isinstance(perf_events, PerfEvents) or not perf_events.events:
            return
        first_ts = perf_events.events[0].unix_ts
        if self._recent_perf_ts >= first_ts:
            return  # stale sample
        self._recent_perf_ts = first_ts
        perf_events.add(
            self.config.my_node_name, "OPENR_FIB_ROUTES_PROGRAMMED"
        )
        total_ms = perf_events.events[-1].unix_ts - first_ts
        if self.config.enable_ordered_fib and self.kvstore_client is not None:
            # local programming time from holds-expiry → programmed
            hold_ts = next(
                (
                    e.unix_ts
                    for e in perf_events.events
                    if e.event_descr == "ORDERED_FIB_HOLDS_EXPIRED"
                ),
                None,
            )
            if hold_ts is not None:
                local_ms = perf_events.events[-1].unix_ts - hold_ts
                if 0 <= local_ms <= CONVERGENCE_MAX_MS:
                    self.kvstore_client.persist_key(
                        FIB_TIME_MARKER + self.config.my_node_name,
                        str(local_ms).encode(),
                    )
        if total_ms < 0 or total_ms > CONVERGENCE_MAX_MS:
            return
        self.perf_db.append(perf_events.copy())
        while len(self.perf_db) >= PERF_BUFFER_SIZE:
            self.perf_db.pop(0)
        self._bump("fib.convergence_time_ms", int(total_ms))
        self._bump("fib.route_convergence_events")
