"""Daemon composition root.

Behavioral port of openr/Main.cpp: builds the inter-module queues
(Main.cpp:244-250), constructs every module against its seams, starts them
in dependency order ConfigStore → Monitor → KvStore → PrefixManager →
PrefixAllocator → Spark → LinkMonitor → Decision → Fib → CtrlServer
(Main.cpp:355-586) and stops in reverse with queue closing
(Main.cpp:597-654). One asyncio loop replaces the per-module EventBase
threads; each module is an independent task set on that loop, watched by
the Watchdog.

Seams (all injectable, mirroring the reference's test wrappers):
  - io_provider:  Spark's packet transport (UDP or MockIoNetwork endpoint)
  - kv_transport: KvStore's peer transport (TCP or InProcessTransport)
  - fib_service:  route programming agent (NetlinkFibHandler or mock)
"""

from __future__ import annotations

import asyncio
import logging
from typing import Optional

from openr_tpu.config import Config
from openr_tpu.configstore import PersistentStore
from openr_tpu.ctrl import CtrlServer
from openr_tpu.decision import Decision, DecisionConfig
from openr_tpu.fib import Fib, FibConfig
from openr_tpu.kvstore import KvStore, KvStoreClient, KvStoreParams
from openr_tpu.linkmonitor.link_monitor import LinkMonitor, LinkMonitorConfig
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.monitor import (
    MetricsExporter,
    Monitor,
    Watchdog,
    WatchdogConfig,
)
from openr_tpu.monitor.spans import GC_WATCH
from openr_tpu.platform import MockFibHandler
from openr_tpu.prefixmanager import PrefixManager, PrefixManagerConfig
from openr_tpu.spark.spark import Spark, SparkConfig as SparkModuleConfig

log = logging.getLogger(__name__)


class OpenrDaemon:
    """All modules of one Open/R node on one asyncio loop."""

    def __init__(
        self,
        config: Config,
        *,
        io_provider,
        kv_transport,
        fib_service=None,
        config_store_path: Optional[str] = None,
        ctrl_port: Optional[int] = None,
        kvstore_host: str = "127.0.0.1",
        kvstore_port: int = 0,
        loop: Optional[asyncio.AbstractEventLoop] = None,
    ) -> None:
        from openr_tpu.kvstore import KvStoreTcpServer, TcpTransport
        from openr_tpu.utils.compile_cache import ensure_compile_cache

        # before the first compile: a restarted daemon loads its solver
        # executables from the persistent cache instead of recompiling
        ensure_compile_cache()
        self.config = config
        self._loop = loop
        # real-socket deployment: when KvStore peers over TCP, this daemon
        # must also *serve* the peer RPC surface, Spark must advertise the
        # serving port in its handshake, and LinkMonitor must peer by
        # host:port instead of node id
        self._kv_tcp = isinstance(kv_transport, TcpTransport)
        self._kv_transport = kv_transport
        self.kvstore_server: Optional[KvStoreTcpServer] = None
        c = config.config
        node = c.node_name
        areas = config.get_area_ids()

        # --- queues (Main.cpp:244-250) --------------------------------
        self.route_updates_queue = ReplicateQueue()
        self.interface_updates_queue = ReplicateQueue()
        self.neighbor_updates_queue = ReplicateQueue()
        self.prefix_updates_queue = ReplicateQueue()
        self.static_routes_queue = ReplicateQueue()
        self.log_sample_queue = ReplicateQueue()

        # --- config store ---------------------------------------------
        self.config_store = PersistentStore(
            config_store_path or f"/tmp/openr_tpu_{node}.bin",
            dryrun=config_store_path is None,
            loop=loop,
        )

        # --- monitor + watchdog + exporter ----------------------------
        mc = c.monitor_config
        self.monitor = Monitor(
            node,
            self.log_sample_queue.get_reader(),
            max_event_log=mc.max_event_log,
            rollup_window_s=mc.rollup_window_s,
            rollup_max_windows=mc.rollup_max_windows,
            loop=loop,
        )
        self.exporter = MetricsExporter(
            self.monitor,
            push_target=mc.exporter_push_target,
            push_interval_s=mc.exporter_push_interval_s,
            loop=loop,
        )
        # the exporter registers like any module so its own overhead
        # metrics (monitor.exporter.*) ride every scrape
        self.monitor.register_module("monitor", self.exporter)
        self.watchdog: Optional[Watchdog] = None
        if c.enable_watchdog:
            self.watchdog = Watchdog(
                WatchdogConfig(
                    interval_s=c.watchdog_config.interval_s,
                    thread_timeout_s=c.watchdog_config.thread_timeout_s,
                    max_memory_mb=c.watchdog_config.max_memory_mb,
                ),
                loop=loop,
            )

        # --- kvstore ---------------------------------------------------
        self.kvstore = KvStore(
            node,
            areas,
            kv_transport,
            KvStoreParams(
                node_id=node,
                ttl_decrement_ms=c.kvstore_config.ttl_decrement_ms,
                flood_rate=(
                    float(c.kvstore_config.flood_rate.flood_msg_per_sec)
                    if c.kvstore_config.flood_rate is not None
                    else None
                ),
                enable_flood_optimization=(
                    c.kvstore_config.enable_flood_optimization
                ),
                is_flood_root=c.kvstore_config.is_flood_root,
                use_native_store=c.kvstore_config.enable_native_store,
                damping_enabled=c.kvstore_config.damping_enabled,
                damping_half_life_s=c.kvstore_config.damping_half_life_s,
                damping_max_hold_s=c.kvstore_config.damping_max_hold_s,
                damping_suppress_limit=(
                    c.kvstore_config.damping_suppress_limit
                ),
                damping_reuse_limit=c.kvstore_config.damping_reuse_limit,
                quarantine_enabled=c.kvstore_config.quarantine_enabled,
                peer_suspect_failures=(
                    c.kvstore_config.peer_suspect_failures
                ),
                peer_quarantine_failures=(
                    c.kvstore_config.peer_quarantine_failures
                ),
                peer_probe_min_backoff=(
                    c.kvstore_config.peer_probe_min_backoff_s
                ),
                peer_probe_max_backoff=(
                    c.kvstore_config.peer_probe_max_backoff_s
                ),
                peer_probe_successes=c.kvstore_config.peer_probe_successes,
                anti_entropy_enabled=(
                    c.kvstore_config.anti_entropy_enabled
                ),
                anti_entropy_interval_s=float(
                    c.kvstore_config.sync_interval_s
                ),
                flood_duplicate_budget=(
                    c.kvstore_config.flood_duplicate_budget
                ),
                forensics_dir=c.decision_config.solver_forensics_dir,
            ),
            loop=loop,
            # flood-trace samples (FLOOD_TRACE) drain into the monitor's
            # event-log ring next to the convergence traces
            log_sample_fn=self.log_sample_queue.push,
        )
        # mutual-TLS contexts (Main.cpp:517-543): one server + one client
        # context shared by the ctrl server and the KvStore peering
        server_ssl = client_ssl = None
        if c.enable_secure_thrift_server:
            from openr_tpu.utils.tls import (
                client_ssl_context,
                server_ssl_context,
            )

            if not (c.x509_cert_path and c.x509_key_path and c.x509_ca_path):
                raise ValueError(
                    "enable_secure_thrift_server requires x509_cert_path, "
                    "x509_key_path and x509_ca_path"
                )
            server_ssl = server_ssl_context(
                c.x509_cert_path, c.x509_key_path, c.x509_ca_path
            )
            client_ssl = client_ssl_context(
                c.x509_ca_path, c.x509_cert_path, c.x509_key_path
            )
            if self._kv_tcp:
                kv_transport.set_ssl_context(client_ssl)
        self._server_ssl = server_ssl
        if self._kv_tcp:
            self.kvstore_server = KvStoreTcpServer(
                self.kvstore,
                host=kvstore_host,
                port=kvstore_port,
                ssl_context=server_ssl,
                tls_acceptable_peers=c.tls_acceptable_peers or None,
            )
        # config_store attaches the warm-boot version floors: after a
        # graceful restart, self-originated keys (prefix advertisements,
        # fibTime markers) re-advertise strictly above the versions peers
        # held through the GR window (docs/Robustness.md)
        self.kvstore_client = KvStoreClient(
            self.kvstore, node, loop, config_store=self.config_store
        )

        # --- prefix manager -------------------------------------------
        self.prefix_manager = PrefixManager(
            PrefixManagerConfig(node_name=node, areas=areas),
            self.kvstore_client,
            config_store=self.config_store,
            prefix_updates=self.prefix_updates_queue.get_reader(),
            route_updates=self.route_updates_queue.get_reader(),
            loop=loop,
        )

        # --- prefix allocator (optional) -------------------------------
        self.prefix_allocator = None
        if config.is_prefix_allocation_enabled():
            from openr_tpu.allocators import (
                PrefixAllocationMode,
                PrefixAllocationParams,
                PrefixAllocator,
                PrefixAllocatorConfig,
            )
            from openr_tpu.types import IpPrefix, PrefixEntry, PrefixType

            pac = c.prefix_allocation_config
            params = None
            if pac.seed_prefix and pac.allocate_prefix_len:
                params = PrefixAllocationParams(
                    IpPrefix(pac.seed_prefix), pac.allocate_prefix_len
                )
            self.prefix_allocator = PrefixAllocator(
                PrefixAllocatorConfig(
                    node_name=node,
                    mode=PrefixAllocationMode(pac.prefix_allocation_mode),
                    params=params,
                    set_loopback_addr=pac.set_loopback_addr,
                    loopback_iface=pac.loopback_interface,
                ),
                self.kvstore_client,
                config_store=self.config_store,
                on_advertise=lambda entry: (
                    self.prefix_manager.advertise_prefixes([entry])
                ),
                on_withdraw=lambda prefix: (
                    self.prefix_manager.withdraw_prefixes(
                        [
                            PrefixEntry(
                                prefix=prefix,
                                type=PrefixType.PREFIX_ALLOCATOR,
                            )
                        ]
                    )
                ),
                loop=loop,
            )

        # --- spark -----------------------------------------------------
        sc = c.spark_config
        self.spark = Spark(
            SparkModuleConfig(
                node_name=node,
                domain=c.domain,
                area_configs=[
                    (a.area_id, r)
                    for a in c.areas
                    for r in (a.neighbor_regexes or [".*"])
                ]
                or [("0", ".*")],
                hello_time=sc.hello_time_s,
                fastinit_hello_time=sc.fastinit_hello_time_ms / 1000.0,
                keepalive_time=sc.keepalive_time_s,
                hold_time=sc.hold_time_s,
                graceful_restart_time=sc.graceful_restart_time_s,
                **({"kvstore_host": kvstore_host} if self._kv_tcp else {}),
            ),
            io_provider,
            self.neighbor_updates_queue,
            loop=loop,
        )

        # --- link monitor ---------------------------------------------
        lmc = c.link_monitor_config
        self.link_monitor = LinkMonitor(
            LinkMonitorConfig(
                node_name=node,
                enable_rtt_metric=lmc.use_rtt_metric,
                flap_initial_backoff=lmc.linkflap_initial_backoff_ms / 1000,
                flap_max_backoff=lmc.linkflap_max_backoff_ms / 1000,
                areas=areas,
                peer_addr_mode="tcp" if self._kv_tcp else "node_id",
            ),
            self.neighbor_updates_queue.get_reader(),
            self.kvstore,
            self.spark,
            config_store=self.config_store,
            interface_updates_queue=self.interface_updates_queue,
            loop=loop,
        )

        # --- decision --------------------------------------------------
        dc = c.decision_config
        self.decision = Decision(
            DecisionConfig(
                my_node_name=node,
                areas=areas,
                solver_backend=dc.solver_backend,
                solver_mesh=(
                    tuple(dc.solver_mesh) if dc.solver_mesh else None
                ),
                solver_supervised=dc.solver_supervised,
                solver_failure_threshold=dc.solver_failure_threshold,
                solver_max_attempts=dc.solver_max_attempts,
                solver_deadline_s=dc.solver_deadline_s,
                solver_probe_interval_s=dc.solver_probe_interval_s,
                solver_probe_successes=dc.solver_probe_successes,
                solver_audit_interval=dc.solver_audit_interval,
                solver_mesh_degrade=dc.solver_mesh_degrade,
                solver_apsp=dc.solver_apsp,
                solver_apsp_max_nodes=dc.solver_apsp_max_nodes,
                solver_trace_ring=dc.solver_trace_ring,
                solver_forensics_dir=dc.solver_forensics_dir,
                solver_mem_headroom_frac=dc.solver_mem_headroom_frac,
                solver_mem_capacity_bytes=dc.solver_mem_capacity_bytes,
                enable_v4=c.enable_v4,
                compute_lfa_paths=dc.compute_lfa_paths,
                enable_ordered_fib=c.enable_ordered_fib_programming,
                bgp_use_igp_metric=c.bgp_use_igp_metric,
                debounce_min=dc.debounce_min_ms / 1000.0,
                debounce_max=dc.debounce_max_ms / 1000.0,
                eor_time_s=float(c.eor_time_s or 0),
            ),
            self.kvstore.updates_queue.get_reader(),
            self.route_updates_queue,
            static_routes_updates=self.static_routes_queue.get_reader(),
            loop=loop,
            # solver fault domain: the supervisor stamps solve sections
            # into the watchdog heartbeat map and emits breaker/audit
            # events into the monitor's log-sample ring
            watchdog=self.watchdog,
            log_sample_fn=self.log_sample_queue.push,
        )

        # --- fib -------------------------------------------------------
        if fib_service is None:
            if c.enable_fib_agent:
                # standalone native agent (platform_linux equivalent) at
                # fib_port; Fib's aliveSince keep-alive handles restarts
                from openr_tpu.platform import RemoteFibService

                fib_service = RemoteFibService(port=c.fib_port)
            elif config.is_netlink_fib_handler_enabled():
                from openr_tpu.platform import NetlinkFibHandler

                fib_service = NetlinkFibHandler(loop=loop)
            else:
                fib_service = MockFibHandler()
        self.fib_service = fib_service
        self.fib = Fib(
            FibConfig(
                my_node_name=node,
                dryrun=c.dryrun,
                enable_segment_routing=c.enable_segment_routing,
                enable_ordered_fib=c.enable_ordered_fib_programming,
                has_eor_time=c.eor_time_s is not None,
                cold_start_duration=c.fib_config.cold_start_duration_s,
                stale_sweep_deadline_s=c.fib_config.stale_sweep_deadline_s,
                # restart forensics share the solver fault domain's
                # artifact directory (PR 13 dump path)
                forensics_dir=dc.solver_forensics_dir,
            ),
            fib_service,
            self.route_updates_queue.get_reader(),
            self.interface_updates_queue.get_reader(),
            kvstore_client=self.kvstore_client,
            # finished convergence spans (CONVERGENCE_TRACE) drain into the
            # monitor's event-log ring like every other LogSample
            log_sample_fn=self.log_sample_queue.push,
            loop=loop,
        )

        # --- streaming control plane (docs/Streaming.md) ---------------
        from openr_tpu.streaming import (
            AdmissionConfig,
            AdmissionController,
            StreamConfig,
            StreamManager,
        )

        stc = c.stream_config
        self.stream_manager = StreamManager(
            kvstore_updates=self.kvstore.updates_queue,
            route_updates=self.route_updates_queue,
            config=StreamConfig(
                subscriber_max_pending=stc.subscriber_max_pending,
                coalesce_budget=stc.coalesce_budget,
                max_subscribers=stc.max_subscribers,
            ),
            loop=loop,
        )
        self.admission = AdmissionController(
            AdmissionConfig(
                capacity=stc.admission_capacity,
                max_wait_s=stc.admission_max_wait_s,
                max_queue=stc.admission_max_queue,
                max_queue_per_client=stc.admission_max_queue_per_client,
            )
        )

        # --- state journal (docs/Journal.md) ---------------------------
        from openr_tpu.journal import JournalConfig, StateJournal

        jc = c.journal_config
        self.journal = StateJournal(
            node,
            JournalConfig(
                enabled=jc.enabled,
                ring_size=jc.ring_size,
                key_history=jc.key_history,
                sample_every=jc.sample_every,
                path=jc.path,
                flush_interval_s=jc.flush_interval_s,
                min_compact_bytes=jc.min_compact_bytes,
            ),
            kvstore_updates=self.kvstore.updates_queue,
            route_updates=self.route_updates_queue,
            # replay re-derives routes through the CPU oracle with the
            # same flags Decision solves under
            solver_flags={
                "enable_v4": c.enable_v4,
                "compute_lfa_paths": dc.compute_lfa_paths,
                "enable_ordered_fib": c.enable_ordered_fib_programming,
                "bgp_use_igp_metric": c.bgp_use_igp_metric,
            },
            loop=loop,
        )

        # --- ctrl server ----------------------------------------------
        self.ctrl_server = CtrlServer(
            node,
            host="127.0.0.1",
            port=ctrl_port if ctrl_port is not None else c.openr_ctrl_port,
            kvstore=self.kvstore,
            decision=self.decision,
            fib=self.fib,
            link_monitor=self.link_monitor,
            prefix_manager=self.prefix_manager,
            monitor=self.monitor,
            exporter=self.exporter,
            config_store=self.config_store,
            config=config,
            stream_manager=self.stream_manager,
            admission=self.admission,
            journal=self.journal,
            loop=loop,
            ssl_context=self._server_ssl,
            tls_acceptable_peers=c.tls_acceptable_peers or None,
        )

        for name, module in (
            ("kvstore", self.kvstore),
            ("decision", self.decision),
            ("fib", self.fib),
            ("link_monitor", self.link_monitor),
            ("spark", self.spark),
            ("prefix_manager", self.prefix_manager),
            # the fan-out + admission layers register like modules so
            # ctrl.stream.* / ctrl.admission.* ride every scrape
            ("ctrl_stream", self.stream_manager),
            ("ctrl_admission", self.admission),
            ("journal", self.journal),
            # ctrl.decode_ms, and the process's full collections
            # (process.gc_ms): the one watch every daemon of a process shares
            ("ctrl", self.ctrl_server),
            ("process", GC_WATCH),
        ):
            self.monitor.register_module(name, module)

    # ------------------------------------------------------------------

    async def start(self) -> int:
        """Start modules in dependency order; returns the ctrl port."""
        if self.kvstore_server is not None:
            # serve KvStore peering before anyone can discover us; the
            # bound (possibly ephemeral) port goes into Spark's handshake
            await self.kvstore_server.start()
            self.spark.config.kvstore_cmd_port = self.kvstore_server.port
        GC_WATCH.acquire(self)
        self.monitor.start()
        self.exporter.start()  # push loop only when a sink is configured
        if self.watchdog is not None:
            for name in ("kvstore", "decision", "fib", "link_monitor"):
                self.watchdog.add_module(name)
            self.watchdog.start()
        self.prefix_manager.start()
        if self.prefix_allocator is not None:
            self.prefix_allocator.start()
        self.link_monitor.start()
        self.decision.start()
        self.fib.start()
        # fan-out dispatch must drain before the ctrl server can accept
        # subscribers (its readers consume the module queues continuously)
        self.stream_manager.start()
        self.journal.start()
        port = await self.ctrl_server.start()
        if self.config.config.enable_bgp_peering:
            # extension seam (Main.cpp:589-595, plugin/Plugin.h:24-34);
            # only build PluginArgs (and register its queue reader) when a
            # plugin is actually installed — an undrained reader would
            # accumulate every route update forever
            from openr_tpu.plugin import PluginArgs, has_plugin, plugin_start

            if has_plugin():
                plugin_start(
                    PluginArgs(
                        prefix_updates_queue=self.prefix_updates_queue,
                        static_routes_queue=self.static_routes_queue,
                        route_updates_reader=(
                            self.route_updates_queue.get_reader()
                        ),
                        config=self.config,
                    )
                )
        log.info(
            "openr-tpu daemon %s up, ctrl on :%d",
            self.config.node_name,
            port,
        )
        return port

    async def stop(self) -> None:
        """Reverse-order shutdown with queue closing (Main.cpp:597-654).

        Graceful restart: with `spark_config.graceful_restart_enabled`,
        restarting hellos go out FIRST — before any module stops — so
        neighbors enter the Spark RESTART hold (keeping adjacencies and
        the routes through them for graceful_restart_time_s) instead of
        tearing the node out of the topology on hold expiry. The restarted
        incarnation then warm-boots: Fib keeps the agent forwarding on
        stale routes, KvStore re-advertisements ride the persisted version
        floors (docs/Robustness.md "Graceful restart & warm boot")."""
        if self.config.config.spark_config.graceful_restart_enabled:
            self.spark.flood_restarting()
        if self.config.config.enable_bgp_peering:
            from openr_tpu.plugin import plugin_stop

            plugin_stop()
        await self.ctrl_server.stop()
        self.journal.stop()  # flushes the pending durable-log batch
        self.stream_manager.stop()
        self.fib.stop()
        self.decision.stop()
        self.link_monitor.stop()
        self.spark.stop()
        if self.prefix_allocator is not None:
            self.prefix_allocator.stop()
        self.prefix_manager.stop()
        self.kvstore_client.stop()
        if self.kvstore_server is not None:
            await self.kvstore_server.stop()
        if self._kv_tcp:
            self._kv_transport.close()  # persistent peer connections
        self.kvstore.stop()
        if self.watchdog is not None:
            self.watchdog.stop()
        self.exporter.stop()
        self.monitor.stop()
        GC_WATCH.release(self)
        self.config_store.stop()
        for q in (
            self.route_updates_queue,
            self.interface_updates_queue,
            self.neighbor_updates_queue,
            self.prefix_updates_queue,
            self.static_routes_queue,
            self.log_sample_queue,
        ):
            q.close()
