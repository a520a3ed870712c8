"""Typed daemon configuration.

Behavioral port of openr/if/OpenrConfig.thrift:180-244 (the OpenrConfig
struct with per-module sub-structs and defaults) and openr/config/Config.h
(the accessor class deriving per-area regex sets and feature predicates).
Loaded from a JSON file exactly like the reference loads thrift-JSON
(Main.cpp:199-207); unknown fields are rejected so typos fail loudly
(Config::Config runs a parse-validate pass).
"""

from __future__ import annotations

import dataclasses
import json
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from openr_tpu.types import PrefixForwardingAlgorithm, PrefixForwardingType


@dataclass
class KvstoreFloodRate:
    flood_msg_per_sec: int = 0
    flood_msg_burst_size: int = 0


@dataclass
class KvstoreConfig:
    """OpenrConfig.thrift KvstoreConfig:19."""

    key_ttl_ms: int = 300_000
    sync_interval_s: int = 60
    ttl_decrement_ms: int = 1
    flood_rate: Optional[KvstoreFloodRate] = None
    set_leaf_node: bool = False
    key_prefix_filters: List[str] = field(default_factory=list)
    key_originator_id_filters: List[str] = field(default_factory=list)
    enable_flood_optimization: bool = False
    is_flood_root: bool = False
    # keep the key->Value table + CRDT merge in the native C++ engine
    # (native/kvstore) when the library is available
    enable_native_store: bool = True
    # flood-storm damping: per-(key, originator) exponential penalty with a
    # hold-down (docs/Robustness.md "Hostile-network hardening")
    damping_enabled: bool = True
    damping_half_life_s: float = 8.0
    damping_max_hold_s: float = 30.0
    damping_suppress_limit: float = 8000.0
    damping_reuse_limit: float = 2000.0
    # peer-health quarantine ladder (healthy → suspect → quarantined →
    # probing) with probe-driven recovery hysteresis
    quarantine_enabled: bool = True
    peer_suspect_failures: int = 3
    peer_quarantine_failures: int = 6
    peer_probe_min_backoff_s: float = 0.1
    peer_probe_max_backoff_s: float = 2.0
    peer_probe_successes: int = 2
    # adaptive anti-entropy: `sync_interval_s` rounds arm only when flood
    # health (duplicate ratio / failures / wire rejects) is off budget
    anti_entropy_enabled: bool = True
    flood_duplicate_budget: float = 0.5


@dataclass
class LinkMonitorConfig:
    """OpenrConfig.thrift LinkMonitorConfig:35."""

    linkflap_initial_backoff_ms: int = 60_000
    linkflap_max_backoff_ms: int = 300_000
    use_rtt_metric: bool = True
    include_interface_regexes: List[str] = field(default_factory=list)
    exclude_interface_regexes: List[str] = field(default_factory=list)
    redistribute_interface_regexes: List[str] = field(default_factory=list)


@dataclass
class StepDetectorConfig:
    """OpenrConfig.thrift StepDetectorConfig:44."""

    fast_window_size: int = 10
    slow_window_size: int = 60
    lower_threshold: int = 2
    upper_threshold: int = 5
    ads_threshold: int = 500


@dataclass
class SparkConfig:
    """OpenrConfig.thrift SparkConfig:52."""

    neighbor_discovery_port: int = 6666
    hello_time_s: float = 20.0
    fastinit_hello_time_ms: float = 500.0
    keepalive_time_s: float = 2.0
    hold_time_s: float = 10.0
    graceful_restart_time_s: float = 30.0
    # graceful-restart warm boot (docs/Robustness.md "Graceful restart &
    # warm boot"): when set, the daemon's stop path floods restarting
    # hellos so neighbors enter the RESTART hold instead of dropping the
    # adjacency. Opt-in: a drained permanent shutdown should NOT leave
    # neighbors holding routes through the GR window.
    graceful_restart_enabled: bool = False
    step_detector_conf: StepDetectorConfig = field(
        default_factory=StepDetectorConfig
    )


@dataclass
class WatchdogConfig:
    """OpenrConfig.thrift WatchdogConfig:65."""

    interval_s: int = 20
    thread_timeout_s: int = 300
    max_memory_mb: int = 800


@dataclass
class MonitorConfig:
    """OpenrConfig.thrift MonitorConfig:71 + the continuous-telemetry
    knobs (docs/Monitoring.md): the event-log ring bound, the
    eviction-proof convergence-rollup window geometry, and the optional
    metrics push sink."""

    # bound of the LogSample ring (monitor/monitor.py). Samples evicted
    # from the ring are still covered by the windowed rollup, which folds
    # spans at record time — raising this buys raw-sample retention, not
    # report completeness.
    max_event_log: int = 100
    # convergence-rollup window geometry: per-stage histograms aggregate
    # into rollup_window_s-wide wall-clock windows, bounded at
    # rollup_max_windows (older windows fold into the evicted-events
    # count; their samples stay in the cumulative layer)
    rollup_window_s: float = 60.0
    rollup_max_windows: int = 120
    # metrics push mode: render the Prometheus exposition every
    # exporter_push_interval_s and push it to a sink — "host:port" (TCP)
    # or a file path (atomic replace) — with exponential backoff on
    # failure. None (default) = scrape-only.
    exporter_push_target: Optional[str] = None
    exporter_push_interval_s: float = 15.0


@dataclass
class PrefixAllocationConfig:
    """OpenrConfig.thrift PrefixAllocationConfig:98."""

    loopback_interface: str = "lo"
    set_loopback_addr: bool = False
    override_loopback_addr: bool = False
    prefix_allocation_mode: str = "DYNAMIC_LEAF_NODE"
    seed_prefix: Optional[str] = None
    allocate_prefix_len: Optional[int] = None


@dataclass
class AreaConfig:
    """OpenrConfig.thrift AreaConfig:135 — area id + interface/neighbor
    regex membership."""

    area_id: str
    interface_regexes: List[str] = field(default_factory=list)
    neighbor_regexes: List[str] = field(default_factory=list)


@dataclass
class DecisionConfigSection:
    """Decision knobs (Flags + OpenrConfig eor/debounce semantics) +
    the rebuild's solver backend selector (BASELINE.json north star)."""

    debounce_min_ms: float = 10.0
    debounce_max_ms: float = 250.0
    compute_lfa_paths: bool = False
    solver_backend: str = "cpu"  # 'cpu' | 'tpu'
    # (batch, graph) device-mesh shape for the tpu backend, e.g. [4, 2]
    # on a v5e-8; None/empty = single device
    solver_mesh: Optional[List[int]] = None
    # solver fault domain (docs/Robustness.md): supervision wraps the tpu
    # backend with classified retries, a CPU-fallback circuit breaker,
    # probe-driven recovery, and an every-Nth-solve warm-state audit
    solver_supervised: bool = True
    solver_failure_threshold: int = 3
    solver_max_attempts: int = 2
    solver_deadline_s: float = 30.0
    solver_probe_interval_s: float = 5.0
    solver_probe_successes: int = 2
    solver_audit_interval: int = 0
    # partial-mesh degradation: device-loss streaks shrink the solver
    # mesh over surviving chips before the breaker trips to the oracle
    solver_mesh_degrade: bool = True
    # resident blocked-FW all-pairs matrix (docs/Apsp.md) for areas up to
    # solver_apsp_max_nodes real nodes; keeps DeltaPath enabled under
    # compute_lfa_paths and serves KSP layer seeding + TE hard-scoring
    solver_apsp: bool = True
    solver_apsp_max_nodes: int = 4096
    # solver flight recorder (docs/Monitoring.md "Flight recorder &
    # profiling"): per-area SolveTrace ring bound and an optional
    # forensics-dump artifact directory
    solver_trace_ring: int = 64
    solver_forensics_dir: Optional[str] = None
    # device-memory observatory (docs/Monitoring.md "Device-memory
    # observatory"): capacity admission keeps this fraction of device
    # capacity free when predict_fit gates a layout, and an explicit
    # capacity override in bytes stands in when the backend exposes no
    # memory_stats (0 = auto-detect)
    solver_mem_headroom_frac: float = 0.10
    solver_mem_capacity_bytes: int = 0


@dataclass
class FibConfigSection:
    """Fib cold-start + warm-boot knobs (docs/Fib.md "Cold start, EOR and
    warm boot")."""

    # hold before the first full sync when NO eor_time_s gates it
    # (Fib.cpp:73-76 coldStartDuration). The seed's 0.0 default synced —
    # and wiped any surviving agent routes — before Decision had ever
    # converged; 1s gives the LSDB a fighting chance, and a node whose
    # agent carries warm-boot (stale) routes additionally gates the sync
    # on the first Decision route db regardless of this hold.
    cold_start_duration_s: float = 1.0
    # warm boot: routes recovered from the agent at start are marked
    # stale and kept forwarding until Decision's first converged route db
    # reconciles them; if convergence never arrives within this deadline
    # the stale set is force-flushed with a forensics dump
    # (fib.stale_sweep_deadline_s in the ISSUE/ops docs)
    stale_sweep_deadline_s: float = 300.0


@dataclass
class StreamConfigSection:
    """Streaming control plane knobs (docs/Streaming.md): the ctrl
    server's delta-subscription fan-out bounds and the admission queue
    in front of expensive RPCs."""

    # frames buffered per subscriber before coalescing kicks in
    subscriber_max_pending: int = 64
    # merged-delta entry budget after coalescing; beyond it the
    # subscriber's queue is dropped and a marked snapshot-resync is sent
    coalesce_budget: int = 4096
    # hard cap on concurrent subscriptions (typed server-busy beyond)
    max_subscribers: int = 1024
    # admission queue for runTeOptimize / getRouteDbComputed /
    # getConvergenceReport: concurrent cost units, bounded queue wait,
    # queue depth caps (global + per client — the fairness bound)
    admission_capacity: int = 2
    admission_max_wait_s: float = 2.0
    admission_max_queue: int = 16
    admission_max_queue_per_client: int = 4


@dataclass
class JournalConfigSection:
    """State-journal knobs (docs/Journal.md): bounded record ring +
    compacted base, the sampled-overhead guard cadence, and the optional
    crash-safe on-disk log."""

    enabled: bool = False
    # in-memory record ring bound; older records fold into the base
    ring_size: int = 4096
    # per-(area, key) publication-history entries for `kvstore history`
    key_history: int = 16
    # every Nth record takes perf_counter stamps into journal.record_ms
    # (0 disables the guard, never the recording)
    sample_every: int = 16
    # durable log file (RecordLog framing); None = memory only
    path: Optional[str] = None
    # append-batch debounce; a crash loses at most this window
    flush_interval_s: float = 0.2
    # appended-tail size that forces the next flush to compact
    min_compact_bytes: int = 65536


@dataclass
class OpenrConfig:
    """OpenrConfig.thrift OpenrConfig:180."""

    node_name: str = ""
    domain: str = "openr"
    areas: List[AreaConfig] = field(default_factory=list)
    listen_addr: str = "::"
    openr_ctrl_port: int = 2018
    dryrun: bool = False
    enable_v4: bool = True
    enable_netlink_fib_handler: bool = False
    # route programming through the standalone native agent binary
    # (onl_fib_agent, the platform_linux equivalent) at fib_port instead of
    # the in-process netlink handler
    enable_fib_agent: bool = False
    eor_time_s: Optional[int] = None
    prefix_forwarding_type: PrefixForwardingType = PrefixForwardingType.IP
    prefix_forwarding_algorithm: PrefixForwardingAlgorithm = (
        PrefixForwardingAlgorithm.SP_ECMP
    )
    enable_segment_routing: bool = False
    prefix_min_nexthop: Optional[int] = None
    kvstore_config: KvstoreConfig = field(default_factory=KvstoreConfig)
    link_monitor_config: LinkMonitorConfig = field(
        default_factory=LinkMonitorConfig
    )
    spark_config: SparkConfig = field(default_factory=SparkConfig)
    decision_config: DecisionConfigSection = field(
        default_factory=DecisionConfigSection
    )
    enable_watchdog: bool = False
    watchdog_config: WatchdogConfig = field(default_factory=WatchdogConfig)
    enable_prefix_allocation: bool = False
    prefix_allocation_config: PrefixAllocationConfig = field(
        default_factory=PrefixAllocationConfig
    )
    enable_ordered_fib_programming: bool = False
    fib_config: FibConfigSection = field(default_factory=FibConfigSection)
    fib_port: int = 60100
    enable_rib_policy: bool = False
    monitor_config: MonitorConfig = field(default_factory=MonitorConfig)
    stream_config: StreamConfigSection = field(
        default_factory=StreamConfigSection
    )
    journal_config: JournalConfigSection = field(
        default_factory=JournalConfigSection
    )
    enable_bgp_peering: bool = False
    bgp_use_igp_metric: bool = False
    # mutual TLS for the ctrl server and KvStore TCP peering
    # (openr/Main.cpp:517-543 TLS setup semantics)
    enable_secure_thrift_server: bool = False
    x509_cert_path: Optional[str] = None
    x509_key_path: Optional[str] = None
    x509_ca_path: Optional[str] = None
    tls_acceptable_peers: List[str] = field(default_factory=list)


_ENUM_FIELDS = {
    "prefix_forwarding_type": PrefixForwardingType,
    "prefix_forwarding_algorithm": PrefixForwardingAlgorithm,
}


def _from_dict(cls, data: Dict[str, Any]):
    """Recursive dataclass hydration; unknown keys raise (validate pass)."""
    field_map = {f.name: f for f in dataclasses.fields(cls)}
    kwargs = {}
    for key, value in data.items():
        if key not in field_map:
            raise ValueError(f"unknown config field {cls.__name__}.{key}")
        f = field_map[key]
        if key in _ENUM_FIELDS and isinstance(value, str):
            value = _ENUM_FIELDS[key][value]
        elif (
            f.default_factory is not dataclasses.MISSING  # type: ignore
            and dataclasses.is_dataclass(f.default_factory)
            and isinstance(value, dict)
        ):
            value = _from_dict(f.default_factory, value)
        elif key == "areas" and isinstance(value, list):
            value = [_from_dict(AreaConfig, v) for v in value]
        elif key == "flood_rate" and isinstance(value, dict):
            value = _from_dict(KvstoreFloodRate, value)
        elif key == "step_detector_conf" and isinstance(value, dict):
            value = _from_dict(StepDetectorConfig, value)
        kwargs[key] = value
    return cls(**kwargs)


class AreaConfiguration:
    """Compiled area membership matcher (Config.h:21, derived regex sets)."""

    def __init__(self, area: AreaConfig) -> None:
        self.area_id = area.area_id
        self._iface_res = [re.compile(r) for r in area.interface_regexes]
        self._neighbor_res = [re.compile(r) for r in area.neighbor_regexes]

    def matches_interface(self, if_name: str) -> bool:
        return any(r.fullmatch(if_name) for r in self._iface_res)

    def matches_neighbor(self, node_name: str) -> bool:
        return any(r.fullmatch(node_name) for r in self._neighbor_res)


class Config:
    """Accessor wrapper (openr/config/Config.h:34): feature predicates +
    derived per-area regex matchers."""

    DEFAULT_AREA = "0"

    def __init__(self, config: OpenrConfig) -> None:
        if not config.node_name:
            raise ValueError("node_name is required")
        self.config = config
        self.area_configurations = [
            AreaConfiguration(a) for a in config.areas
        ]

    @staticmethod
    def load_file(path: str) -> "Config":
        """Load thrift-JSON-style config file (Main.cpp:199-207)."""
        with open(path) as f:
            data = json.load(f)
        return Config(_from_dict(OpenrConfig, data))

    @staticmethod
    def from_dict(data: Dict[str, Any]) -> "Config":
        return Config(_from_dict(OpenrConfig, data))

    # -- derived -----------------------------------------------------------

    @property
    def node_name(self) -> str:
        return self.config.node_name

    def get_area_ids(self) -> List[str]:
        if not self.config.areas:
            return [self.DEFAULT_AREA]
        return [a.area_id for a in self.config.areas]

    def get_area_for(
        self, if_name: str = "", neighbor_name: str = ""
    ) -> Optional[str]:
        """First area whose regexes match (Spark area negotiation seam)."""
        if not self.area_configurations:
            return self.DEFAULT_AREA
        for area in self.area_configurations:
            if if_name and area.matches_interface(if_name):
                return area.area_id
            if neighbor_name and area.matches_neighbor(neighbor_name):
                return area.area_id
        return None

    # -- feature predicates (Config.h:60-123) ------------------------------

    def is_v4_enabled(self) -> bool:
        return self.config.enable_v4

    def is_segment_routing_enabled(self) -> bool:
        return self.config.enable_segment_routing

    def is_ordered_fib_programming_enabled(self) -> bool:
        return self.config.enable_ordered_fib_programming

    def is_netlink_fib_handler_enabled(self) -> bool:
        return self.config.enable_netlink_fib_handler

    def is_prefix_allocation_enabled(self) -> bool:
        return self.config.enable_prefix_allocation

    def is_rib_policy_enabled(self) -> bool:
        return self.config.enable_rib_policy

    def is_watchdog_enabled(self) -> bool:
        return self.config.enable_watchdog

    def is_dryrun(self) -> bool:
        return self.config.dryrun
