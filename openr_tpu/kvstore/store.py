"""KvStore: per-area replicated store with CRDT merge, TTL, sync, flooding.

Behavioral port of openr/kvstore/KvStore.{h,cpp}:
  - merge_key_values (KvStore.cpp:261-411): the CRDT merge — higher version
    wins; same version → higher originatorId; same originator → higher value
    bytes; identical value → retain higher ttlVersion; ttl-refresh updates
    (no value) bump ttl/ttlVersion only.
  - compare_values (KvStore.cpp:416-450): 3-way ordering used by the
    difference dump; -2 = unknown (hash mismatch but no bodies).
  - TTL countdown queue (KvStore.h:64-80, cleanup KvStore.cpp:2594-2644):
    lazily-invalidated heap entries; expiry floods expiredKeys.
  - 3-way full sync (KvStore.cpp:1381/1331/2705): requester sends its
    hashes; responder returns better/missing keys + tobeUpdatedKeys; the
    requester finalizes by pushing those keys back.
  - flooding (KvStore.cpp:2851-2970): nodeIds path vector appended with our
    id, never flood back to the sender, token-bucket rate limiting with a
    merge buffer (KvStore.cpp:2648-2702).
  - peer FSM IDLE → SYNCING → INITIALIZED (KvStore.h:46-62) with
    exponential backoff on transport failure.

Flood tracing (docs/Monitoring.md): every flooded publication carries a
wall-clock PerfEvents hop trace next to the nodeIds path vector —
KVSTORE_FLOOD_ORIGINATED at the origin, one KVSTORE_FLOOD_RECEIVED per
hop — so each store exports per-hop flood latency (`kvstore.flood.hop_ms`),
origin-to-here latency (`kvstore.flood.e2e_ms`), flood-buffer queue delay
(`kvstore.flood.buffer_delay_ms`) and a redundant-flood ratio
(`kvstore.flood.duplicates` / `kvstore.flood.received`), and emits one
FLOOD_TRACE LogSample per received flood for the cross-node convergence
report (monitor/report.py, ctrl getConvergenceReport).
"""

from __future__ import annotations

import asyncio
import enum
import heapq
import random
import time
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from openr_tpu.messaging import ReplicateQueue
from openr_tpu.monitor.monitor import LogSample
from openr_tpu.monitor.spans import stage
from openr_tpu.testing.faults import fault_point
from openr_tpu.utils.ownership import owned_by
from openr_tpu.types import (
    KeyVals,
    PerfEvents,
    Publication,
    TTL_INFINITY,
    Value,
    generate_hash,
)
from openr_tpu.utils import AsyncThrottle, ExponentialBackoff
from openr_tpu.utils.counters import CountersMixin, HistogramsMixin
from openr_tpu.kvstore.transport import KvStoreTransport

# flood-hop PerfEvent descriptors (ride the KEY_SET RPC, wire.py); Decision
# maps them onto convergence-span stages (decision.py:_FLOOD_*)
FLOOD_ORIGINATED_EVENT = "KVSTORE_FLOOD_ORIGINATED"
FLOOD_RECEIVED_EVENT = "KVSTORE_FLOOD_RECEIVED"
# one LogSample per received flooded publication (docs/Monitoring.md
# event catalog): hop count, per-hop + origin-to-here latency, duplicate flag
FLOOD_TRACE_EVENT = "FLOOD_TRACE"
# peer-health quarantine ladder events (docs/Monitoring.md event catalog):
# one sample when a peer trips into quarantine (with the forensics dump id)
# and one when the probe path recovers it
PEER_QUARANTINED_EVENT = "KVSTORE_PEER_QUARANTINED"
PEER_RECOVERED_EVENT = "KVSTORE_PEER_RECOVERED"
# hop-trace length bound: the origin stamp plus the most recent hops. On
# large-diameter topologies (a 256-node emulated ring) an unbounded trace
# is O(diameter) per-copy per-forward — O(diameter²) allocations per
# publication — for stamps nothing reads: per-hop latency uses the LAST
# stamp, origin-to-here the FIRST. The nodeIds path vector stays complete
# (it is load-bearing for loop prevention); only the timing trace is capped.
FLOOD_TRACE_MAX_EVENTS = 17


# ---------------------------------------------------------------------------
# pure functions
# ---------------------------------------------------------------------------


def merge_key_values(
    store: KeyVals,
    key_vals: KeyVals,
    filters: Optional["KvStoreFilters"] = None,
) -> KeyVals:
    """Merge key_vals into store; return the accepted updates to flood."""
    native_merge = getattr(store, "native_merge", None)
    if native_merge is not None:
        return native_merge(key_vals, filters)
    updates: KeyVals = {}
    for key, value in key_vals.items():
        if filters is not None and not filters.key_match(key, value):
            continue

        # versions start at 1 (KvStore.cpp:277-279); reject anything lower
        if value.version < 1:
            continue

        # TTL must be infinite or positive
        if value.ttl != TTL_INFINITY and value.ttl <= 0:
            continue

        existing = store.get(key)
        my_version = existing.version if existing is not None else 0
        if value.version < my_version:
            continue  # stale

        update_all = False
        update_ttl = False
        if value.value is not None:
            if value.version > my_version:
                update_all = True
            elif value.originator_id > existing.originator_id:
                update_all = True
            elif value.originator_id == existing.originator_id:
                if existing.value is None or value.value > existing.value:
                    # deterministic winner on divergent same-version values
                    update_all = True
                elif value.value == existing.value:
                    if value.ttl_version > existing.ttl_version:
                        update_ttl = True

        # ttl refresh (no value body)
        if (
            value.value is None
            and existing is not None
            and value.version == existing.version
            and value.originator_id == existing.originator_id
            and value.ttl_version > existing.ttl_version
        ):
            update_ttl = True

        if not update_all and not update_ttl:
            continue

        if update_all:
            new_value = value.copy()
            if new_value.hash is None:
                new_value.hash = generate_hash(
                    new_value.version, new_value.originator_id, new_value.value
                )
            store[key] = new_value
            # flood the hash-filled copy (the reference fills the hash at
            # the originator before storing/flooding) so every forwarded
            # frame is integrity-checkable end to end
            updates[key] = new_value
        elif update_ttl:
            existing.ttl = value.ttl
            existing.ttl_version = value.ttl_version
            updates[key] = value
    return updates


def compare_values(v1: Value, v2: Value) -> int:
    """1: v1 better, -1: v2 better, 0: same, -2: unknown."""
    if v1.version != v2.version:
        return 1 if v1.version > v2.version else -1
    if v1.originator_id != v2.originator_id:
        return 1 if v1.originator_id > v2.originator_id else -1
    if v1.hash is not None and v2.hash is not None and v1.hash == v2.hash:
        if v1.ttl_version != v2.ttl_version:
            return 1 if v1.ttl_version > v2.ttl_version else -1
        return 0
    if v1.value is not None and v2.value is not None:
        if v1.value == v2.value:
            if v1.ttl_version != v2.ttl_version:
                return 1 if v1.ttl_version > v2.ttl_version else -1
            return 0
        return 1 if v1.value > v2.value else -1
    return -2


class KvStoreFilters:
    """Key-prefix and originator filters (KvStore.h:82-119)."""

    def __init__(
        self,
        key_prefixes: Optional[List[str]] = None,
        originator_ids: Optional[Set[str]] = None,
    ) -> None:
        self.key_prefixes = key_prefixes or []
        self.originator_ids = originator_ids or set()

    def _prefix_match(self, key: str) -> bool:
        if not self.key_prefixes:
            return True
        return any(key.startswith(p) for p in self.key_prefixes)

    def key_match(self, key: str, value: Value) -> bool:
        """OR semantics: match by prefix or by originator."""
        if not self.key_prefixes and not self.originator_ids:
            return True
        if self.key_prefixes and self._prefix_match(key):
            return True
        if self.originator_ids and value.originator_id in self.originator_ids:
            return True
        return False

    def key_match_all(self, key: str, value: Value) -> bool:
        """AND semantics."""
        return self._prefix_match(key) and (
            not self.originator_ids
            or value.originator_id in self.originator_ids
        )


# ---------------------------------------------------------------------------
# peers
# ---------------------------------------------------------------------------


class PeerState(enum.Enum):
    IDLE = "IDLE"
    SYNCING = "SYNCING"
    INITIALIZED = "INITIALIZED"


class PeerEvent(enum.Enum):
    PEER_ADD = "PEER_ADD"
    SYNC_RESP_RCVD = "SYNC_RESP_RCVD"
    SYNC_TIMEOUT = "SYNC_TIMEOUT"
    API_ERROR = "API_ERROR"


# state transition matrix (KvStore.h:421)
_PEER_FSM: Dict[Tuple[PeerState, PeerEvent], PeerState] = {
    (PeerState.IDLE, PeerEvent.PEER_ADD): PeerState.SYNCING,
    (PeerState.SYNCING, PeerEvent.SYNC_RESP_RCVD): PeerState.INITIALIZED,
    (PeerState.SYNCING, PeerEvent.SYNC_TIMEOUT): PeerState.IDLE,
    (PeerState.SYNCING, PeerEvent.API_ERROR): PeerState.IDLE,
    (PeerState.INITIALIZED, PeerEvent.SYNC_TIMEOUT): PeerState.IDLE,
    (PeerState.INITIALIZED, PeerEvent.API_ERROR): PeerState.IDLE,
    (PeerState.INITIALIZED, PeerEvent.SYNC_RESP_RCVD): PeerState.INITIALIZED,
}


class PeerHealth(enum.Enum):
    """Per-peer scoring ladder (mirror of the solver breaker FSM):
    consecutive transport failures walk HEALTHY → SUSPECT → QUARANTINED;
    a quarantined peer receives no floods, only probe-driven full syncs
    (QUARANTINED ⇄ PROBING), and recovers with hysteresis after
    `peer_probe_successes` consecutive probe successes."""

    HEALTHY = "HEALTHY"
    SUSPECT = "SUSPECT"
    QUARANTINED = "QUARANTINED"
    PROBING = "PROBING"


@dataclass(frozen=True)
class PeerSpec:
    """Addressing info for one peer (thrift::PeerSpec equivalent)."""

    peer_addr: str  # transport address (node id for in-process)
    support_flood_optimization: bool = False


@dataclass
class _Peer:
    spec: PeerSpec
    backoff: ExponentialBackoff
    state: PeerState = PeerState.IDLE
    health: PeerHealth = PeerHealth.HEALTHY
    failures: int = 0  # consecutive transport failures
    probes: int = 0
    probe_streak: int = 0  # consecutive probe successes (hysteresis)
    floods_skipped: int = 0
    quarantined_at: float = 0.0
    probe_backoff: Optional[ExponentialBackoff] = None


@dataclass
class _DampingEntry:
    """Flood-storm damping state for one (key, originator): an exponential
    penalty (decayed with `damping_half_life_s`) accrued on every
    value-bearing accepted update; crossing `damping_suppress_limit` puts
    the key behind a hold-down until the penalty decays below
    `damping_reuse_limit` (or `damping_max_hold_s` elapses), at which point
    the CURRENT store value is flooded — latest always wins on release."""

    penalty: float = 0.0
    last_decay: float = 0.0  # monotonic ts of the last decay application
    held: bool = False
    held_since: float = 0.0


@dataclass
class _TtlEntry:
    expiry: float
    key: str
    epoch: int  # store-write epoch; stale entries fail the epoch check

    def __lt__(self, other: "_TtlEntry") -> bool:
        return self.expiry < other.expiry


class _TokenBucket:
    """Flood rate limiter (folly::BasicTokenBucket equivalent)."""

    def __init__(self, rate: float, burst: float) -> None:
        self.rate = rate
        self.burst = burst
        self._tokens = burst
        self._last = time.monotonic()

    def consume(self, n: float = 1.0) -> bool:
        now = time.monotonic()
        self._tokens = min(
            self.burst, self._tokens + (now - self._last) * self.rate
        )
        self._last = now
        if self._tokens >= n:
            self._tokens -= n
            return True
        return False


# ---------------------------------------------------------------------------
# KvStoreDb — one area
# ---------------------------------------------------------------------------


@dataclass
class KvStoreParams:
    node_id: str
    ttl_decrement_ms: int = 1  # decrement applied when forwarding ttls
    flood_rate: Optional[float] = None  # msgs/sec; None = unlimited
    flood_burst: float = 32.0
    flood_buffer_delay: float = 0.1  # kFloodPendingPublication (100ms)
    sync_max_backoff: float = 8.0
    filters: Optional[KvStoreFilters] = None
    # DUAL flood-topology optimization: flood on a spanning tree instead of
    # the full peer mesh (KvstoreConfig.enable_flood_optimization)
    enable_flood_optimization: bool = False
    is_flood_root: bool = False
    # keep the key->Value table and CRDT merge in the native C++ engine
    # (native/kvstore); falls back to the Python dict if the library is
    # unavailable
    use_native_store: bool = False
    # deterministic seed for jittered backoffs / anti-entropy peer choice;
    # None derives a per-node seed from the node id (still deterministic)
    jitter_seed: Optional[int] = None
    # flood-storm damping (per-(key, originator) exponential penalty)
    damping_enabled: bool = True
    damping_penalty: float = 1000.0  # accrued per value-bearing update
    damping_suppress_limit: float = 8000.0  # hold-down trip threshold
    damping_reuse_limit: float = 2000.0  # release threshold after decay
    damping_half_life_s: float = 8.0
    damping_max_hold_s: float = 30.0  # hard cap on any hold-down
    damping_sweep_s: float = 0.5  # decay/release sweep cadence
    # adjacency withdrawals must propagate immediately; TTL expiry is
    # structurally exempt (expired_keys never pass through damping)
    damping_exempt_prefixes: Tuple[str, ...] = ("adj:",)
    # peer-health quarantine ladder
    quarantine_enabled: bool = True
    peer_suspect_failures: int = 3  # consecutive failures → SUSPECT
    peer_quarantine_failures: int = 6  # consecutive failures → QUARANTINED
    peer_probe_min_backoff: float = 0.1
    peer_probe_max_backoff: float = 2.0
    peer_probe_successes: int = 2  # hysteresis before recovery
    # adaptive anti-entropy: periodic rounds arm only when flood health is
    # off budget (duplicate ratio, sync/flood failures, wire rejects)
    anti_entropy_enabled: bool = True
    anti_entropy_interval_s: float = 60.0
    flood_duplicate_budget: float = 0.5  # duplicates/received per interval
    # directory for quarantine forensics artifacts (None = in-memory only)
    forensics_dir: Optional[str] = None


@owned_by("kvstore-loop")
class KvStoreDb(CountersMixin, HistogramsMixin):
    def __init__(
        self,
        area: str,
        params: KvStoreParams,
        transport: KvStoreTransport,
        updates_queue: ReplicateQueue,
        loop: Optional[asyncio.AbstractEventLoop] = None,
        histograms: Optional[Dict] = None,
        log_sample_fn=None,
    ) -> None:
        self.area = area
        self.params = params
        self.transport = transport
        self.updates_queue = updates_queue
        self._loop = loop
        # flood-latency histograms; the multi-area container passes ONE
        # shared dict so per-node flood stats aggregate across areas (the
        # monitor reads the container's `histograms` attribute)
        self.histograms: Dict = histograms if histograms is not None else {}
        # sink for FLOOD_TRACE LogSamples (the daemon's monitor queue push;
        # None drops them — flood counters/histograms still record)
        self._log_sample_fn = log_sample_fn
        self.store: KeyVals = {}
        if params.use_native_store:
            from openr_tpu.kvstore.native import (
                NativeKvTable,
                native_kv_available,
            )

            if native_kv_available():
                self.store = NativeKvTable()
        self.peers: Dict[str, _Peer] = {}
        self._ttl_heap: List[_TtlEntry] = []
        # per-key write epoch: bumped on every accepted update so TTL heap
        # entries from superseded writes can never evict the current value
        self._ttl_epochs: Dict[str, int] = {}
        self._ttl_timer: Optional[asyncio.TimerHandle] = None
        self._flood_limiter = (
            _TokenBucket(params.flood_rate, params.flood_burst)
            if params.flood_rate
            else None
        )
        # pending buffered flood keys (merge buffer under rate limiting)
        self._publication_buffer: Set[str] = set()
        self._buffer_flush = AsyncThrottle(
            params.flood_buffer_delay, self._flush_buffered, loop=loop
        )
        # flood-buffer queue-delay bookkeeping: when the first key entered
        # the buffer, plus the oldest buffered publication's span stages /
        # hop trace (the merged flush re-attaches them, same oldest-event
        # rule Decision's debounce uses)
        self._buffer_first_ts: Optional[float] = None
        self._buffer_span_stages: Optional[List[Tuple[str, float]]] = None
        self._buffer_perf_events: Optional[PerfEvents] = None
        self._retry_pending: Set[str] = set()
        self._sync_tasks: Set[asyncio.Task] = set()
        self.counters: Dict[str, int] = {}
        # deterministic per-node rng: decorrelated-jitter backoffs and
        # anti-entropy peer choice replay identically under a fixed seed
        seed = (
            params.jitter_seed
            if params.jitter_seed is not None
            else zlib.crc32(f"{params.node_id}/{area}".encode())
        )
        self._rng = random.Random(seed)
        # monotonic expiry deadline per finite-ttl key: the authoritative
        # remaining-lifetime record (stored Value.ttl is the ORIGINAL ttl)
        self._ttl_expiry: Dict[str, float] = {}
        # flood-storm damping state + lazy decay/release sweep timer
        self._damping: Dict[Tuple[str, str], _DampingEntry] = {}
        self._damping_timer: Optional[asyncio.TimerHandle] = None
        # adaptive anti-entropy: lazy timer + counter snapshot from the
        # previous tick (flood-health deltas are per-interval)
        self._ae_timer: Optional[asyncio.TimerHandle] = None
        self._ae_last: Dict[str, int] = {}
        # quarantine forensics recorder (lazy, PR 13 flight-recorder flow)
        self._forensics = None
        # DUAL flood-topology optimization (KvStore.h:193 inherits DualNode;
        # composed here): SPT per flood-root, flood only to SPT peers
        self.dual: Optional["_KvDualNode"] = None
        if params.enable_flood_optimization:
            self.dual = _KvDualNode(self)

    # -- basic API ---------------------------------------------------------

    def loop(self) -> asyncio.AbstractEventLoop:
        return self._loop or asyncio.get_event_loop()

    def get_key(self, key: str) -> Optional[Value]:
        return self.store.get(key)

    def get_key_vals(self, keys: List[str]) -> Publication:
        pub = Publication(area=self.area, ts_monotonic=time.monotonic())
        for key in keys:
            v = self.store.get(key)
            if v is not None:
                pub.key_vals[key] = v
        return pub

    def dump_all(
        self,
        filters: Optional[KvStoreFilters] = None,
        match_all: bool = False,
    ) -> Publication:
        pub = Publication(area=self.area, ts_monotonic=time.monotonic())
        filters = filters or KvStoreFilters()
        match = filters.key_match_all if match_all else filters.key_match
        for key, value in self.store.items():
            if match(key, value):
                pub.key_vals[key] = value
        return pub

    def dump_hashes(
        self, filters: Optional[KvStoreFilters] = None
    ) -> Publication:
        pub = Publication(area=self.area, ts_monotonic=time.monotonic())
        filters = filters or KvStoreFilters()
        for key, value in self.store.items():
            if filters.key_match(key, value):
                pub.key_vals[key] = Value(
                    version=value.version,
                    originator_id=value.originator_id,
                    value=None,
                    ttl=value.ttl,
                    ttl_version=value.ttl_version,
                    hash=value.hash,
                )
        return pub

    def dump_difference(
        self, my_key_vals: KeyVals, req_key_vals: KeyVals
    ) -> Publication:
        """3-way sync difference (KvStore.cpp:1331-1375): keyVals = keys
        where we are better/only-us; tobe_updated_keys = keys where the
        requester is better/only-them."""
        pub = Publication(area=self.area, ts_monotonic=time.monotonic())
        pub.tobe_updated_keys = []
        for key in set(my_key_vals) | set(req_key_vals):
            mine = my_key_vals.get(key)
            theirs = req_key_vals.get(key)
            if mine is None:
                pub.tobe_updated_keys.append(key)
                continue
            if theirs is None:
                pub.key_vals[key] = mine
                continue
            rc = compare_values(mine, theirs)
            if rc in (1, -2):
                pub.key_vals[key] = mine
            if rc in (-1, -2):
                pub.tobe_updated_keys.append(key)
        return pub

    # -- local writes ------------------------------------------------------

    # analysis: shared — sync ctrl handler, loop-serialized with the owner
    def set_key_vals(
        self, key_vals: KeyVals, span_stages=None
    ) -> KeyVals:
        """Local API write (thrift setKvStoreKeyVals): merge + flood.

        `span_stages` — monotonic pre-publish convergence-span marks from
        the producing module (LinkMonitor's spark→advertise chain) — ride
        the local publication so Decision's span starts at the Spark event,
        not at this store's publish stamp."""
        # merge + publish, to the push onto the subscribers' queues
        with stage("kvstore.set_key_vals", self.histograms):
            updates = merge_key_values(
                self.store, key_vals, self.params.filters
            )
            self._update_ttl_countdown(updates)
            if updates:
                self._bump("kvstore.updated_key_vals", len(updates))
                flood = self._damp_updates(updates)
                if flood:
                    self.flood_publication(
                        Publication(
                            key_vals=flood,
                            area=self.area,
                            span_stages=span_stages,
                        )
                    )
        return updates

    def handle_set_key_vals(
        self,
        key_vals: KeyVals,
        node_ids: Optional[List[str]],
        perf_events: Optional[PerfEvents] = None,
    ) -> None:
        """KEY_SET arriving from a peer (flooded publication).

        Flood-hop accounting happens here: the incoming wall-clock hop
        trace (`perf_events`) yields this hop's latency and the
        origin-to-here latency; the nodeIds path vector is the hop count;
        a merge that accepts nothing is a redundant (duplicate) flood."""
        recv_wall_ms = time.time() * 1e3
        hop_count = len(node_ids) if node_ids else 0
        self._bump("kvstore.flood.received")
        self.counters["kvstore.flood.hop_count_last"] = hop_count
        hop_ms: Optional[float] = None
        e2e_ms: Optional[float] = None
        if perf_events is not None and perf_events.events:
            hop_ms = max(0.0, recv_wall_ms - perf_events.events[-1].unix_ts)
            e2e_ms = max(0.0, recv_wall_ms - perf_events.events[0].unix_ts)
            self._observe("kvstore.flood.hop_ms", hop_ms)
            self._observe("kvstore.flood.e2e_ms", e2e_ms)
        if node_ids is not None and self.params.node_id in node_ids:
            self._bump("kvstore.looped_publications")
            self._bump("kvstore.flood.duplicates")
            self._emit_flood_trace(
                node_ids, hop_count, len(key_vals), 0, hop_ms, e2e_ms
            )
            return  # path-vector loop prevention (KvStore.cpp:2874-2884)
        updates = merge_key_values(self.store, key_vals, self.params.filters)
        self._update_ttl_countdown(updates)
        if not updates:
            self._bump("kvstore.flood.duplicates")
        self._emit_flood_trace(
            node_ids, hop_count, len(key_vals), len(updates), hop_ms, e2e_ms
        )
        flood = self._damp_updates(updates) if updates else updates
        if flood:
            traced = perf_events.copy() if perf_events is not None else None
            if traced is not None:
                traced.add_fine(self.params.node_id, FLOOD_RECEIVED_EVENT)
                if len(traced.events) > FLOOD_TRACE_MAX_EVENTS:
                    traced.events = [traced.events[0]] + traced.events[
                        -(FLOOD_TRACE_MAX_EVENTS - 1):
                    ]
            self.flood_publication(
                Publication(
                    key_vals=flood,
                    area=self.area,
                    node_ids=list(node_ids or []),
                    perf_events=traced,
                )
            )

    def _emit_flood_trace(
        self,
        node_ids: Optional[List[str]],
        hop_count: int,
        keys: int,
        updated: int,
        hop_ms: Optional[float],
        e2e_ms: Optional[float],
    ) -> None:
        if self._log_sample_fn is None:
            return
        sample = LogSample()
        sample.add_string("event", FLOOD_TRACE_EVENT)
        sample.add_string("area", self.area)
        sample.add_string("origin", node_ids[0] if node_ids else "")
        sample.add_int("hop_count", hop_count)
        sample.add_int("keys", keys)
        sample.add_int("updated", updated)
        sample.add_int("duplicate", 0 if updated else 1)
        if hop_ms is not None:
            sample.add_double("hop_ms", hop_ms)
        if e2e_ms is not None:
            sample.add_double("e2e_ms", e2e_ms)
        try:
            self._log_sample_fn(sample)
        except Exception:
            # a closed monitor queue must never break the flood path
            self._bump("kvstore.flood.trace_drops")

    def handle_dump(self, key_val_hashes: Optional[KeyVals]) -> Publication:
        """KEY_DUMP serving side; with hashes, serve the 3-way difference."""
        pub = self.dump_all()
        if key_val_hashes is not None:
            pub = self.dump_difference(pub.key_vals, key_val_hashes)
        self._update_publication_ttl(pub)
        # full-sync responses are publications too: stamp so any downstream
        # span seeded from this object never starts from a missing stamp
        pub.ts_monotonic = time.monotonic()
        return pub

    # -- flood-storm damping -----------------------------------------------

    def _damp_updates(self, updates: KeyVals) -> KeyVals:
        """Filter accepted updates through the per-(key, originator)
        damping penalty. Held keys stay merged in the store (the CRDT is
        untouched) but are withheld from flooding AND from the local
        updates queue, bounding Decision/journal/stream churn during event
        storms. TTL refreshes (no value body) never accrue penalty and
        always pass; exempt prefixes (adjacency keys) always pass."""
        if not self.params.damping_enabled:
            return updates
        now = time.monotonic()
        half_life = self.params.damping_half_life_s
        flood: KeyVals = {}
        for key, value in updates.items():
            if value.value is None or key.startswith(
                self.params.damping_exempt_prefixes
            ):
                flood[key] = value
                continue
            slot = (key, value.originator_id)
            entry = self._damping.get(slot)
            if entry is None:
                entry = _DampingEntry(last_decay=now)
                self._damping[slot] = entry
            else:
                entry.penalty *= 0.5 ** ((now - entry.last_decay) / half_life)
                entry.last_decay = now
            entry.penalty += self.params.damping_penalty
            if entry.held:
                self._bump("kvstore.damping.suppressed")
            elif entry.penalty >= self.params.damping_suppress_limit:
                entry.held = True
                entry.held_since = now
                self._bump("kvstore.damping.holds")
                self._bump("kvstore.damping.suppressed")
            else:
                flood[key] = value
        if self._damping:
            self._set_damping_gauge()
            self._arm_damping_sweep()
        return flood

    def _arm_damping_sweep(self) -> None:
        if self._damping_timer is not None:
            return
        try:
            loop = self.loop()
        except RuntimeError:
            # no event loop (synchronous unit-test context): decay state
            # is tracked per-entry, so the sweep arms on the next damped
            # update that happens inside a loop — nothing is lost
            return
        self._damping_timer = loop.call_later(
            self.params.damping_sweep_s, self._damping_sweep
        )

    def _set_damping_gauge(self) -> None:
        self.counters["kvstore.damping.active_last"] = sum(
            1 for e in self._damping.values() if e.held
        )

    def _damping_sweep(self) -> None:
        """Decay penalties; release hold-downs whose penalty fell below the
        reuse limit (or that hit the hard hold cap) by flooding the CURRENT
        store value — the latest accepted write always wins on release."""
        self._damping_timer = None
        now = time.monotonic()
        half_life = self.params.damping_half_life_s
        release_keys: Set[str] = set()
        for slot, entry in list(self._damping.items()):
            entry.penalty *= 0.5 ** ((now - entry.last_decay) / half_life)
            entry.last_decay = now
            if entry.held and (
                entry.penalty <= self.params.damping_reuse_limit
                or now - entry.held_since >= self.params.damping_max_hold_s
            ):
                entry.held = False
                entry.penalty = min(
                    entry.penalty, self.params.damping_reuse_limit
                )
                self._observe(
                    "kvstore.damping.hold_ms",
                    (now - entry.held_since) * 1e3,
                )
                self._bump("kvstore.damping.released")
                release_keys.add(slot[0])
            if not entry.held and entry.penalty < 1.0:
                del self._damping[slot]
        self._set_damping_gauge()
        if release_keys:
            pub = Publication(area=self.area)
            for key in sorted(release_keys):
                value = self.store.get(key)
                if value is not None:
                    pub.key_vals[key] = value
            if pub.key_vals:
                self.flood_publication(pub, rate_limit=False)
        if self._damping:
            self._arm_damping_sweep()

    # -- flooding ----------------------------------------------------------

    def flood_publication(
        self,
        publication: Publication,
        rate_limit: bool = True,
        _from_buffer: bool = False,
    ) -> None:
        if (
            self._flood_limiter is not None
            and rate_limit
            and not self._flood_limiter.consume(1)
        ):
            self._buffer_publication(publication)
            self._buffer_flush()
            return
        if self._publication_buffer and not _from_buffer:
            self._buffer_publication(publication)
            self._flush_buffered()
            return

        self._update_publication_ttl(publication, decrement=True)
        if not publication.key_vals and not publication.expired_keys:
            return

        sender_id: Optional[str] = None
        if publication.node_ids:
            sender_id = publication.node_ids[-1]
        if publication.node_ids is None:
            publication.node_ids = []
        publication.node_ids.append(self.params.node_id)

        # hop-trace origin stamp: a publication with no inbound sender is
        # being originated HERE — start the wall-clock flood trace every
        # downstream hop measures per-hop latency against
        if (
            publication.key_vals
            and publication.perf_events is None
            and sender_id is None
        ):
            publication.perf_events = PerfEvents()
            publication.perf_events.add_fine(
                self.params.node_id, FLOOD_ORIGINATED_EVENT
            )

        # internal subscribers (Decision et al.); the monotonic stamp seeds
        # Decision's convergence span (this store's clock — always restamp:
        # a shared in-process publication object may carry another node's)
        publication.ts_monotonic = time.monotonic()
        self.updates_queue.push(publication)
        self._bump("kvstore.num_updates")

        if not publication.key_vals:
            return  # expiry-only publications stay local

        for peer_name in self.get_flood_peers():
            peer = self.peers[peer_name]
            if sender_id is not None and sender_id == peer_name:
                continue  # never flood back to the sender
            if peer.state == PeerState.IDLE:
                continue
            if peer.health in (PeerHealth.QUARANTINED, PeerHealth.PROBING):
                # quarantined peers get no floods — only the probe-driven
                # full syncs the quarantine loop issues
                peer.floods_skipped += 1
                self._bump("kvstore.quarantine.floods_skipped")
                continue
            self._spawn(
                self._send_key_vals(
                    peer_name,
                    dict(publication.key_vals),
                    list(publication.node_ids),
                    (
                        publication.perf_events.copy()
                        if publication.perf_events is not None
                        else None
                    ),
                )
            )

    def get_flood_peers(self, record: bool = True) -> List[str]:
        """SPT peers when flood optimization has a ready tree, else all
        peers (KvStore.cpp:2819-2839). `record=False` for introspection
        reads (SPT dump) so they don't inflate the flood-ratio counter."""
        if self.dual is not None:
            root_id = self.dual.get_spt_root_id()
            spt_peers = self.dual.get_spt_peers(root_id)
            if spt_peers:
                if record:
                    self._bump("kvstore.flood_via_spt")
                return [p for p in spt_peers if p in self.peers]
        return list(self.peers)

    def _buffer_publication(self, publication: Publication) -> None:
        self._bump("kvstore.rate_limit_suppress")
        if self._buffer_first_ts is None:
            self._buffer_first_ts = time.monotonic()
        # the merged flush keeps the OLDEST buffered publication's span
        # stages and hop trace (Decision's oldest-event-of-a-batch rule)
        if self._buffer_span_stages is None:
            self._buffer_span_stages = publication.span_stages
        if self._buffer_perf_events is None:
            self._buffer_perf_events = publication.perf_events
        self._publication_buffer.update(publication.key_vals.keys())
        self._publication_buffer.update(publication.expired_keys)

    def _flush_buffered(self) -> None:
        self._buffer_flush.cancel()
        if not self._publication_buffer:
            return
        if self._buffer_first_ts is not None:
            self._observe(
                "kvstore.flood.buffer_delay_ms",
                (time.monotonic() - self._buffer_first_ts) * 1e3,
            )
        pub = Publication(
            area=self.area,
            span_stages=self._buffer_span_stages,
            perf_events=self._buffer_perf_events,
        )
        self._buffer_first_ts = None
        self._buffer_span_stages = None
        self._buffer_perf_events = None
        for key in self._publication_buffer:
            value = self.store.get(key)
            if value is not None:
                pub.key_vals[key] = value
            else:
                pub.expired_keys.append(key)
        self._publication_buffer.clear()
        # forwarded as merged publication, not rate limited again
        self.flood_publication(pub, rate_limit=False, _from_buffer=True)

    async def _send_key_vals(
        self,
        peer_name: str,
        key_vals: KeyVals,
        node_ids: List[str],
        perf_events: Optional[PerfEvents] = None,
    ) -> None:
        peer = self.peers.get(peer_name)
        if peer is None:
            return
        try:
            # named fault seam: an injected send failure exercises the
            # API_ERROR peer-state path without a real transport fault
            fault_point("kvstore.flood_send", peer_name)
            await self.transport.set_key_vals(
                peer.spec.peer_addr,
                self.area,
                key_vals,
                node_ids,
                perf_events=perf_events,
            )
            self._bump("kvstore.thrift.num_flood_pub")
            self._note_peer_success(peer_name)
        except Exception:
            self._bump("kvstore.thrift.num_flood_pub_failure")
            self._note_peer_failure(peer_name)
            self._peer_event(peer_name, PeerEvent.API_ERROR)

    # -- peers + full sync -------------------------------------------------

    def add_peers(self, peers: Dict[str, PeerSpec]) -> None:
        for name, spec in peers.items():
            existing = self.peers.get(name)
            if existing is not None and existing.spec == spec:
                continue
            self.peers[name] = _Peer(
                spec=spec,
                # decorrelated jitter (the Fib resync pattern): concurrent
                # sync failures across peers/nodes retry decorrelated
                # instead of thundering back in lockstep
                backoff=ExponentialBackoff(
                    0.064,
                    self.params.sync_max_backoff,
                    jitter=True,
                    rng=self._rng,
                ),
            )
            self._peer_event(name, PeerEvent.PEER_ADD)
            if self.dual is not None:
                self.dual.peer_up(name, 1)  # KvStore peers at unit metric
            self._spawn(self._full_sync(name))
        if (
            self.params.anti_entropy_enabled
            and self._ae_timer is None
            and self.peers
        ):
            self._ae_timer = self.loop().call_later(
                self.params.anti_entropy_interval_s, self._anti_entropy_tick
            )

    def del_peers(self, names: List[str]) -> None:
        for name in names:
            if self.peers.pop(name, None) is not None and (
                self.dual is not None
            ):
                self.dual.peer_down(name)

    def get_peers(self) -> Dict[str, PeerSpec]:
        return {name: p.spec for name, p in self.peers.items()}

    def peer_state(self, name: str) -> Optional[PeerState]:
        peer = self.peers.get(name)
        return peer.state if peer else None

    def _peer_event(self, name: str, event: PeerEvent) -> None:
        peer = self.peers.get(name)
        if peer is None:
            return
        next_state = _PEER_FSM.get((peer.state, event))
        if next_state is not None:
            peer.state = next_state
        if event == PeerEvent.API_ERROR:
            peer.backoff.report_error()
            if peer.health in (PeerHealth.QUARANTINED, PeerHealth.PROBING):
                return  # the probe loop owns recovery
            if name not in self._retry_pending:
                self._retry_pending.add(name)
                self._spawn(self._retry_sync(name))

    async def _retry_sync(self, name: str) -> None:
        try:
            peer = self.peers.get(name)
            if peer is None:
                return
            wait = peer.backoff.get_time_remaining_until_retry()
            self._observe("kvstore.full_sync_backoff_ms", wait * 1e3)
            await asyncio.sleep(wait)
            peer = self.peers.get(name)
            if (
                peer is not None
                and peer.state == PeerState.IDLE
                and peer.health
                not in (PeerHealth.QUARANTINED, PeerHealth.PROBING)
            ):
                peer.state = PeerState.SYNCING
                self._retry_pending.discard(name)
                await self._full_sync(name)
        finally:
            self._retry_pending.discard(name)

    async def _full_sync(self, peer_name: str) -> None:
        """3-way full sync with one peer (requester side)."""
        peer = self.peers.get(peer_name)
        if peer is None:
            return
        my_hashes = self.dump_hashes().key_vals
        try:
            # named fault seam: an injected dump failure exercises the
            # full-sync retry/backoff path (docs/Robustness.md catalog)
            fault_point("kvstore.full_sync", peer_name)
            pub = await self.transport.dump_key_vals(
                peer.spec.peer_addr, self.area, my_hashes
            )
        except Exception:
            self._bump("kvstore.full_sync_failure")
            self._note_peer_failure(peer_name)
            self._peer_event(peer_name, PeerEvent.API_ERROR)
            return
        peer.backoff.report_success()
        self._note_peer_success(peer_name)
        self._bump("kvstore.thrift.num_full_sync")
        # merge their better keys and flood resulting updates onward
        self.handle_set_key_vals(pub.key_vals, [peer_name])
        self._peer_event(peer_name, PeerEvent.SYNC_RESP_RCVD)
        # push back keys the peer is missing / has worse
        if pub.tobe_updated_keys:
            await self._finalize_full_sync(pub.tobe_updated_keys, peer_name)

    async def _finalize_full_sync(
        self, keys: List[str], peer_name: str
    ) -> None:
        updates: KeyVals = {}
        for key in keys:
            value = self.store.get(key)
            if value is not None:
                updates[key] = value
        pub = Publication(key_vals=updates, area=self.area)
        self._update_publication_ttl(pub)
        if not pub.key_vals:
            return
        peer = self.peers.get(peer_name)
        if peer is None or peer.state == PeerState.IDLE:
            return
        self._bump("kvstore.thrift.num_finalized_sync")
        try:
            await self.transport.set_key_vals(
                peer.spec.peer_addr,
                self.area,
                pub.key_vals,
                [self.params.node_id],
            )
        except Exception:
            self._note_peer_failure(peer_name)
            self._peer_event(peer_name, PeerEvent.API_ERROR)

    # -- peer-health quarantine --------------------------------------------

    def _note_peer_failure(self, name: str) -> None:
        """Score one transport failure toward this peer: consecutive
        failures walk the HEALTHY → SUSPECT → QUARANTINED ladder."""
        peer = self.peers.get(name)
        if peer is None or not self.params.quarantine_enabled:
            return
        if peer.health in (PeerHealth.QUARANTINED, PeerHealth.PROBING):
            return  # probe-loop failures are scored by the probe loop
        peer.failures += 1
        if peer.failures >= self.params.peer_quarantine_failures:
            self._quarantine_peer(name)
        elif (
            peer.failures >= self.params.peer_suspect_failures
            and peer.health == PeerHealth.HEALTHY
        ):
            peer.health = PeerHealth.SUSPECT
            self._bump("kvstore.quarantine.suspects")

    def _note_peer_success(self, name: str) -> None:
        peer = self.peers.get(name)
        if peer is None:
            return
        if peer.health in (PeerHealth.QUARANTINED, PeerHealth.PROBING):
            return  # only probe hysteresis recovers a quarantined peer
        peer.failures = 0
        if peer.health == PeerHealth.SUSPECT:
            peer.health = PeerHealth.HEALTHY

    def _set_quarantine_gauge(self) -> None:
        self.counters["kvstore.quarantine.active_last"] = sum(
            1
            for p in self.peers.values()
            if p.health in (PeerHealth.QUARANTINED, PeerHealth.PROBING)
        )

    def _quarantine_peer(self, name: str) -> None:
        peer = self.peers.get(name)
        if peer is None or peer.health == PeerHealth.QUARANTINED:
            return
        peer.health = PeerHealth.QUARANTINED
        peer.quarantined_at = time.monotonic()
        peer.probe_streak = 0
        peer.probe_backoff = ExponentialBackoff(
            self.params.peer_probe_min_backoff,
            self.params.peer_probe_max_backoff,
            jitter=True,
            rng=self._rng,
        )
        self._bump("kvstore.quarantine.trips")
        self._set_quarantine_gauge()
        self._dump_quarantine_forensics(name, peer)
        self._spawn(self._probe_quarantined(name))

    def _dump_quarantine_forensics(self, name: str, peer: _Peer) -> None:
        """Snapshot a quarantine-trip forensics artifact through the PR 13
        flight-recorder dump path and emit one KVSTORE_PEER_QUARANTINED
        LogSample carrying the dump id."""
        forensics_id = ""
        try:
            from openr_tpu.solver.flight_recorder import FlightRecorder

            if self._forensics is None:
                self._forensics = FlightRecorder(
                    node=self.params.node_id,
                    forensics_dir=self.params.forensics_dir,
                )
            dump = self._forensics.dump(
                "kvstore_peer_quarantined",
                counters=dict(self.counters),
                extra={
                    "peer": name,
                    "area": self.area,
                    "failures": peer.failures,
                    "peer_state": peer.state.value,
                    "peer_health": dict(self.get_peer_health()),
                },
            )
            forensics_id = dump["id"]
            self._bump("kvstore.forensics_dumps")
        except Exception:
            pass  # forensics must never break the store loop
        if self._log_sample_fn is not None:
            sample = LogSample()
            sample.add_string("event", PEER_QUARANTINED_EVENT)
            sample.add_string("area", self.area)
            sample.add_string("peer", name)
            sample.add_int("failures", peer.failures)
            sample.add_string("forensics_id", forensics_id)
            try:
                self._log_sample_fn(sample)
            except Exception:
                pass  # a closed monitor queue must never break the loop

    async def _probe_quarantined(self, name: str) -> None:
        """Recovery loop for one quarantined peer: jittered-backoff probes
        through the full-sync dump path; `peer_probe_successes` consecutive
        successes recover the peer (hysteresis against flapping links)."""
        while True:
            peer = self.peers.get(name)
            if peer is None or peer.health not in (
                PeerHealth.QUARANTINED,
                PeerHealth.PROBING,
            ):
                return
            peer.probe_backoff.report_error()
            await asyncio.sleep(
                peer.probe_backoff.get_time_remaining_until_retry()
            )
            peer = self.peers.get(name)
            if peer is None or peer.health not in (
                PeerHealth.QUARANTINED,
                PeerHealth.PROBING,
            ):
                return
            peer.health = PeerHealth.PROBING
            peer.probes += 1
            self._bump("kvstore.quarantine.probes")
            my_hashes = self.dump_hashes().key_vals
            try:
                # named fault seam: an injected probe failure keeps the
                # peer quarantined through another backoff round
                fault_point("kvstore.quarantine_probe", name)
                pub = await self.transport.dump_key_vals(
                    peer.spec.peer_addr, self.area, my_hashes
                )
            except Exception:
                self._bump("kvstore.quarantine.probe_failures")
                peer.probe_streak = 0
                peer.health = PeerHealth.QUARANTINED
                continue
            peer.probe_streak += 1
            if peer.probe_streak >= self.params.peer_probe_successes:
                self._recover_peer(name, pub)
                return
            peer.health = PeerHealth.QUARANTINED

    def _recover_peer(self, name: str, pub: Publication) -> None:
        """Probe hysteresis satisfied: merge the probe's full-sync dump,
        restore the peer FSM, and resume flooding toward the peer."""
        peer = self.peers.get(name)
        if peer is None:
            return
        peer.health = PeerHealth.HEALTHY
        peer.failures = 0
        peer.probe_streak = 0
        peer.backoff.report_success()
        if peer.state == PeerState.IDLE:
            peer.state = PeerState.SYNCING
        held_ms = (time.monotonic() - peer.quarantined_at) * 1e3
        self._observe("kvstore.quarantine.duration_ms", held_ms)
        self._bump("kvstore.quarantine.recoveries")
        self._set_quarantine_gauge()
        self._bump("kvstore.thrift.num_full_sync")
        self.handle_set_key_vals(pub.key_vals, [name])
        self._peer_event(name, PeerEvent.SYNC_RESP_RCVD)
        if pub.tobe_updated_keys:
            self._spawn(
                self._finalize_full_sync(pub.tobe_updated_keys, name)
            )
        if self._log_sample_fn is not None:
            sample = LogSample()
            sample.add_string("event", PEER_RECOVERED_EVENT)
            sample.add_string("area", self.area)
            sample.add_string("peer", name)
            sample.add_int("probes", peer.probes)
            sample.add_double("quarantined_ms", held_ms)
            try:
                self._log_sample_fn(sample)
            except Exception:
                pass  # a closed monitor queue must never break the loop

    def get_peer_health(self) -> Dict[str, Dict]:
        """Per-peer quarantine-ladder snapshot (ctrl getKvStorePeerHealth /
        `breeze kvstore peer-health`)."""
        now = time.monotonic()
        out: Dict[str, Dict] = {}
        for name, peer in self.peers.items():
            quarantined = peer.health in (
                PeerHealth.QUARANTINED,
                PeerHealth.PROBING,
            )
            out[name] = {
                "state": peer.state.value,
                "health": peer.health.value,
                "failures": peer.failures,
                "probes": peer.probes,
                "probe_streak": peer.probe_streak,
                "floods_skipped": peer.floods_skipped,
                "quarantined_ms": (
                    round((now - peer.quarantined_at) * 1e3, 1)
                    if quarantined
                    else 0.0
                ),
            }
        return out

    # -- adaptive anti-entropy ---------------------------------------------

    def _flood_health_degraded(self) -> bool:
        """Per-interval flood-health check: any sync/flood failure or wire
        reject, or a duplicate/received ratio off budget, counts as
        degraded and arms an anti-entropy round."""
        watched = (
            "kvstore.flood.received",
            "kvstore.flood.duplicates",
            "kvstore.full_sync_failure",
            "kvstore.thrift.num_flood_pub_failure",
            "kvstore.wire.rejected_total",
        )
        deltas: Dict[str, int] = {}
        for counter in watched:
            current = self.counters.get(counter, 0)
            deltas[counter] = current - self._ae_last.get(counter, 0)
            self._ae_last[counter] = current
        if (
            deltas["kvstore.full_sync_failure"] > 0
            or deltas["kvstore.thrift.num_flood_pub_failure"] > 0
            or deltas["kvstore.wire.rejected_total"] > 0
        ):
            return True
        received = deltas["kvstore.flood.received"]
        return (
            received >= 4
            and deltas["kvstore.flood.duplicates"] / received
            > self.params.flood_duplicate_budget
        )

    def _anti_entropy_tick(self) -> None:
        self._ae_timer = None
        if not self.peers:
            return  # re-armed by the next add_peers
        degraded = self._flood_health_degraded()
        self.counters["kvstore.anti_entropy.armed_last"] = int(degraded)
        if degraded:
            candidates = [
                name
                for name, peer in self.peers.items()
                if peer.health
                not in (PeerHealth.QUARANTINED, PeerHealth.PROBING)
            ]
            if candidates:
                peer_name = candidates[self._rng.randrange(len(candidates))]
                self._spawn(self._anti_entropy_round(peer_name))
        self._ae_timer = self.loop().call_later(
            self.params.anti_entropy_interval_s, self._anti_entropy_tick
        )

    async def _anti_entropy_round(self, peer_name: str) -> None:
        """One 3-way repair round against a healthy peer: the hash dump
        ships only divergent keys in either direction."""
        peer = self.peers.get(peer_name)
        if peer is None:
            return
        t0 = time.monotonic()
        my_hashes = self.dump_hashes().key_vals
        try:
            # named fault seam: a failed repair round scores the peer and
            # re-arms on the next degraded interval
            fault_point("kvstore.anti_entropy", peer_name)
            pub = await self.transport.dump_key_vals(
                peer.spec.peer_addr, self.area, my_hashes
            )
        except Exception:
            self._bump("kvstore.anti_entropy.round_failures")
            self._note_peer_failure(peer_name)
            self._peer_event(peer_name, PeerEvent.API_ERROR)
            return
        self._bump("kvstore.anti_entropy.rounds")
        self._note_peer_success(peer_name)
        if pub.key_vals:
            self._bump("kvstore.anti_entropy.keys_repaired", len(pub.key_vals))
            self.handle_set_key_vals(pub.key_vals, [peer_name])
        if pub.tobe_updated_keys:
            await self._finalize_full_sync(pub.tobe_updated_keys, peer_name)
        self._observe(
            "kvstore.anti_entropy.round_ms", (time.monotonic() - t0) * 1e3
        )

    # -- TTL ---------------------------------------------------------------

    def _update_ttl_countdown(self, key_vals: KeyVals) -> None:
        """Register countdown entries for accepted updates. Every accepted
        update bumps the key's epoch so entries from superseded writes (even
        ones with identical version/originator/ttlVersion, e.g. the
        value-bytes tiebreak) can never evict the refreshed value."""
        now = time.monotonic()
        for key, value in key_vals.items():
            epoch = self._ttl_epochs.get(key, 0) + 1
            self._ttl_epochs[key] = epoch
            if value.ttl == TTL_INFINITY:
                self._ttl_expiry.pop(key, None)
                continue
            self._ttl_expiry[key] = now + value.ttl / 1000.0
            entry = _TtlEntry(
                expiry=now + value.ttl / 1000.0, key=key, epoch=epoch
            )
            if (
                not self._ttl_heap or entry.expiry <= self._ttl_heap[0].expiry
            ):
                self._schedule_ttl_timer(value.ttl / 1000.0)
            heapq.heappush(self._ttl_heap, entry)

    def _schedule_ttl_timer(self, delay: float) -> None:
        if self._ttl_timer is not None:
            self._ttl_timer.cancel()
        self._ttl_timer = self.loop().call_later(
            max(0.0, delay), self.cleanup_ttl_countdown_queue
        )

    def cleanup_ttl_countdown_queue(self) -> None:
        """Evict expired keys; lazily drop invalidated heap entries."""
        self._ttl_timer = None
        expired: List[str] = []
        now = time.monotonic()
        while self._ttl_heap and self._ttl_heap[0].expiry <= now:
            top = heapq.heappop(self._ttl_heap)
            if (
                top.key in self.store
                and self._ttl_epochs.get(top.key) == top.epoch
            ):
                expired.append(top.key)
                del self.store[top.key]
                del self._ttl_epochs[top.key]
                self._ttl_expiry.pop(top.key, None)
                self._bump("kvstore.expired_key_vals")
        if self._ttl_heap:
            self._schedule_ttl_timer(self._ttl_heap[0].expiry - now)
        if expired:
            self.flood_publication(
                Publication(expired_keys=expired, area=self.area)
            )

    def _update_publication_ttl(
        self, publication: Publication, decrement: bool = False
    ) -> None:
        """Serve the REMAINING ttl (countdown deadline minus now), drop
        about-to-expire keys, decrement forwarded TTLs
        (KvStore.cpp:2038 updatePublicationTtl).

        Stored Values keep their ORIGINAL ttl; serving that here would
        re-arm a dead originator's keys to full lifetime on every full
        sync / dump — with refreshes lost on a hostile network, such keys
        would never age out anywhere (the immortal-key bug). Publications
        always carry a copy so the stored Value is never mutated."""
        dec = self.params.ttl_decrement_ms
        now = time.monotonic()
        for key in list(publication.key_vals.keys()):
            value = publication.key_vals[key]
            if value.ttl == TTL_INFINITY:
                continue
            expiry = self._ttl_expiry.get(key)
            remaining = (
                int((expiry - now) * 1000.0)
                if expiry is not None
                else value.ttl
            )
            if decrement:
                remaining -= dec
            if remaining <= 0:
                del publication.key_vals[key]
                continue
            if remaining != value.ttl:
                new_value = value.copy()
                new_value.ttl = remaining
                publication.key_vals[key] = new_value

    # -- misc --------------------------------------------------------------

    def _spawn(self, coro) -> None:
        task = self.loop().create_task(coro)
        self._sync_tasks.add(task)
        task.add_done_callback(self._sync_tasks.discard)


    def stop(self) -> None:
        if self._ttl_timer is not None:
            self._ttl_timer.cancel()
            self._ttl_timer = None
        if self._damping_timer is not None:
            self._damping_timer.cancel()
            self._damping_timer = None
        if self._ae_timer is not None:
            self._ae_timer.cancel()
            self._ae_timer = None
        self._buffer_flush.cancel()
        for task in list(self._sync_tasks):
            task.cancel()

    # -- DUAL flood-topology integration -----------------------------------

    def handle_dual_messages(self, msgs) -> None:
        """Peer-delivered DUAL messages (KvStore.cpp:892)."""
        if self.dual is not None:
            self.dual.process_dual_messages(msgs)

    def handle_flood_topo_set(
        self, root_id: str, src_id: str, set_child: bool, all_roots: bool
    ) -> None:
        """processFloodTopoSet (KvStore.cpp:2238-2267)."""
        if self.dual is None:
            return
        if all_roots and not set_child:
            for dual in self.dual.duals.values():
                dual.remove_child(src_id)
            return
        if not self.dual.has_dual(root_id):
            return
        dual = self.dual.get_dual(root_id)
        if set_child:
            dual.add_child(src_id)
        else:
            dual.remove_child(src_id)

    def get_spt_infos(self) -> Dict:
        """processFloodTopoGet (KvStore.cpp:2202-2234): SPT state dump."""
        out: Dict = {"spt_infos": {}, "flood_root_id": None, "flood_peers": []}
        if self.dual is None:
            out["flood_peers"] = list(self.peers)
            return out
        for root_id, dual in self.dual.duals.items():
            out["spt_infos"][root_id] = {
                "passive": dual.sm.state.name == "PASSIVE",
                "cost": dual.distance,
                "parent": dual.nexthop,
                "children": sorted(dual.children()),
            }
        out["flood_root_id"] = self.dual.get_spt_root_id()
        out["flood_peers"] = self.get_flood_peers(record=False)
        return out


class _KvDualNode:
    """DualNode subclass-equivalent bound to one KvStoreDb (the reference
    makes KvStoreDb inherit DualNode, KvStore.h:193; composition here).

    Nexthop changes drive the flood topology: unset-child on the old
    parent, set-child + full-sync on the new one (KvStore.cpp:2315-2360).
    """

    def __init__(self, db: KvStoreDb) -> None:
        from openr_tpu.dual import DualNode

        outer = self

        class _Node(DualNode):
            def send_dual_messages(self, neighbor, msgs) -> bool:
                return outer._send(neighbor, msgs)

            def process_nexthop_change(self, root_id, old_nh, new_nh):
                outer._nexthop_change(root_id, old_nh, new_nh)

        self.db = db
        self._node = _Node(
            db.params.node_id, is_root=db.params.is_flood_root
        )

    # -- DualNode facade -------------------------------------------------

    def __getattr__(self, name):
        return getattr(self._node, name)

    @property
    def duals(self):
        return self._node.duals

    # -- wiring ----------------------------------------------------------

    async def _dual_rpc(self, peer_name: str, counter: str, coro) -> None:
        """Await a DUAL/flood-topo transport call, surfacing failures as
        counters + an API_ERROR peer event (the reference's thenError path,
        KvStore.cpp:1161-1169) instead of dying unobserved in the task."""
        try:
            await coro
            self.db._bump(f"kvstore.thrift.num_{counter}")
        except Exception:
            self.db._bump(f"kvstore.thrift.num_{counter}_failure")
            self.db._peer_event(peer_name, PeerEvent.API_ERROR)

    def _send(self, neighbor: str, msgs) -> bool:
        peer = self.db.peers.get(neighbor)
        if peer is None:
            return False
        self.db._spawn(
            self._dual_rpc(
                neighbor,
                "dual_msg",
                self.db.transport.dual_messages(
                    peer.spec.peer_addr, self.db.area, msgs
                ),
            )
        )
        return True

    def _topo_set(self, peer_name: str, root_id: str, set_child: bool) -> None:
        self.db._spawn(
            self._dual_rpc(
                peer_name,
                "flood_topo_set",
                self.db.transport.flood_topo_set(
                    self.db.peers[peer_name].spec.peer_addr,
                    self.db.area,
                    root_id,
                    self.db.params.node_id,
                    set_child,
                ),
            )
        )

    def _nexthop_change(self, root_id, old_nh, new_nh) -> None:
        if new_nh is not None and new_nh in self.db.peers:
            self._topo_set(new_nh, root_id, True)
            # full sync with the new parent so the SPT edge carries a
            # consistent store (KvStore.cpp:2342-2349)
            self.db._spawn(self.db._full_sync(new_nh))
        if old_nh is not None and old_nh in self.db.peers:
            self._topo_set(old_nh, root_id, False)


# ---------------------------------------------------------------------------
# KvStore — multi-area container
# ---------------------------------------------------------------------------


class KvStore:
    """Container of per-area KvStoreDbs sharing one transport + node id."""

    def __init__(
        self,
        node_id: str,
        areas: List[str],
        transport,
        params: Optional[KvStoreParams] = None,
        loop: Optional[asyncio.AbstractEventLoop] = None,
        log_sample_fn=None,
    ) -> None:
        import dataclasses

        from openr_tpu.kvstore.transport import (
            BoundTransport,
            InProcessTransport,
        )

        self.node_id = node_id
        params = params or KvStoreParams(node_id=node_id)
        # never mutate the caller's params object (it may be shared)
        self.params = dataclasses.replace(params, node_id=node_id)
        if isinstance(transport, InProcessTransport):
            transport.register(node_id, self)
            transport = BoundTransport(transport, node_id)
        self.updates_queue: ReplicateQueue = ReplicateQueue()
        # one histograms dict shared by every area db: per-node flood
        # latency stats aggregate across areas, and the monitor (which
        # registers this container, not the dbs) reads them live
        self.histograms: Dict = {}
        self.dbs: Dict[str, KvStoreDb] = {
            area: KvStoreDb(
                area,
                self.params,
                transport,
                self.updates_queue,
                loop,
                histograms=self.histograms,
                log_sample_fn=log_sample_fn,
            )
            for area in areas
        }

    def db(self, area: str = "0") -> KvStoreDb:
        return self.dbs[area]

    def note_wire_reject(self, kind: str) -> None:
        """Typed wire-decode rejection (oversized / truncated / malformed /
        hash_mismatch) observed by a transport serving this store. Counters
        live on the per-area dbs; route through the first db so the
        kvstore.wire.* namespace reaches getCounters."""
        db = next(iter(self.dbs.values()), None)
        if db is None:
            return
        db._bump("kvstore.wire.rejected_total")
        db._bump(f"kvstore.wire.rejected.{kind}")

    def get_peer_health(self, area: str = "0") -> Dict[str, Dict]:
        return self.dbs[area].get_peer_health()

    @property
    def counters(self) -> Dict[str, int]:
        """Merged per-area counters for the monitor registry (counters live
        on the KvStoreDbs; without this the kvstore.* namespace would be
        invisible to getCounters)."""
        merged: Dict[str, int] = {}
        for db in self.dbs.values():
            for name, value in db.counters.items():
                merged[name] = merged.get(name, 0) + value
        return merged

    # -- local API (OpenrCtrl surface) ------------------------------------

    def set_key(
        self,
        key: str,
        value: Value,
        area: str = "0",
        span_stages=None,
    ) -> None:
        self.dbs[area].set_key_vals({key: value}, span_stages=span_stages)

    def get_key(self, key: str, area: str = "0") -> Optional[Value]:
        return self.dbs[area].get_key(key)

    def dump_all(self, area: str = "0", **kw) -> Publication:
        return self.dbs[area].dump_all(**kw)

    def add_peers(self, peers: Dict[str, PeerSpec], area: str = "0") -> None:
        self.dbs[area].add_peers(peers)

    def del_peers(self, names: List[str], area: str = "0") -> None:
        self.dbs[area].del_peers(names)

    # -- transport server side --------------------------------------------

    def handle_set_key_vals(
        self,
        area: str,
        key_vals: KeyVals,
        node_ids: Optional[List[str]],
        perf_events: Optional[PerfEvents] = None,
    ) -> None:
        db = self.dbs.get(area)
        if db is not None:
            db.handle_set_key_vals(key_vals, node_ids, perf_events)

    def handle_dual_messages(self, area: str, msgs) -> None:
        db = self.dbs.get(area)
        if db is not None:
            db.handle_dual_messages(msgs)

    def handle_flood_topo_set(
        self,
        area: str,
        root_id: str,
        src_id: str,
        set_child: bool,
        all_roots: bool,
    ) -> None:
        db = self.dbs.get(area)
        if db is not None:
            db.handle_flood_topo_set(root_id, src_id, set_child, all_roots)

    def handle_dump(
        self, area: str, key_val_hashes: Optional[KeyVals]
    ) -> Publication:
        db = self.dbs.get(area)
        if db is None:
            return Publication(area=area)
        return db.handle_dump(key_val_hashes)

    def stop(self) -> None:
        for db in self.dbs.values():
            db.stop()
        self.updates_queue.close()
