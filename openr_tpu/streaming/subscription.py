"""Streaming control plane: delta subscriptions with bounded fan-out.

The reference Open/R serves its control plane as *streams* —
`subscribeKvStoreFilter` (OpenrCtrlHandler.h:207-211) pushes LSDB deltas
to subscribers instead of re-snapshotting per request. This module is the
fan-out layer between the daemon's module queues and the ctrl server's
per-connection stream handlers:

  - `StreamManager` owns ONE reader per source `ReplicateQueue` (KvStore
    publications, Decision route updates) and fans each item out to every
    registered subscriber with a **non-blocking** `offer()` — publication
    never waits on any client.
  - Fan-out encode cost is O(filter-equivalence-classes), not
    O(subscribers): subscribers with equal filters (KvStore: area +
    key-prefixes + originators; routes: unfiltered, one class) are
    grouped, each source item is filtered once per class, and the
    resulting `SharedFrame` memoizes its serialized body once per codec —
    per-subscriber work is a queue append plus an envelope splice and
    buffer write in the connection task (docs/Streaming.md
    "Shared-encode fan-out").
  - Each subscriber holds a **bounded** frame queue. When a slow client
    falls `max_pending` frames behind, the queue is coalesced: KvStore
    deltas merge per key (newest value wins, expiry/update cancel each
    other), route deltas merge per prefix/label. If the *merged* delta
    still exceeds `coalesce_budget` entries, the queue is dropped and the
    subscriber is flagged for a **marked snapshot-resync** — the stream
    handler sends a fresh full dump tagged `"type": "resync"`, so the
    client knows to replace (not merge) its state. Overflow is therefore
    never silent loss: a subscriber always ends at a state equal to a
    fresh dump.
  - Slow-client isolation falls out of the design: the only blocking
    waits (`writer.drain()`) live in the per-connection handler task; a
    stalled reader stalls its own bounded queue, nothing else.

Everything runs on the daemon's single asyncio loop. The publisher-side
enqueue (`offer`, called from the dispatch task) and the subscriber-side
dequeue (`next_frame`, called from the connection task) interleave only
at awaits — the subscriber-queue handover pattern the thread-ownership
analyzer sanctions via the `# analysis: queue` attribute marker
(docs/Analysis.md).

Observability: `ctrl.stream.*` counters/histograms (docs/Monitoring.md),
`ctrl.stream.publish` fault point at the fan-out seam and
`ctrl.stream.deliver` at the per-frame delivery seam (docs/Robustness.md).
"""

from __future__ import annotations

import asyncio
import collections
import time
from dataclasses import dataclass
from typing import Any, Deque, Dict, List, Optional, Set, Tuple

from openr_tpu.messaging import QueueClosedError
from openr_tpu.solver import DecisionRouteUpdate
from openr_tpu.testing.faults import fault_point
from openr_tpu.types import Publication
from openr_tpu.utils.counters import CountersMixin, HistogramsMixin
from openr_tpu.utils.ownership import owned_by


@dataclass
class StreamConfig:
    """Fan-out bounds (config `stream_config` section, docs/Streaming.md)."""

    # frames buffered per subscriber before the queue is coalesced
    subscriber_max_pending: int = 64
    # merged-delta entry budget after coalescing; beyond it the queue is
    # dropped and the subscriber resyncs from a marked snapshot
    coalesce_budget: int = 4096
    # hard cap on concurrent subscriptions (typed server-busy beyond)
    max_subscribers: int = 1024


class SharedFrame:
    """One source item filtered for one filter-equivalence class.

    Every subscriber in the class holds a reference to the same
    SharedFrame in its bounded queue; the frame's body bytes are encoded
    lazily, once per codec, by the first connection task that delivers
    it (`body()`), and every later delivery reuses the memoized bytes.
    `body()` is synchronous and all consumers share one asyncio loop, so
    the memoization is race-free without locks.

    The per-subscriber oldest-enqueue stamp `publish_to_deliver_ms`
    depends on NEVER rides this object — it stays on the queue entry
    (`_frames` stores `(frame, t_enq)` tuples), so shared bytes cannot
    overwrite another subscriber's latency accounting.
    """

    __slots__ = ("item", "kind", "_manager", "_bodies")

    def __init__(self, item: Any, kind: str, manager: "StreamManager") -> None:
        self.item = item
        self.kind = kind  # "kvstore" | "routes"
        self._manager = manager
        self._bodies: Dict[str, bytes] = {}

    def body(self, codec_name: str) -> bytes:
        """Frame body bytes for `codec_name`; encodes on first use (the
        class encode), reuses thereafter (the class hit)."""
        cached = self._bodies.get(codec_name)
        if cached is not None:
            self._manager.note_class_hit()
            return cached
        from openr_tpu.streaming import codec as _codec

        t0 = time.perf_counter()
        if self.kind == "kvstore":
            body = _codec.encode_kv_body(self.item, codec_name)
        else:
            body = _codec.encode_route_body(
                _codec.route_fields_from_update(self.item), codec_name
            )
        self._bodies[codec_name] = body
        self._manager.note_class_encode(
            (time.perf_counter() - t0) * 1e3, len(body)
        )
        return body


def _unwrap(frame: Any) -> Any:
    """Queue entries may be SharedFrames (shared path) or raw items
    (direct `offer`, coalesced merges) — coalescing works on the item."""
    return frame.item if type(frame) is SharedFrame else frame


class SubscriberLimitError(RuntimeError):
    """Raised when `max_subscribers` is reached (typed server-busy)."""

    error_kind = "server_busy"
    retry_after_ms = 1000


class _BaseSubscription:
    """One subscriber's bounded frame queue (publisher side: `offer`,
    sync; subscriber side: `next_frame`, async — same loop)."""

    kind = "?"

    def __init__(self, manager: "StreamManager", label: str = "") -> None:
        self._manager = manager
        self.label = label
        cfg = manager.config
        self.max_pending = cfg.subscriber_max_pending
        self.coalesce_budget = cfg.coalesce_budget
        self._frames: Deque[Tuple[Any, float]] = collections.deque()
        self._resync_at: Optional[float] = None
        self._waiter: Optional[asyncio.Future] = None
        self.closed = False
        # per-frame delivery delay (seconds), consumed one-shot by the
        # stream handler before each write: the `ctrl.stream.deliver`
        # fault point's action hook sets it to emulate a slow client
        # deterministically (docs/Robustness.md)
        self.throttle_s = 0.0
        self.coalesces = 0
        self.resyncs = 0
        self.delivered = 0

    # -- publisher side (dispatch task) --------------------------------

    def offer(self, item: Any, t_enq: float) -> None:
        """Non-blocking enqueue of one unfiltered source item; never
        raises, never waits. The dispatch task pre-filters once per
        class and calls `offer_shared`; this is the per-subscriber
        filter, as the tests of filtering and coalescing drive it."""
        if self.closed:
            return
        filtered = self._filter(item)
        if filtered is None:
            return
        self._enqueue(filtered, t_enq)

    def offer_shared(self, frame: SharedFrame, t_enq: float) -> None:
        """Shared-path enqueue: the dispatch task already filtered the
        item once for this subscriber's whole filter-equivalence class,
        so per-subscriber work is exactly one queue append."""
        if self.closed:
            return
        self._enqueue(frame, t_enq)

    def _enqueue(self, filtered: Any, t_enq: float) -> None:
        if self._resync_at is not None:
            # a pending resync supersedes deltas: the snapshot the
            # handler is about to take will already contain this change
            self._manager._bump("ctrl.stream.dropped_for_resync")
            self._wake()
            return
        self._frames.append((filtered, t_enq))
        depth = len(self._frames)
        counters = self._manager._ensure_counters()
        if depth > counters.get("ctrl.stream.queue_depth_last", 0):
            counters["ctrl.stream.queue_depth_last"] = depth
        if depth > self.max_pending:
            merged, t0, size = self._coalesce(self._frames)
            self.coalesces += 1
            self._manager._bump("ctrl.stream.coalesced")
            self._frames.clear()
            if size > self.coalesce_budget:
                # over budget even merged: drop everything, force a
                # marked snapshot-resync — never silent loss
                self._resync_at = t0
                self.resyncs += 1
                self._manager._bump("ctrl.stream.resyncs")
            else:
                self._frames.append((merged, t0))
        self._wake()

    def force_resync(self) -> None:
        """Drop pending frames and flag a marked snapshot-resync (the
        fan-out fault recovery: a failed publish must not become loss)."""
        if self.closed:
            return
        t0 = self._frames[0][1] if self._frames else time.monotonic()
        self._frames.clear()
        if self._resync_at is None:
            self._resync_at = t0
            self.resyncs += 1
            self._manager._bump("ctrl.stream.resyncs")
        self._wake()

    def close(self) -> None:
        self.closed = True
        self._wake()

    def _wake(self) -> None:
        if self._waiter is not None and not self._waiter.done():
            self._waiter.set_result(None)

    # -- subscriber side (connection task) -----------------------------

    async def next_frame(self) -> Tuple[str, Any, float]:
        """('delta', item, t_enqueued) | ('resync', None, t) |
        ('closed', None, t). Awaits until one is available."""
        while True:
            if self._resync_at is not None:
                t0 = self._resync_at
                self._resync_at = None
                return ("resync", None, t0)
            if self._frames:
                item, t0 = self._frames.popleft()
                return ("delta", item, t0)
            if self.closed:
                return ("closed", None, time.monotonic())
            self._waiter = asyncio.get_running_loop().create_future()
            try:
                await self._waiter
            finally:
                self._waiter = None

    # -- kind-specific hooks --------------------------------------------

    @property
    def filter_key(self) -> Tuple:
        """Filter-equivalence class key: subscriptions with equal keys
        see byte-identical filtered frames, so one class encode serves
        them all (docs/Streaming.md "Shared-encode fan-out")."""
        raise NotImplementedError

    def _filter(self, item: Any) -> Optional[Any]:
        raise NotImplementedError

    def _coalesce(
        self, frames: Deque[Tuple[Any, float]]
    ) -> Tuple[Any, float, int]:
        """Merge all pending frames into one; returns (merged, oldest
        enqueue stamp, merged entry count)."""
        raise NotImplementedError


class KvSubscription(_BaseSubscription):
    """KvStore publication stream with key-prefix/originator filters."""

    kind = "kvstore"

    def __init__(
        self,
        manager: "StreamManager",
        *,
        area: str = "0",
        prefixes: Optional[List[str]] = None,
        originators: Optional[Set[str]] = None,
        label: str = "",
    ) -> None:
        super().__init__(manager, label)
        self.area = area
        self.prefixes = list(prefixes or [])
        self.originators = set(originators or ())

    @property
    def filter_key(self) -> Tuple:
        return (
            "kvstore",
            self.area,
            tuple(sorted(self.prefixes)),
            tuple(sorted(self.originators)),
        )

    def _filter(self, pub: Publication) -> Optional[Publication]:
        if pub.area != self.area:
            return None
        key_vals = pub.key_vals
        expired = list(pub.expired_keys)
        if self.prefixes:
            key_vals = {
                k: v
                for k, v in key_vals.items()
                if any(k.startswith(p) for p in self.prefixes)
            }
            expired = [
                k
                for k in expired
                if any(k.startswith(p) for p in self.prefixes)
            ]
        if self.originators:
            key_vals = {
                k: v
                for k, v in key_vals.items()
                if v.originator_id in self.originators
            }
        if not key_vals and not expired:
            return None
        if len(key_vals) == len(pub.key_vals) and len(expired) == len(
            pub.expired_keys
        ):
            return pub  # unfiltered: share the publication object
        return Publication(
            key_vals=key_vals, expired_keys=expired, area=self.area
        )

    def _coalesce(self, frames):
        t0 = frames[0][1]
        key_vals: Dict[str, Any] = {}
        expired: Dict[str, None] = {}
        for frame, _ in frames:
            pub = _unwrap(frame)
            for key in pub.expired_keys:
                key_vals.pop(key, None)
                expired[key] = None
            for key, value in pub.key_vals.items():
                expired.pop(key, None)
                key_vals[key] = value  # newest version wins
        merged = Publication(
            key_vals=key_vals, expired_keys=list(expired), area=self.area
        )
        return merged, t0, len(key_vals) + len(expired)


# delete markers inside the coalesced route maps
_DELETE = object()


class RouteSubscription(_BaseSubscription):
    """Decision route-update stream (the DeltaPath consumer path)."""

    kind = "routes"

    @property
    def filter_key(self) -> Tuple:
        # route subscriptions carry no filters: one class for all
        return ("routes",)

    def _filter(
        self, update: DecisionRouteUpdate
    ) -> Optional[DecisionRouteUpdate]:
        return None if update.empty() else update

    def _coalesce(self, frames):
        t0 = frames[0][1]
        unicast: Dict[Any, Any] = {}
        mpls: Dict[int, Any] = {}
        for frame, _ in frames:
            update = _unwrap(frame)
            for prefix in update.unicast_routes_to_delete:
                unicast[prefix] = _DELETE
            for entry in update.unicast_routes_to_update:
                unicast[entry.prefix] = entry
            for label in update.mpls_routes_to_delete:
                mpls[label] = _DELETE
            for entry in update.mpls_routes_to_update:
                mpls[entry.label] = entry
        merged = DecisionRouteUpdate(
            unicast_routes_to_update=[
                e for e in unicast.values() if e is not _DELETE
            ],
            unicast_routes_to_delete=[
                p for p, e in unicast.items() if e is _DELETE
            ],
            mpls_routes_to_update=[
                e for e in mpls.values() if e is not _DELETE
            ],
            mpls_routes_to_delete=[
                label for label, e in mpls.items() if e is _DELETE
            ],
        )
        return merged, t0, len(unicast) + len(mpls)


@owned_by("ctrl-loop")
class StreamManager(CountersMixin, HistogramsMixin):
    """Subscription registry + fan-out dispatch for the ctrl server.

    One instance per daemon, registered with the Monitor as the
    `ctrl_stream` module so `ctrl.stream.*` land in every scrape."""

    def __init__(
        self,
        *,
        kvstore_updates=None,
        route_updates=None,
        config: Optional[StreamConfig] = None,
        loop: Optional[asyncio.AbstractEventLoop] = None,
    ) -> None:
        self._kvstore_updates = kvstore_updates
        self._route_updates = route_updates
        self.config = config or StreamConfig()
        self._loop = loop
        # subscriber registries: appended by ctrl connection tasks,
        # iterated by the dispatch tasks — all on one loop (the
        # publisher-side enqueue is the sanctioned handover seam)
        self._kv_subs: List[KvSubscription] = []  # analysis: queue
        self._route_subs: List[RouteSubscription] = []  # analysis: queue
        # filter-equivalence classes, maintained incrementally on add/
        # remove so dispatch never re-groups 100k subscribers per frame:
        # filter_key -> members (same handover seam as the registries)
        self._kv_classes: Dict[Tuple, List[KvSubscription]] = {}  # analysis: queue
        self._route_classes: Dict[Tuple, List[RouteSubscription]] = {}  # analysis: queue
        self._tasks: List[asyncio.Task] = []
        self._started = False
        self._ensure_counters()
        self._ensure_histograms()

    def loop(self) -> asyncio.AbstractEventLoop:
        return self._loop or asyncio.get_event_loop()

    # -- lifecycle ------------------------------------------------------

    def start(self) -> None:
        """Start one dispatch task per wired source queue. The readers
        drain continuously (zero subscribers = cheap drop), so the source
        ReplicateQueues never grow behind an idle manager."""
        if self._started:
            return
        self._started = True
        if self._kvstore_updates is not None:
            self._tasks.append(
                self.loop().create_task(
                    self._dispatch(
                        self._kvstore_updates.get_reader(),
                        self._kv_subs,
                        self._kv_classes,
                        "kvstore",
                    )
                )
            )
        if self._route_updates is not None:
            self._tasks.append(
                self.loop().create_task(
                    self._dispatch(
                        self._route_updates.get_reader(),
                        self._route_subs,
                        self._route_classes,
                        "routes",
                    )
                )
            )

    def stop(self) -> None:
        for task in self._tasks:
            task.cancel()
        self._tasks.clear()
        self._started = False
        for sub in list(self._kv_subs) + list(self._route_subs):
            sub.close()
        self._kv_subs.clear()
        self._route_subs.clear()
        self._kv_classes.clear()
        self._route_classes.clear()

    # -- subscription registry (ctrl connection tasks) ------------------

    def add_kvstore_subscriber(self, **kw) -> KvSubscription:
        self._check_capacity()
        sub = KvSubscription(self, **kw)
        self._kv_subs.append(sub)
        self._kv_classes.setdefault(sub.filter_key, []).append(sub)
        self._bump("ctrl.stream.subscribed_total")
        self._gauge_subscribers()
        return sub

    def add_route_subscriber(self, **kw) -> RouteSubscription:
        self._check_capacity()
        sub = RouteSubscription(self, **kw)
        self._route_subs.append(sub)
        self._route_classes.setdefault(sub.filter_key, []).append(sub)
        self._bump("ctrl.stream.subscribed_total")
        self._gauge_subscribers()
        return sub

    def remove_subscriber(self, sub: _BaseSubscription) -> None:
        sub.close()
        for registry in (self._kv_subs, self._route_subs):
            if sub in registry:
                registry.remove(sub)
        classes = (
            self._kv_classes if sub.kind == "kvstore" else self._route_classes
        )
        members = classes.get(sub.filter_key)
        if members is not None and sub in members:
            members.remove(sub)
            if not members:
                del classes[sub.filter_key]
        self._gauge_subscribers()

    def ensure_capacity(self) -> None:
        """Typed server-busy when `max_subscribers` is reached. The ctrl
        server calls this in the request handler (before the stream
        starts) so the rejection rides the normal error response; the
        add_* registrations re-check, race-free on one loop."""
        total = len(self._kv_subs) + len(self._route_subs)
        if total >= self.config.max_subscribers:
            self._bump("ctrl.stream.subscriber_rejects")
            raise SubscriberLimitError(
                f"subscriber limit reached ({self.config.max_subscribers})"
            )

    _check_capacity = ensure_capacity

    def _gauge_subscribers(self) -> None:
        counters = self._ensure_counters()
        counters["ctrl.stream.kv_subscribers_active"] = len(self._kv_subs)
        counters["ctrl.stream.route_subscribers_active"] = len(
            self._route_subs
        )

    def note_encode(self, ms: float, nbytes: int) -> None:
        """One REAL body serialization (docs/Monitoring.md): on the
        shared path this fires once per filter-class per frame (via
        `note_class_encode`); snapshot/resync/coalesced frames are
        per-subscriber state and meter their private encodes here too.
        `encode_ms` x `encode_bytes` is therefore the actual
        serialization bill — compare against `deliver_*` for the
        per-subscriber splice-and-write cost the sharing reduced it to."""
        self._observe("ctrl.stream.encode_ms", ms)
        self._bump("ctrl.stream.encode_bytes", nbytes)

    def note_class_encode(self, ms: float, nbytes: int) -> None:
        """A shared-path class encode: the one serialization a whole
        filter-equivalence class amortizes (`SharedFrame.body` miss)."""
        self._bump("ctrl.stream.encode_classes")
        self.note_encode(ms, nbytes)

    def note_class_hit(self) -> None:
        """A shared-bytes reuse (`SharedFrame.body` hit): hit rate =
        encode_class_hits / (encode_class_hits + encode_classes)."""
        self._bump("ctrl.stream.encode_class_hits")

    def note_deliver(self, ms: float, nbytes: int) -> None:
        """Per-subscriber delivery work (envelope splice + buffer
        write), recorded by the ctrl server per frame actually sent —
        the O(subscribers) half of the fan-out bill."""
        self._observe("ctrl.stream.deliver_ms", ms)
        self._bump("ctrl.stream.deliver_bytes", nbytes)

    def mark_delivered(self, sub: _BaseSubscription, t_enq: float) -> None:
        """Delivery accounting, called by the stream handler after the
        frame hit the socket: publish-to-deliver latency includes every
        millisecond a slow client spent stalled."""
        sub.delivered += 1
        self._bump("ctrl.stream.delivered")
        self._observe(
            "ctrl.stream.publish_to_deliver_ms",
            (time.monotonic() - t_enq) * 1e3,
        )

    def stats(self) -> Dict[str, Any]:
        """Live fan-out stats (ctrl getStreamStats / docs/Streaming.md)."""
        return {
            "kv_subscribers": len(self._kv_subs),
            "route_subscribers": len(self._route_subs),
            "kv_filter_classes": len(self._kv_classes),
            "route_filter_classes": len(self._route_classes),
            "max_subscribers": self.config.max_subscribers,
            "subscriber_max_pending": self.config.subscriber_max_pending,
            "coalesce_budget": self.config.coalesce_budget,
            "counters": dict(self._ensure_counters()),
        }

    # -- fan-out dispatch -----------------------------------------------

    async def _dispatch(
        self,
        reader,
        subs: List[_BaseSubscription],
        classes: Dict[Tuple, List[_BaseSubscription]],
        kind: str,
    ) -> None:
        try:
            while True:
                item = await reader.get()
                t_enq = time.monotonic()
                t0 = time.perf_counter()
                try:
                    # named fault seam: an injected fan-out failure must
                    # degrade to marked resyncs, never silent loss
                    fault_point("ctrl.stream.publish", item)
                    # filter ONCE per filter-equivalence class, wrap
                    # the result in a SharedFrame whose body bytes
                    # every class member reuses; per-subscriber work
                    # is one queue append
                    for members in list(classes.values()):
                        if not members:
                            continue
                        filtered = members[0]._filter(item)
                        if filtered is None:
                            continue
                        frame = SharedFrame(filtered, kind, self)
                        for sub in list(members):
                            sub.offer_shared(frame, t_enq)
                except Exception:
                    self._bump("ctrl.stream.publish_errors")
                    for sub in list(subs):
                        sub.force_resync()
                self._bump("ctrl.stream.published")
                if subs:
                    self._observe(
                        "ctrl.stream.fanout_ms",
                        (time.perf_counter() - t0) * 1e3,
                    )
        except (QueueClosedError, asyncio.CancelledError):
            return
        finally:
            reader.close()
