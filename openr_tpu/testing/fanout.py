"""In-process fan-out cohort: the scale half of the 100k-subscriber proof.

Real TCP subscribers cap out at the file-descriptor limit (one socket
each on both ends — ~10k subscribers against a 20k fd limit), so the
100k-subscriber soak round (docs/Streaming.md, testing/soak.py --round)
runs a HYBRID cohort:

  - a real-socket cohort (a few thousand `subscribeKvStore` connections,
    mixed JSON/binary codecs, admission control and slow-client
    isolation live under load), and
  - an in-process cohort: subscribers registered directly on each
    node's `StreamManager` — indistinguishable from socket subscribers
    to the fan-out dispatch, the filter-class grouping, coalescing and
    resync machinery — drained by ONE pump task per node through the
    exact delivery path the ctrl server uses: `SharedFrame.body()`
    (shared class encode), envelope splice via the frame-segment
    builders, `note_deliver`/`mark_delivered` metering, with the bytes
    landing in a counting sink instead of a socket.

The cohort sizes are reported separately everywhere (SOAK artifact,
flap-batch summary) so the accounting stays honest about what was a real
socket and what was in-process.
"""

from __future__ import annotations

import asyncio
import time
from typing import Any, Dict, List

from openr_tpu.streaming import SharedFrame
from openr_tpu.streaming import codec as stream_codec


class InprocFanout:
    """`count` in-process KvStore subscribers on one daemon's
    StreamManager, drained by a single pump task.

    All subscribers share one filter class by default (`area`, no
    prefix/originator filters) — the shape the shared-encode path
    amortizes; pass `prefixes` per the class you want to exercise.
    """

    def __init__(
        self,
        daemon,
        count: int,
        *,
        codec: str = stream_codec.CODEC_JSON,
        area: str = "0",
        prefixes: List[str] | None = None,
    ) -> None:
        self.daemon = daemon
        self.count = count
        self.codec = stream_codec.normalize_codec(codec)
        self.area = area
        self.prefixes = list(prefixes or [])
        self.subs: List[Any] = []
        self._task: asyncio.Task | None = None
        self._stop = False
        self.stats: Dict[str, int] = {
            "subscribers": count,
            "frames": 0,
            "deltas": 0,
            "resyncs": 0,
            "bytes": 0,
        }

    def attach(self) -> None:
        """Register the cohort (counts against `max_subscribers`, same
        as socket subscribers — raise the cap in the node config for
        scale runs)."""
        manager = self.daemon.stream_manager
        for i in range(self.count):
            self.subs.append(
                manager.add_kvstore_subscriber(
                    area=self.area,
                    prefixes=self.prefixes,
                    label=f"inproc-{i}",
                )
            )

    def start(self) -> None:
        self._task = asyncio.get_running_loop().create_task(self._pump())

    async def stop(self) -> None:
        self._stop = True
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        manager = self.daemon.stream_manager
        for sub in self.subs:
            manager.remove_subscriber(sub)
        self.subs.clear()

    async def _pump(self) -> None:
        """One task drains every cohort subscriber: all members share a
        filter class, so a sequential sweep never blocks on one empty
        queue while another has frames — each sweep delivers whatever
        the dispatch enqueued since the last one."""
        manager = self.daemon.stream_manager
        seqs = [0] * len(self.subs)
        while not self._stop:
            delivered = False
            for idx, sub in enumerate(self.subs):
                while sub._frames or sub._resync_at is not None:
                    kind, frame, t_enq = await sub.next_frame()
                    if kind == "closed":
                        break
                    seqs[idx] += 1
                    if kind == "resync":
                        # the real resync cost: fresh dump + private encode
                        pub = self.daemon.kvstore.dump_all(area=self.area)
                        t0 = time.perf_counter()
                        body = stream_codec.encode_kv_body(pub, self.codec)
                        manager.note_encode(
                            (time.perf_counter() - t0) * 1e3, len(body)
                        )
                        self.stats["resyncs"] += 1
                    elif isinstance(frame, SharedFrame):
                        body = frame.body(self.codec)
                        self.stats["deltas"] += 1
                    else:
                        t0 = time.perf_counter()
                        body = stream_codec.encode_kv_body(frame, self.codec)
                        manager.note_encode(
                            (time.perf_counter() - t0) * 1e3, len(body)
                        )
                        self.stats["deltas"] += 1
                    # the per-subscriber delivery work, identical to the
                    # ctrl server's: envelope splice + buffer "write"
                    t0 = time.perf_counter()
                    segments = stream_codec.kv_frame_segments(
                        self.codec, 1, kind, seqs[idx], self.area, body
                    )
                    nbytes = sum(len(s) for s in segments)
                    manager.note_deliver(
                        (time.perf_counter() - t0) * 1e3, nbytes
                    )
                    self.stats["bytes"] += nbytes
                    self.stats["frames"] += 1
                    manager.mark_delivered(sub, t_enq)
                    delivered = True
                    # cooperative: a 12k-subscriber sweep must not
                    # monopolize the loop the daemon itself runs on
                    if self.stats["frames"] % 512 == 0:
                        await asyncio.sleep(0)
            if not delivered:
                await asyncio.sleep(0.02)
