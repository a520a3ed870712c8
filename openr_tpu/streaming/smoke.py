"""STREAM_SMOKE tier-1 smoke (the streaming sibling of FLEET/FAULT/
TRACE/SOAK/RESTART_SMOKE): the shared-encode fan-out invariant proven
end-to-end over real ctrl sockets — N subscribers in ONE
filter-equivalence class must cost exactly ONE class encode per
dispatched frame, with every other member reusing the shared bytes.

Sequence:

  1. a small VirtualNetwork line converges; N `subscribeKvStore`
     subscribers (same area, no prefix/originator filters — one filter
     class by construction) attach to one node and drain their
     snapshots (snapshots are per-subscriber private encodes and never
     touch the class meters);
  2. one mid-link flap runs fail→restore→reconverge; every delta frame
     the flap floods through the subscribed node's fan-out is filtered
     once, encoded once (`encode_classes`), and reused N-1 times
     (`encode_class_hits`);
  3. the contract: class encodes == frames each subscriber saw, class
     hits == (N-1) x class encodes, zero coalesces/resyncs (the queues
     are sized for the burst), and the node reports exactly one live
     kv filter class while the cohort is attached.

Sizes scale via STREAM_SMOKE_NODES / STREAM_SMOKE_SUBS; returns a
summary dict.
"""

from __future__ import annotations

import asyncio
import os
from typing import Any, Dict


def run_stream_smoke() -> Dict[str, Any]:
    from openr_tpu.ctrl.client import CtrlClient
    from openr_tpu.testing.wrapper import VirtualNetwork, wait_until

    n = max(3, int(os.environ.get("STREAM_SMOKE_NODES", "3")))
    subs = max(2, int(os.environ.get("STREAM_SMOKE_SUBS", "8")))
    mid = n // 2
    host = "n0"

    async def body() -> Dict[str, Any]:
        net = VirtualNetwork()
        for i in range(n):
            net.add_node(
                f"n{i}",
                loopback_prefix=f"10.{i}.0.0/24",
                # roomy bounds: the invariant under test is the encode
                # count, so no subscriber may overflow into coalesce or
                # resync (those re-enter the private-encode path)
                config_overrides={
                    "stream_config": {
                        "subscriber_max_pending": 256,
                        "coalesce_budget": 256,
                    }
                },
            )
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

        def partitioned() -> bool:
            left = net.wrappers[host].programmed_prefixes()
            return f"10.{n - 1}.0.0/24" not in left

        counts = [
            {"snapshot": 0, "delta": 0, "resync": 0} for _ in range(subs)
        ]
        clients: list = []
        tasks: list = []

        async def watch(client, idx: int) -> None:
            try:
                async for frame in client.subscribe(
                    "subscribeKvStore", area="0", client=f"smoke-{idx}"
                ):
                    kind = frame.get("type")
                    if kind in counts[idx]:
                        counts[idx][kind] += 1
            except Exception:
                pass

        sm = net.wrappers[host].daemon.stream_manager
        try:
            await wait_until(converged, timeout=60.0)
            port = net.wrappers[host].ctrl_port
            for i in range(subs):
                client = await CtrlClient("127.0.0.1", port).connect()
                clients.append(client)
                tasks.append(
                    asyncio.get_running_loop().create_task(
                        watch(client, i)
                    )
                )
            # every subscriber drained its snapshot (private encodes)
            await wait_until(
                lambda: all(c["snapshot"] == 1 for c in counts),
                timeout=30.0,
            )
            live = sm.stats()
            counters0 = dict(sm._ensure_counters())

            net.fail_link(
                f"n{mid}", f"if{mid}r", f"n{mid + 1}", f"if{mid + 1}l"
            )
            await wait_until(partitioned, timeout=60.0)
            net.restore_link(
                f"n{mid}", f"if{mid}r", f"n{mid + 1}", f"if{mid + 1}l"
            )
            await wait_until(converged, timeout=60.0)

            # drain to quiescence: the meters and every subscriber's
            # delta count must be read in ONE sync block (no await in
            # between) after a stable window, or in-flight deliveries
            # would skew the exact-count assertions below
            async def settle():
                while True:
                    pre = dict(sm._ensure_counters())
                    await asyncio.sleep(0.4)
                    post = dict(sm._ensure_counters())
                    snap = [c["delta"] for c in counts]
                    if (
                        snap[0] > 0
                        and all(s == snap[0] for s in snap)
                        and pre.get("ctrl.stream.published")
                        == post.get("ctrl.stream.published")
                        and pre.get("ctrl.stream.delivered")
                        == post.get("ctrl.stream.delivered")
                    ):
                        return post, snap[0]

            counters1, frames_per_sub = await asyncio.wait_for(
                settle(), timeout=30.0
            )
        finally:
            for task in tasks:
                task.cancel()
            if tasks:
                await asyncio.gather(*tasks, return_exceptions=True)
            for client in clients:
                await client.close()
            await net.stop_all()

        def delta(name: str) -> int:
            return counters1.get(name, 0) - counters0.get(name, 0)

        class_encodes = delta("ctrl.stream.encode_classes")
        class_hits = delta("ctrl.stream.encode_class_hits")
        summary = {
            "nodes": n,
            "subscribers": subs,
            "filter_classes_live": live["kv_filter_classes"],
            "frames_per_subscriber": frames_per_sub,
            "class_encodes": class_encodes,
            "class_hits": class_hits,
            "coalesced": delta("ctrl.stream.coalesced"),
            "resyncs": delta("ctrl.stream.resyncs"),
            "counts": counts,
        }
        # -- the smoke's contract ----------------------------------------
        # one filter class while the whole cohort is attached
        assert live["kv_filter_classes"] == 1, summary
        assert live["kv_subscribers"] == subs, summary
        # nothing overflowed: the invariant below would not hold otherwise
        assert summary["coalesced"] == 0, summary
        assert summary["resyncs"] == 0, summary
        assert all(c["resync"] == 0 for c in counts), summary
        # the tentpole invariant: exactly ONE class encode per frame,
        # shared with every other member of the class
        assert frames_per_sub > 0, summary
        assert class_encodes == frames_per_sub, summary
        assert class_hits == (subs - 1) * class_encodes, summary
        return summary

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(body())
    finally:
        loop.close()
