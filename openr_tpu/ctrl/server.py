"""Control-plane API server.

Behavioral port of openr/ctrl-server/OpenrCtrlHandler.{h,cpp}: one server
holding references to every module, exposing the OpenrCtrl surface
(openr/if/OpenrCtrl.thrift:128-507) — route/adjacency/prefix reads, KvStore
get/set/dump, drain + metric-override controls, RibPolicy, config-store
keys, event logs, counters — plus the server-streaming KvStore subscription
(subscribeKvStoreFilter, OpenrCtrlHandler.h:207-211) and the adjacency
long-poll (longPollKvStoreAdj, OpenrCtrlLongPollTest.cpp semantics).

Transport is length-free newline-delimited JSON over TCP (the fbthrift
Rocket transport is Meta-stack-specific; a framed-JSON protocol keeps the
same request/response + streaming semantics with zero extra dependencies):
  request:   {"id": N, "method": "...", "params": {...}}
  response:  {"id": N, "result": ...} | {"id": N, "error": "..."}
  streaming: {"id": N, "stream": ...}* then {"id": N, "done": true}
  typed err: {"id": N, "error": "...", "error_kind": "server_busy",
              "retry_after_ms": M}

The streaming control plane (docs/Streaming.md) rides this transport:
`subscribeKvStore` / `subscribeRouteDb` stream typed frames ("snapshot",
then "delta"s, with marked "resync" snapshots after fan-out overflow)
through the daemon's StreamManager (bounded per-subscriber queues —
a stalled reader can never block publication or other subscribers), and
the expensive RPCs (`runTeOptimize`, `getRouteDbComputed`,
`getConvergenceReport`) pass through the AdmissionController's weighted
fair queue, rejecting with the typed server-busy error above when the
bounded wait expires.
"""

from __future__ import annotations

import asyncio
import base64
import json
import logging
import time
from typing import Any, Callable, Dict, List, Optional

from openr_tpu.kvstore import wire
from openr_tpu.messaging import QueueClosedError
from openr_tpu.monitor.spans import stage
from openr_tpu.testing.faults import fault_point
from openr_tpu.types import (
    ADJ_DB_MARKER,
    IpPrefix,
    KeyVals,
    Publication,
    Value,
)
from openr_tpu.utils import serializer

log = logging.getLogger(__name__)


def _b64(data: Optional[bytes]) -> Optional[str]:
    return None if data is None else base64.b64encode(data).decode()


def _unb64(text: Optional[str]) -> Optional[bytes]:
    return None if text is None else base64.b64decode(text)


# Value codecs are shared with the TCP peer protocol (kvstore/wire.py) so
# the ctrl API and peer wire format cannot drift apart
_value_to_json = wire.value_to_json
_value_from_json = wire.value_from_json


def _publication_to_json(pub: Publication) -> Dict[str, Any]:
    """Subscriber-facing publication: node_ids/tobe_updated_keys (peer-sync
    internals) are intentionally omitted."""
    return {
        "area": pub.area,
        "key_vals": wire.key_vals_to_json(pub.key_vals),
        "expired_keys": list(pub.expired_keys),
    }


def _encode_config(config) -> dict:
    """Serialize a Config's OpenrConfig dataclass tree to plain JSON."""
    import dataclasses

    def enc(obj):
        if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
            return {
                f.name: enc(getattr(obj, f.name))
                for f in dataclasses.fields(obj)
            }
        if isinstance(obj, (list, tuple)):
            return [enc(x) for x in obj]
        if hasattr(obj, "name") and hasattr(obj, "value"):
            return obj.name  # enum
        return obj

    return enc(config.config)


def _obj_to_json(obj: Any) -> Any:
    """Wire dataclasses ride the deterministic serializer as b64 blobs."""
    return _b64(serializer.dumps(obj))


class CtrlServer:
    def __init__(
        self,
        node_name: str,
        host: str = "127.0.0.1",
        port: int = 2018,
        *,
        kvstore=None,
        decision=None,
        fib=None,
        link_monitor=None,
        prefix_manager=None,
        monitor=None,
        exporter=None,
        config_store=None,
        config=None,
        stream_manager=None,
        admission=None,
        journal=None,
        route_updates=None,
        loop: Optional[asyncio.AbstractEventLoop] = None,
        ssl_context=None,
        tls_acceptable_peers=None,
    ) -> None:
        self.node_name = node_name
        self.host = host
        self.port = port
        self._ssl_context = ssl_context
        self._tls_acceptable_peers = tls_acceptable_peers
        self.kvstore = kvstore
        self.decision = decision
        self.fib = fib
        self.link_monitor = link_monitor
        self.prefix_manager = prefix_manager
        self.monitor = monitor
        self.exporter = exporter
        self.config_store = config_store
        self.config = config
        # streaming control plane (docs/Streaming.md): in the daemon both
        # are built by openr.py and shared with the monitor; standalone
        # embeddings (tests, tools) get defaults built in start()
        self.stream_manager = stream_manager
        self.admission = admission
        self.journal = journal
        # this module's own stages (ctrl.decode_ms); the daemon registers
        # the server with its monitor like any other module
        self.histograms: Dict = {}
        self._route_updates = route_updates
        self._own_stream_manager = False
        # on-demand jax profiling window (monitor/profiling.py), built
        # lazily by the first startProfile/getProfileStatus
        self._profile_controller = None
        self._loop = loop
        self._server: Optional[asyncio.AbstractServer] = None
        self._conn_tasks: set = set()
        self._methods: Dict[str, Callable] = {
            name[len("m_"):]: getattr(self, name)
            for name in dir(self)
            if name.startswith("m_")
        }

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    async def start(self) -> int:
        if self.stream_manager is None and (
            self.kvstore is not None or self._route_updates is not None
        ):
            # standalone embedding: own a default-config fan-out layer
            from openr_tpu.streaming import StreamManager

            self.stream_manager = StreamManager(
                kvstore_updates=(
                    self.kvstore.updates_queue
                    if self.kvstore is not None
                    else None
                ),
                route_updates=self._route_updates,
                loop=self._loop,
            )
            self._own_stream_manager = True
        if self.stream_manager is not None and self._own_stream_manager:
            self.stream_manager.start()
        if self.admission is None:
            from openr_tpu.streaming import AdmissionController

            self.admission = AdmissionController()
        # request lines are one JSON document each; bulk writes (e.g. a
        # big setKvStoreKeyVals) overflow asyncio's default 64 KiB
        # readline limit — mirror the client's fleet-scale line limit
        from openr_tpu.ctrl.client import _LINE_LIMIT

        self._server = await asyncio.start_server(
            self._handle_conn,
            self.host,
            self.port,
            ssl=self._ssl_context,
            limit=_LINE_LIMIT,
        )
        self.port = self._server.sockets[0].getsockname()[1]
        return self.port

    async def stop(self) -> None:
        if self._profile_controller is not None:
            # a profiling window must not outlive the daemon it profiles
            self._profile_controller.stop()
        if self.stream_manager is not None and self._own_stream_manager:
            self.stream_manager.stop()
        if self._server is not None:
            self._server.close()
            # cancel in-flight handlers (streaming subscriptions block on
            # the kvstore updates reader and never see the socket close)
            for task in list(self._conn_tasks):
                task.cancel()
            await asyncio.gather(*self._conn_tasks, return_exceptions=True)
            self._conn_tasks.clear()
            await self._server.wait_closed()
            self._server = None

    async def _handle_conn(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        task = asyncio.current_task()
        if task is not None:
            self._conn_tasks.add(task)
            task.add_done_callback(self._conn_tasks.discard)
        if self._ssl_context is not None:
            from openr_tpu.utils.tls import enforce_acceptable_peer

            if not enforce_acceptable_peer(
                writer, self._tls_acceptable_peers, log, "ctrl"
            ):
                return
        try:
            while True:
                line = await reader.readline()
                if not line:
                    return
                if line.startswith((b"GET ", b"HEAD ")):
                    # plain HTTP-ish scrape handler: a stock Prometheus
                    # scraper (or curl) polling GET /metrics on the ctrl
                    # port gets a one-shot exposition response — no JSON
                    # request ever starts with an HTTP method line
                    await self._serve_http_scrape(line, reader, writer)
                    return
                try:
                    req = json.loads(line)
                    name = req.get("method", "")
                    method = self._methods.get(name)
                    if method is None:
                        resp = {
                            "id": req.get("id"),
                            "error": f"unknown method {name}",
                        }
                    else:
                        params = req.get("params") or {}
                        if self.admission is not None and (
                            self.admission.guards(name)
                        ):
                            # expensive RPC: weighted fair admission with
                            # bounded wait + typed server-busy rejection
                            # (docs/Streaming.md admission section)
                            result = await self.admission.run(
                                name,
                                self._client_id(writer, params),
                                lambda: method(params),
                            )
                        else:
                            result = method(params)
                        if asyncio.iscoroutine(result):
                            result = await result
                        if result is _STREAMING:
                            # streaming method wrote frames itself
                            continue
                        resp = {"id": req.get("id"), "result": result}
                except _Streaming as stream:
                    await stream.run(req.get("id"), writer)
                    continue
                except Exception as exc:  # per-request isolation
                    resp = {"id": req.get("id"), "error": str(exc)}
                    kind = getattr(exc, "error_kind", None)
                    if kind is not None:
                        # typed rejection (server_busy): clients back off
                        # on retry_after_ms instead of piling on
                        resp["error_kind"] = kind
                        retry = getattr(exc, "retry_after_ms", None)
                        if retry is not None:
                            resp["retry_after_ms"] = int(retry)
                    else:
                        log.exception("ctrl method failed")
                writer.write(json.dumps(resp).encode() + b"\n")
                await writer.drain()
        except (
            ConnectionResetError,
            BrokenPipeError,
            asyncio.CancelledError,
        ):
            pass  # client hung up (possibly mid-write): normal teardown
        finally:
            writer.close()

    # ------------------------------------------------------------------
    # identity / config
    # ------------------------------------------------------------------

    def m_getMyNodeName(self, params) -> str:
        return self.node_name

    def m_getBuildInfo(self, params) -> Dict[str, str]:
        """fb303 getBuildInfo equivalent (common/BuildInfo exportBuildInfo)."""
        from openr_tpu.utils.build_info import get_build_info

        return get_build_info()

    def m_getRunningConfig(self, params) -> Optional[dict]:
        if self.config is None:
            return None
        return _encode_config(self.config)

    def m_dryrunConfig(self, params) -> dict:
        """Validate a candidate config (JSON text) without applying it;
        returns the parsed config dict or raises
        (OpenrCtrl.thrift dryrunConfig)."""
        import json as _json

        from openr_tpu.config import Config

        text = params.get("file")
        if params.get("path"):
            with open(params["path"], "r") as fh:
                text = fh.read()
        return _encode_config(Config.from_dict(_json.loads(text)))

    def m_processKvStoreDualMessage(self, params) -> None:
        """Inject a DualMessages batch into the area's KvStore DUAL node
        (OpenrCtrl.thrift processKvStoreDualMessage)."""
        assert self.kvstore is not None
        from openr_tpu.dual import DualMessage, DualMessages, DualMessageType

        msgs = DualMessages(
            src_id=params["messages"]["src_id"],
            messages=[
                DualMessage(
                    dst_id=m["dst_id"],
                    distance=int(m["distance"]),
                    type=DualMessageType[m["type"]]
                    if isinstance(m["type"], str)
                    else DualMessageType(m["type"]),
                )
                for m in params["messages"]["messages"]
            ],
        )
        self.kvstore.handle_dual_messages(params.get("area", "0"), msgs)

    def m_getCounters(self, params) -> Dict[str, int]:
        if self.monitor is not None:
            return self.monitor.get_counters()
        counters: Dict[str, int] = {}
        for module in (self.decision, self.fib, self.link_monitor):
            if module is not None and hasattr(module, "counters"):
                counters.update(module.counters)
        return counters

    def m_getHistograms(self, params) -> Dict[str, Any]:
        """Merged latency histograms of every registered module
        (count/sum/avg/min/max + p50/p95/p99 per name) — the fb303
        exported-histogram surface next to getCounters. `reset: true`
        clears the sources after export (reset-on-read windowing, so
        dashboards can compute rates from consecutive snapshots)."""
        reset = bool(params.get("reset", False))
        if self.monitor is not None:
            return self.monitor.get_histograms(reset=reset)
        from openr_tpu.monitor import merge_module_histograms

        merged = merge_module_histograms(
            (
                m
                for m in (self.decision, self.fib, self.link_monitor, self)
                if m is not None
            ),
            reset=reset,
        )
        return {name: h.to_dict() for name, h in sorted(merged.items())}

    def m_getSolverHealth(self, params) -> Dict[str, Any]:
        """Solver fault-domain state: degraded flag, breaker state,
        probe/audit stats, last-solve timing gauges, flight-recorder ring
        + forensics state (docs/Robustness.md)."""
        assert self.decision is not None, "decision module not attached"
        return self.decision.get_solver_health()

    def m_getDeviceMemory(self, params) -> Dict[str, Any]:
        """Device-memory observatory read surface (docs/Monitoring.md
        "Device-memory observatory"): the resident-state ledger snapshot
        — per-structure live bytes, exact-accounting totals, watermark
        reconciliation, capacity verdict and last admission refusal.
        params: area (narrows the entry listing)."""
        assert self.decision is not None, "decision module not attached"
        return self.decision.get_device_memory(
            area=params.get("area") or None
        )

    def m_getSolveTraces(self, params) -> Dict[str, Any]:
        """Flight-recorder read surface (docs/Monitoring.md "Flight
        recorder & profiling"): per-area SolveTrace rings (event class,
        layout, warm/cold, per-phase ms), ring/eviction
        accounting, and the forensics-dump index. params: area (filter),
        last_n (most recent N)."""
        assert self.decision is not None, "decision module not attached"
        last_n = params.get("last_n")
        return self.decision.get_solve_traces(
            area=params.get("area") or None,
            last_n=int(last_n) if last_n is not None else None,
        )

    def _profiler(self):
        if getattr(self, "_profile_controller", None) is None:
            from openr_tpu.monitor.profiling import ProfileController

            self._profile_controller = ProfileController()
        return self._profile_controller

    def m_startProfile(self, params) -> Dict[str, Any]:
        """Open a bounded on-demand jax.profiler window writing a
        TensorBoard-compatible trace dir (`breeze decision profile`).
        Admission-controlled like the other expensive RPCs; degrade-safe:
        an unavailable profiler reports in-band, never raises. params:
        seconds (clamped to [0.1, 600]), out (directory; temp dir when
        omitted)."""
        controller = self._profiler()
        result = controller.start(
            out_dir=params.get("out") or params.get("out_dir"),
            seconds=float(params.get("seconds", 5.0)),
        )
        if result.get("started"):
            # arm the expiry on the daemon loop so the bound holds even
            # if no client ever polls getProfileStatus
            try:
                loop = self._loop or asyncio.get_event_loop()
                loop.call_later(
                    controller.seconds + 0.05, controller.maybe_expire
                )
            except RuntimeError:
                pass  # loop-less embedding: status()/start() still expire
        return result

    def m_getProfileStatus(self, params) -> Dict[str, Any]:
        """Live profiling-window state (active, out_dir, remaining_s,
        last_error)."""
        return self._profiler().status()

    def m_getConvergenceReport(self, params) -> Dict[str, Any]:
        """This node's convergence evidence — finished CONVERGENCE_TRACE
        spans, FLOOD_TRACE hop samples and kvstore flood stats — for the
        cross-node aggregation (`breeze perf report`,
        monitor/report.py:aggregate_convergence_reports)."""
        assert self.monitor is not None, "monitor module not attached"
        from openr_tpu.monitor.report import node_convergence_report

        return node_convergence_report(
            self.node_name, self.monitor, kvstore=self.kvstore
        )

    def m_getEventLogs(self, params) -> List[str]:
        if self.monitor is None:
            return []
        return [s.to_json() for s in self.monitor.get_event_logs()]

    def m_getMetricsText(self, params) -> str:
        """The full counter/histogram registry (plus the convergence
        rollup's cumulative-vs-windowed split) in Prometheus text
        exposition format — the `breeze monitor scrape` / GET /metrics
        surface (docs/Monitoring.md exporter section)."""
        return self._metrics_text()

    def _metrics_text(self) -> str:
        from openr_tpu.monitor import merge_module_histograms
        from openr_tpu.monitor.exporter import render_metrics_text

        if self.exporter is not None:
            return self.exporter.render()
        if self.monitor is not None:
            return render_metrics_text(
                self.monitor.get_counters(),
                self.monitor.get_cumulative_histograms(),
                node_name=self.node_name,
                rollup=getattr(self.monitor, "rollup", None),
            )
        # monitor-less fallback: render straight off the wired modules
        modules = [
            m
            for m in (self.decision, self.fib, self.link_monitor)
            if m is not None
        ]
        counters: Dict[str, int] = {}
        for module in modules:
            if hasattr(module, "counters"):
                counters.update(module.counters)
        return render_metrics_text(
            counters,
            merge_module_histograms(modules),
            node_name=self.node_name,
        )

    async def _serve_http_scrape(
        self,
        request_line: bytes,
        reader: asyncio.StreamReader,
        writer: asyncio.StreamWriter,
    ) -> None:
        """Minimal HTTP response for GET/HEAD /metrics on the ctrl port
        (one request per connection, then close — all a scraper needs)."""
        parts = request_line.decode(errors="replace").split()
        method = parts[0] if parts else "GET"
        path = parts[1] if len(parts) > 1 else "/"
        while True:  # drain request headers
            line = await reader.readline()
            if not line or line in (b"\r\n", b"\n"):
                break
        if path.split("?", 1)[0].rstrip("/") in ("", "/metrics"):
            try:
                body = self._metrics_text().encode()
                status = "200 OK"
            except Exception as exc:  # pragma: no cover - defensive
                log.exception("metrics render failed")
                body = f"metrics render failed: {exc}\n".encode()
                status = "500 Internal Server Error"
        else:
            body = b"only /metrics is served here\n"
            status = "404 Not Found"
        head = (
            f"HTTP/1.0 {status}\r\n"
            "Content-Type: text/plain; version=0.0.4; charset=utf-8\r\n"
            f"Content-Length: {len(body)}\r\n"
            "Connection: close\r\n\r\n"
        ).encode()
        writer.write(head if method == "HEAD" else head + body)
        await writer.drain()

    # ------------------------------------------------------------------
    # route APIs
    # ------------------------------------------------------------------

    def m_getRouteDb(self, params) -> Dict[str, Any]:
        assert self.fib is not None, "fib module not attached"
        db = self.fib.get_route_db()
        return {
            "this_node_name": db["this_node_name"],
            "unicast_routes": [_obj_to_json(r) for r in db["unicast_routes"]],
            "mpls_routes": [_obj_to_json(r) for r in db["mpls_routes"]],
        }

    def m_getRouteDbComputed(self, params) -> Dict[str, Any]:
        assert self.decision is not None, "decision module not attached"
        node = params.get("node") or None
        db = self.decision.get_decision_route_db(node)
        unicast = []
        mpls = []
        if db is not None:
            unicast = [
                _obj_to_json(e.to_unicast_route())
                for e in db.unicast_entries.values()
            ]
            mpls = [
                _obj_to_json(e.to_mpls_route())
                for e in db.mpls_entries.values()
            ]
        return {
            "this_node_name": node or self.node_name,
            "unicast_routes": unicast,
            "mpls_routes": mpls,
        }

    def m_getUnicastRoutesFiltered(self, params) -> List[Any]:
        assert self.fib is not None
        routes = self.fib.get_unicast_routes(params.get("prefixes"))
        return [_obj_to_json(r) for r in routes]

    def m_getUnicastRoutes(self, params) -> List[Any]:
        return self.m_getUnicastRoutesFiltered({})

    def m_getMplsRoutesFiltered(self, params) -> List[Any]:
        assert self.fib is not None
        routes = self.fib.get_mpls_routes(params.get("labels"))
        return [_obj_to_json(r) for r in routes]

    def m_getMplsRoutes(self, params) -> List[Any]:
        return self.m_getMplsRoutesFiltered({})

    def m_getPerfDb(self, params) -> List[Any]:
        assert self.fib is not None
        return [_obj_to_json(p) for p in self.fib.get_perf_db()]

    # ------------------------------------------------------------------
    # decision APIs
    # ------------------------------------------------------------------

    def m_getDecisionAdjacencyDbs(self, params) -> Dict[str, Any]:
        assert self.decision is not None
        return {
            node: _obj_to_json(db)
            for node, db in self.decision.get_adjacency_databases().items()
        }

    def m_getAllDecisionAdjacencyDbs(self, params) -> List[Any]:
        """Deprecated list form of getDecisionAdjacencyDbs
        (OpenrCtrl.thrift getAllDecisionAdjacencyDbs)."""
        assert self.decision is not None
        return [
            _obj_to_json(db)
            for _, db in sorted(
                self.decision.get_adjacency_databases().items()
            )
        ]

    def m_getDecisionPrefixDbs(self, params) -> Dict[str, Any]:
        assert self.decision is not None
        return {
            f"{node}:{area}": _obj_to_json(db)
            for (
                node,
                area,
            ), db in self.decision.get_prefix_databases().items()
        }

    def m_runTeOptimize(self, params) -> Dict[str, Any]:
        """What-if gradient-descent TE optimization over the live LSDB
        (docs/TrafficEngineering.md): proposes link-metric changes plus
        the predicted hard-SPF max-link-utilization delta; programs
        nothing. params: demands (spec dict), steps, scenarios, area,
        seed, plus optimizer knobs (lr, tau0, tau_min, ...)."""
        assert self.decision is not None, "decision module not attached"
        return self.decision.run_te_optimize(params or {})

    def m_setRibPolicy(self, params) -> None:
        assert self.decision is not None
        from openr_tpu.solver.rib_policy import RibPolicy

        policy = RibPolicy.from_dict(params["policy"])
        self.decision.set_rib_policy(policy)

    def m_getRibPolicy(self, params) -> Optional[dict]:
        assert self.decision is not None
        policy = self.decision.get_rib_policy()
        return None if policy is None else policy.to_dict()

    # ------------------------------------------------------------------
    # prefix manager APIs
    # ------------------------------------------------------------------

    def _parse_prefix_entries(self, blobs: List[str]):
        return [serializer.loads(_unb64(b)) for b in blobs]

    def m_advertisePrefixes(self, params) -> bool:
        assert self.prefix_manager is not None
        return self.prefix_manager.advertise_prefixes(
            self._parse_prefix_entries(params["prefixes"])
        )

    def m_withdrawPrefixes(self, params) -> bool:
        assert self.prefix_manager is not None
        return self.prefix_manager.withdraw_prefixes(
            self._parse_prefix_entries(params["prefixes"])
        )

    def m_withdrawPrefixesByType(self, params) -> bool:
        assert self.prefix_manager is not None
        from openr_tpu.types import PrefixType

        return self.prefix_manager.withdraw_prefixes_by_type(
            PrefixType(params["type"])
        )

    def m_syncPrefixesByType(self, params) -> bool:
        assert self.prefix_manager is not None
        from openr_tpu.types import PrefixType

        return self.prefix_manager.sync_prefixes_by_type(
            PrefixType(params["type"]),
            self._parse_prefix_entries(params["prefixes"]),
        )

    def m_getPrefixes(self, params) -> List[Any]:
        assert self.prefix_manager is not None
        return [_obj_to_json(e) for e in self.prefix_manager.get_prefixes()]

    def m_getPrefixesByType(self, params) -> List[Any]:
        assert self.prefix_manager is not None
        from openr_tpu.types import PrefixType

        return [
            _obj_to_json(e)
            for e in self.prefix_manager.get_prefixes_by_type(
                PrefixType(params["type"])
            )
        ]

    # ------------------------------------------------------------------
    # kvstore APIs
    # ------------------------------------------------------------------

    def m_getKvStoreKeyVals(self, params) -> Dict[str, Any]:
        assert self.kvstore is not None
        area = params.get("area", "0")
        keys = params.get("keys", [])
        pub = self.kvstore.db(area).get_key_vals(keys)
        return _publication_to_json(pub)

    def m_getKvStoreKeyValsFiltered(self, params) -> Dict[str, Any]:
        assert self.kvstore is not None
        from openr_tpu.kvstore import KvStoreFilters

        area = params.get("area", "0")
        filters = KvStoreFilters(
            key_prefixes=params.get("prefixes") or [],
            originator_ids=set(params.get("originators") or []),
        )
        pub = self.kvstore.dump_all(area=area, filters=filters)
        return _publication_to_json(pub)

    def m_getKvStoreHashFiltered(self, params) -> Dict[str, Any]:
        assert self.kvstore is not None
        from openr_tpu.kvstore import KvStoreFilters

        area = params.get("area", "0")
        filters = KvStoreFilters(
            key_prefixes=params.get("prefixes") or []
        )
        pub = self.kvstore.db(area).dump_hashes(filters)
        return _publication_to_json(pub)

    def m_setKvStoreKeyVals(self, params) -> None:
        assert self.kvstore is not None
        area = params.get("area", "0")
        with stage("ctrl.decode", self.histograms):
            key_vals: KeyVals = {
                k: _value_from_json(v)
                for k, v in params.get("key_vals", {}).items()
            }
        self.kvstore.db(area).set_key_vals(key_vals)

    def m_getKvStorePeers(self, params) -> Dict[str, Any]:
        assert self.kvstore is not None
        area = params.get("area", "0")
        return {
            name: {"peer_addr": spec.peer_addr}
            for name, spec in self.kvstore.db(area).get_peers().items()
        }

    def m_getKvStorePeerHealth(self, params) -> Dict[str, Any]:
        """Peer-health quarantine ladder snapshot (docs/Runbook.md:
        `breeze kvstore peer-health`)."""
        assert self.kvstore is not None
        area = params.get("area", "0")
        return self.kvstore.db(area).get_peer_health()

    def m_getAreasConfig(self, params) -> Dict[str, Any]:
        assert self.kvstore is not None
        return {"areas": sorted(self.kvstore.dbs.keys())}

    def m_getSpanningTreeInfos(self, params) -> Dict[str, Any]:
        """OpenrCtrl.thrift getSpanningTreeInfos:375 — DUAL SPT state."""
        assert self.kvstore is not None
        area = params.get("area", "0")
        return self.kvstore.db(area).get_spt_infos()

    def m_updateFloodTopologyChild(self, params) -> None:
        """OpenrCtrl.thrift updateFloodTopologyChild:367."""
        assert self.kvstore is not None
        area = params.get("area", "0")
        self.kvstore.db(area).handle_flood_topo_set(
            params["root_id"],
            params["src_id"],
            bool(params["set_child"]),
            bool(params.get("all_roots", False)),
        )

    def m_longPollKvStoreAdj(self, params):
        """Block until any adj: key differs from the client's snapshot
        (OpenrCtrl.thrift:353, OpenrCtrlLongPollTest)."""
        assert self.kvstore is not None
        area = params.get("area", "0")
        snapshot: Dict[str, int] = params.get("snapshot", {})
        timeout = float(params.get("timeout_s", 20.0))

        def adj_changed() -> bool:
            pub = self.kvstore.dump_all(area=area)
            current = {
                k: v.version
                for k, v in pub.key_vals.items()
                if k.startswith(ADJ_DB_MARKER)
            }
            for key, version in current.items():
                if snapshot.get(key, -1) < version:
                    return True
            return any(k not in current for k in snapshot)

        async def wait() -> bool:
            if adj_changed():
                return True
            reader = self.kvstore.updates_queue.get_reader()
            loop = asyncio.get_event_loop()
            deadline = loop.time() + timeout
            try:
                while loop.time() < deadline:
                    try:
                        pub = await asyncio.wait_for(
                            reader.get(), deadline - loop.time()
                        )
                    except (asyncio.TimeoutError, QueueClosedError):
                        return False
                    if pub.area != area:
                        continue
                    if any(
                        k.startswith(ADJ_DB_MARKER)
                        for k in list(pub.key_vals) + pub.expired_keys
                    ):
                        return True
                return False
            finally:
                reader.close()

        return wait()

    def m_subscribeKvStoreFilter(self, params):
        """Server-streaming KvStore subscription
        (OpenrCtrlHandler.h:207-211): initial full dump frame, then every
        matching publication as a stream frame. Legacy frame shape (bare
        publication JSON); rides the same bounded fan-out as
        subscribeKvStore — an overflow resync arrives as a full-dump
        publication, which per-key merge clients absorb unmarked."""
        assert self.kvstore is not None
        if self.stream_manager is not None:
            self.stream_manager.ensure_capacity()
        raise _Streaming(self._kvstore_stream_legacy, params)

    def m_subscribeKvStore(self, params):
        """Streaming KvStore delta subscription (docs/Streaming.md):
        typed frames {"type": "snapshot"|"delta"|"resync", "seq": N,
        "pub": {...}} — initial full-sync snapshot, then per-publication
        deltas (key-prefix/originator filtered), with marked
        snapshot-resyncs after bounded fan-out overflow.
        params: area, prefixes, originators, client (fairness label)."""
        assert self.kvstore is not None
        if self.stream_manager is not None:
            # typed server-busy BEFORE the stream starts: the rejection
            # rides the normal error response with retry_after_ms
            self.stream_manager.ensure_capacity()
        raise _Streaming(self._kvstore_stream, params)

    def m_subscribeRouteDb(self, params):
        """Streaming RIB subscription (docs/Streaming.md): initial
        computed-RIB snapshot, then every DecisionRouteUpdate the
        DeltaPath emits, with marked snapshot-resyncs after overflow.
        Frames: {"type": ..., "seq": N, "unicast_to_update": [b64...],
        "unicast_to_delete": [...], "mpls_to_update": [...],
        "mpls_to_delete": [...]}; snapshots/resyncs carry the full RIB
        in the *_to_update fields."""
        assert self.decision is not None
        if self.stream_manager is not None:
            self.stream_manager.ensure_capacity()
        raise _Streaming(self._route_stream, params)

    def m_getStreamStats(self, params) -> Dict[str, Any]:
        """Live fan-out + admission state (docs/Streaming.md)."""
        out: Dict[str, Any] = {}
        if self.stream_manager is not None:
            out["stream"] = self.stream_manager.stats()
        if self.admission is not None:
            out["admission"] = self.admission.stats()
        return out

    # -- state journal (docs/Journal.md) --------------------------------

    def _journal_or_error(self) -> Any:
        if self.journal is None or not self.journal.config.enabled:
            return None
        return self.journal

    def m_getJournalStats(self, params) -> Dict[str, Any]:
        """Journal ring/base/durable-log state + journal.* counters."""
        journal = self._journal_or_error()
        if journal is None:
            return {"enabled": False}
        return journal.stats()

    def m_getJournalTail(self, params) -> Dict[str, Any]:
        """Most recent journal records, raw (forensics attachment +
        `breeze` debugging). params: last_n."""
        journal = self._journal_or_error()
        if journal is None:
            return {"enabled": False, "records": []}
        return {
            "enabled": True,
            "records": journal.tail(int(params.get("last_n", 32))),
        }

    def m_getKvStoreKeyHistory(self, params) -> Dict[str, Any]:
        """Bounded publication history of one key (`breeze kvstore
        history <key>`). params: key (required), area (filter)."""
        journal = self._journal_or_error()
        if journal is None:
            return {"enabled": False, "history": []}
        key = params.get("key")
        assert key, "key is required"
        return {
            "enabled": True,
            "key": key,
            "history": journal.key_history(
                key, area=params.get("area") or None
            ),
        }

    def m_getRibDiff(self, params) -> Dict[str, Any]:
        """RIB delta between two replayed instants (`breeze decision
        rib-diff --from T1 --to T2`). params: from_ts / to_ts — unix
        seconds, negative = relative to now, absent = latest."""
        journal = self._journal_or_error()
        if journal is None:
            return {"enabled": False}
        from_ts = params.get("from_ts")
        to_ts = params.get("to_ts")
        out = journal.rib_diff(
            float(from_ts) if from_ts is not None else None,
            float(to_ts) if to_ts is not None else None,
        )
        out["enabled"] = True
        return out

    def m_verifyJournalReplay(self, params) -> Dict[str, Any]:
        """Standing correctness audit: replay(T) vs the CPU oracle over
        the reconstructed LSDB. params: at."""
        journal = self._journal_or_error()
        if journal is None:
            return {"enabled": False}
        at = params.get("at")
        out = journal.verify_replay(
            float(at) if at is not None else None
        )
        out["enabled"] = True
        return out

    def m_explainRoute(self, params) -> Dict[str, Any]:
        """Provenance chain: route → contributing prefix/adjacency keys →
        originating publication → the SolveTrace that
        computed it. params: prefix (required), at."""
        journal = self._journal_or_error()
        if journal is None:
            return {"enabled": False, "found": False}
        prefix = params.get("prefix")
        assert prefix, "prefix is required"
        at = params.get("at")
        out = journal.explain_route(
            prefix, float(at) if at is not None else None
        )
        out["enabled"] = True
        # link the nearest SolveTrace at-or-before the replayed
        # instant (the flight recorder lives in Decision, not the journal)
        out["solve_trace"] = None
        if self.decision is not None and out.get("found"):
            at_ts = out.get("at_ts") or time.time()
            traces = self.decision.get_solve_traces().get("traces", [])
            best = None
            for trace in traces:
                ts = trace.get("ts")
                if ts is None or ts > at_ts:
                    continue
                if best is None or ts > best.get("ts", 0.0):
                    best = trace
            out["solve_trace"] = best
            if self.config is not None:
                out["rib_policy_active"] = bool(
                    self.config.config.enable_rib_policy
                )
        return out

    def _client_id(self, writer, params) -> str:
        """Admission fairness identity: the client-declared label when
        present (breeze --client), else the peer address."""
        label = params.get("client")
        if label:
            return str(label)
        peer = writer.get_extra_info("peername")
        return str(peer[0]) if peer else "unknown"

    def _kv_snapshot(self, area, prefixes, originators) -> Publication:
        from openr_tpu.kvstore import KvStoreFilters

        filters = None
        if prefixes or originators:
            filters = KvStoreFilters(
                key_prefixes=list(prefixes or []),
                originator_ids=set(originators or ()),
            )
        return self.kvstore.dump_all(area=area, filters=filters)

    def _encode_body(self, encode, *args) -> bytes:
        """One PRIVATE body serialization (snapshot, resync, or a
        coalesced per-subscriber frame) — metered as a real encode so
        `ctrl.stream.encode_*` stays the full serialization bill; the
        shared path meters its class encodes in `SharedFrame.body`."""
        t0 = time.perf_counter()
        body = encode(*args)
        if self.stream_manager is not None:
            self.stream_manager.note_encode(
                (time.perf_counter() - t0) * 1e3, len(body)
            )
        return body

    async def _write_frame(self, writer, segments, drain: bool = True) -> None:
        """Per-subscriber delivery: splice the envelope around the
        (possibly shared) body in ONE transport write — writev-style,
        `writelines` joins the segments once instead of issuing one
        socket send per segment. `ctrl.stream.deliver_*` meters exactly
        this work; the drain (socket backpressure, a slow client's
        stall) stays outside it. Callers delivering a burst pass
        `drain=False` while the subscriber queue still holds frames and
        drain once at burst end — the buffered bytes stay bounded by
        `subscriber_max_pending` frames, and a stalled client still
        blocks its own task at the burst-end drain, nobody else's."""
        t0 = time.perf_counter()
        writer.writelines(segments)
        total = sum(len(seg) for seg in segments)
        if self.stream_manager is not None:
            self.stream_manager.note_deliver(
                (time.perf_counter() - t0) * 1e3, total
            )
        if drain:
            await writer.drain()

    async def _ack_codec(self, writer, req_id, codec_name) -> None:
        """Codec negotiation (docs/Streaming.md): one JSON ack line, then
        every frame on this stream is length-prefixed binary. A server
        without binary support never sends the ack, so old clients and
        old servers both fall back to newline-JSON gracefully."""
        writer.write(
            json.dumps({"id": req_id, "codec": codec_name}).encode() + b"\n"
        )
        await writer.drain()

    async def _deliver_gate(self, sub) -> None:
        """Per-frame delivery seam: the `ctrl.stream.deliver` fault point
        (ctx=subscription) fires here — an armed exception tears the
        stream down (the client reconnects and resyncs), an armed action
        may set `sub.throttle_s` to emulate a slow client; the throttle
        is consumed one-shot per frame."""
        fault_point("ctrl.stream.deliver", sub)
        delay, sub.throttle_s = sub.throttle_s, 0.0
        if delay:
            await asyncio.sleep(delay)

    async def _kvstore_stream(
        self, req_id, writer, params, legacy: bool = False
    ) -> None:
        assert self.stream_manager is not None, "stream manager not wired"
        from openr_tpu.streaming import SharedFrame
        from openr_tpu.streaming import codec as stream_codec

        area = params.get("area", "0")
        prefixes = params.get("prefixes") or []
        originators = params.get("originators") or []
        # legacy streams stay newline-JSON (the debug/compat path);
        # unknown codec names degrade to JSON, never error
        codec_name = stream_codec.CODEC_JSON
        if not legacy:
            codec_name = stream_codec.normalize_codec(params.get("codec"))
        sub = self.stream_manager.add_kvstore_subscriber(
            area=area,
            prefixes=prefixes,
            originators=set(originators),
            label=str(params.get("client") or ""),
        )
        try:
            if codec_name == stream_codec.CODEC_BINARY:
                await self._ack_codec(writer, req_id, codec_name)
            # register-then-snapshot: a publication landing between the
            # two shows up in the snapshot AND as a delta — per-key
            # version merge makes the replay idempotent, nothing is lost
            snapshot = self._kv_snapshot(area, prefixes, originators)
            seq = 0
            body = self._encode_body(
                stream_codec.encode_kv_body, snapshot, codec_name
            )
            await self._write_frame(
                writer,
                stream_codec.kv_frame_segments(
                    codec_name, req_id, "snapshot", seq, area, body, legacy
                ),
            )
            while True:
                kind, frame, t_enq = await sub.next_frame()
                if kind == "closed":
                    return
                await self._deliver_gate(sub)
                seq += 1
                if kind == "resync":
                    # per-subscriber state: a fresh marked snapshot,
                    # encoded privately — it re-enters the shared path
                    # once the class re-converges on live deltas
                    pub = self._kv_snapshot(area, prefixes, originators)
                    body = self._encode_body(
                        stream_codec.encode_kv_body, pub, codec_name
                    )
                elif isinstance(frame, SharedFrame):
                    # the shared path: bytes encoded once per
                    # filter-equivalence class, reused here
                    body = frame.body(codec_name)
                else:
                    # coalesced merges are per-subscriber state:
                    # private encode
                    body = self._encode_body(
                        stream_codec.encode_kv_body, frame, codec_name
                    )
                # burst-drain: while the queue holds more frames, keep
                # splicing into the transport buffer and drain once at
                # burst end (bounded by subscriber_max_pending frames)
                await self._write_frame(
                    writer,
                    stream_codec.kv_frame_segments(
                        codec_name, req_id, kind, seq, area, body, legacy
                    ),
                    drain=not (sub._frames or sub._resync_at is not None),
                )
                self.stream_manager.mark_delivered(sub, t_enq)
        # CancelledError must PROPAGATE: server shutdown cancels this
        # connection task mid-stream, and swallowing the cancel here sent
        # the task back into _handle_conn's readline — stop()'s gather
        # then waited forever on a subscriber that never hangs up
        except (
            QueueClosedError,
            ConnectionResetError,
            BrokenPipeError,
        ):
            pass
        finally:
            self.stream_manager.remove_subscriber(sub)

    async def _kvstore_stream_legacy(self, req_id, writer, params) -> None:
        await self._kvstore_stream(req_id, writer, params, legacy=True)

    def _route_db_fields(self) -> Dict[str, Any]:
        """Full computed RIB as the four route-list fields of a
        snapshot/resync frame body."""
        db = self.decision.get_decision_route_db(None)
        unicast = mpls = []
        if db is not None:
            unicast = [
                _obj_to_json(e.to_unicast_route())
                for e in db.unicast_entries.values()
            ]
            mpls = [
                _obj_to_json(e.to_mpls_route())
                for e in db.mpls_entries.values()
            ]
        return {
            "unicast_to_update": unicast,
            "unicast_to_delete": [],
            "mpls_to_update": mpls,
            "mpls_to_delete": [],
        }

    async def _route_stream(self, req_id, writer, params) -> None:
        assert self.stream_manager is not None, "stream manager not wired"
        from openr_tpu.streaming import SharedFrame
        from openr_tpu.streaming import codec as stream_codec

        codec_name = stream_codec.normalize_codec(params.get("codec"))
        sub = self.stream_manager.add_route_subscriber(
            label=str(params.get("client") or "")
        )
        try:
            if codec_name == stream_codec.CODEC_BINARY:
                await self._ack_codec(writer, req_id, codec_name)
            seq = 0
            body = self._encode_body(
                stream_codec.encode_route_body,
                self._route_db_fields(),
                codec_name,
            )
            await self._write_frame(
                writer,
                stream_codec.route_frame_segments(
                    codec_name, req_id, "snapshot", seq, body
                ),
            )
            while True:
                kind, frame, t_enq = await sub.next_frame()
                if kind == "closed":
                    return
                await self._deliver_gate(sub)
                seq += 1
                if kind == "resync":
                    body = self._encode_body(
                        stream_codec.encode_route_body,
                        self._route_db_fields(),
                        codec_name,
                    )
                elif isinstance(frame, SharedFrame):
                    body = frame.body(codec_name)
                else:
                    body = self._encode_body(
                        stream_codec.encode_route_body,
                        stream_codec.route_fields_from_update(frame),
                        codec_name,
                    )
                await self._write_frame(
                    writer,
                    stream_codec.route_frame_segments(
                        codec_name, req_id, kind, seq, body
                    ),
                    drain=not (sub._frames or sub._resync_at is not None),
                )
                self.stream_manager.mark_delivered(sub, t_enq)
        # CancelledError must propagate (see _kvstore_stream)
        except (
            QueueClosedError,
            ConnectionResetError,
            BrokenPipeError,
        ):
            pass
        finally:
            self.stream_manager.remove_subscriber(sub)

    # ------------------------------------------------------------------
    # link monitor APIs (drain / metric overrides)
    # ------------------------------------------------------------------

    def m_setNodeOverload(self, params) -> None:
        assert self.link_monitor is not None
        self.link_monitor.set_node_overload(True)

    def m_unsetNodeOverload(self, params) -> None:
        assert self.link_monitor is not None
        self.link_monitor.set_node_overload(False)

    def m_setInterfaceOverload(self, params) -> None:
        assert self.link_monitor is not None
        self.link_monitor.set_link_overload(params["interface"], True)

    def m_unsetInterfaceOverload(self, params) -> None:
        assert self.link_monitor is not None
        self.link_monitor.set_link_overload(params["interface"], False)

    def m_setInterfaceMetric(self, params) -> None:
        assert self.link_monitor is not None
        self.link_monitor.set_link_metric(
            params["interface"], int(params["metric"])
        )

    def m_unsetInterfaceMetric(self, params) -> None:
        assert self.link_monitor is not None
        self.link_monitor.set_link_metric(params["interface"], None)

    def m_setAdjacencyMetric(self, params) -> None:
        assert self.link_monitor is not None
        self.link_monitor.set_adjacency_metric(
            params["interface"],
            params["adjNodeName"],
            int(params["metric"]),
        )

    def m_unsetAdjacencyMetric(self, params) -> None:
        assert self.link_monitor is not None
        self.link_monitor.set_adjacency_metric(
            params["interface"], params["adjNodeName"], None
        )

    def m_getInterfaces(self, params) -> Dict[str, Any]:
        assert self.link_monitor is not None
        return {
            name: {
                "is_up": e.is_up,
                "is_active": e.is_active(),
                "addresses": list(e.addresses),
            }
            for name, e in self.link_monitor.get_interfaces().items()
        }

    def m_getLinkMonitorAdjacencies(self, params) -> List[Any]:
        assert self.link_monitor is not None
        return [
            _obj_to_json(adj)
            for adj in self.link_monitor.get_adjacencies().values()
        ]

    # ------------------------------------------------------------------
    # config-store APIs
    # ------------------------------------------------------------------

    def m_setConfigKey(self, params) -> None:
        assert self.config_store is not None
        self.config_store.store(params["key"], _unb64(params["value"]))

    def m_eraseConfigKey(self, params) -> bool:
        assert self.config_store is not None
        return self.config_store.erase(params["key"])

    def m_getConfigKey(self, params) -> Optional[str]:
        assert self.config_store is not None
        return _b64(self.config_store.load(params["key"]))


class _Streaming(Exception):
    """Raised by streaming methods; _handle_conn runs the stream."""

    def __init__(self, fn, params) -> None:
        super().__init__("streaming")
        self.fn = fn
        self.params = params

    async def run(self, req_id, writer) -> None:
        await self.fn(req_id, writer, self.params)


_STREAMING = object()
