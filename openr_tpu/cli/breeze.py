"""breeze — operator CLI for the openr-tpu daemon.

Equivalent of openr/py/openr/cli/breeze.py (the click CLI root) and the
command impls under openr/py/openr/cli/commands/: per-module command groups
talking to the ctrl server (kvstore / decision / fib / lm / prefixmgr /
monitor / openr). argparse instead of click (no extra deps in this image);
same command vocabulary:

  breeze kvstore keys|keyvals|peers|peer-health|areas|history KEY [--area A]
  breeze decision adj|prefixes|routes|rib-policy|solver-health|
                  memory [--area A] [--json]
                  (device-memory observatory ledger, docs/Monitoring.md)|
                  solve-traces [--json]|profile [--seconds N] [--out DIR]|
                  profile-status|
                  te-optimize [--demands file.json] [--steps N] [--json]|
                  explain-route PREFIX [--at T]|
                  rib-diff [--from T1] [--to T2]|verify-replay [--at T]
                  (state-journal provenance + time travel, docs/Journal.md)
  breeze fib routes|unicast-routes|mpls-routes|counters
  breeze lm links|set-node-overload|unset-node-overload|
            set-link-overload|unset-link-overload|
            set-link-metric|unset-link-metric
  breeze prefixmgr view|advertise|withdraw|sync
  breeze monitor counters|histograms[--reset]|logs
  breeze openr version|config
  breeze perf view                   (fib perf event database — 'breeze perf')
  breeze fleet status|watch|report   (fleet observer + SLO watchdog,
                                      docs/Monitoring.md "Fleet observer")
  breeze config show|dryrun          (running config / validate candidate)
  breeze tech-support                (one-shot full state dump)

plus `breeze decision path SRC DST` (all shortest paths between two nodes,
computed client-side from the adjacency dump like
openr/py/openr/cli/commands/decision.py PathCmd) and `breeze kvstore snoop`
(stream deltas; the standalone snooper lives in openr_tpu.kvstore.snooper).

Run as: python -m openr_tpu.cli.breeze --host H --port P <module> <cmd> ...
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, List

from openr_tpu.ctrl.client import (
    BlockingCtrlClient,
    decode_obj,
    encode_obj,
)

from openr_tpu.utils.build_info import PACKAGE as _PKG
from openr_tpu.utils.build_info import VERSION as _PKG_VERSION

VERSION = f"{_PKG} {_PKG_VERSION} (Open/R protocol compatible rebuild)"


def _print_json(data: Any) -> None:
    print(json.dumps(data, indent=2, sort_keys=True, default=str))


def _print_table(headers: List[str], rows: List[List[Any]]) -> None:
    widths = [
        max(len(str(h)), *(len(str(r[i])) for r in rows)) if rows else len(h)
        for i, h in enumerate(headers)
    ]
    line = "  ".join(str(h).ljust(w) for h, w in zip(headers, widths))
    print(line)
    print("-" * len(line))
    for row in rows:
        print("  ".join(str(c).ljust(w) for c, w in zip(row, widths)))


def _fmt_nexthops(route) -> str:
    return ", ".join(
        f"{nh.address}%{nh.iface or '*'} (m={nh.metric}, w={nh.weight})"
        for nh in route.nexthops
    )


# ---------------------------------------------------------------------------
# command handlers
# ---------------------------------------------------------------------------


def cmd_kvstore(client: BlockingCtrlClient, args) -> None:
    if args.cmd == "keys":
        pub = client.call(
            "getKvStoreKeyValsFiltered",
            area=args.area,
            prefixes=[args.prefix] if args.prefix else [],
        )
        rows = [
            [k, v["originator_id"], v["version"], v["ttl"], v["ttl_version"]]
            for k, v in sorted(pub["key_vals"].items())
        ]
        _print_table(
            ["Key", "Originator", "Version", "TTL(ms)", "TTL-Version"], rows
        )
    elif args.cmd == "keyvals":
        pub = client.call(
            "getKvStoreKeyVals", area=args.area, keys=args.keys
        )
        for key, v in sorted(pub["key_vals"].items()):
            print(f"> {key}")
            obj = decode_obj(v["value"])
            _print_json(
                obj if not hasattr(obj, "__dict__") else vars(obj)
            )
    elif args.cmd == "peers":
        peers = client.call("getKvStorePeers", area=args.area)
        _print_table(
            ["Peer", "Address"],
            [[name, spec["peer_addr"]] for name, spec in sorted(peers.items())],
        )
    elif args.cmd == "peer-health":
        health = client.call("getKvStorePeerHealth", area=args.area)
        _print_table(
            [
                "Peer",
                "State",
                "Health",
                "Failures",
                "Probes",
                "Streak",
                "FloodsSkipped",
                "Quarantined(ms)",
            ],
            [
                [
                    name,
                    h["state"],
                    h["health"],
                    h["failures"],
                    h["probes"],
                    h["probe_streak"],
                    h["floods_skipped"],
                    h["quarantined_ms"],
                ]
                for name, h in sorted(health.items())
            ],
        )
    elif args.cmd == "areas":
        _print_json(client.call("getAreasConfig"))
    elif args.cmd == "snoop":
        for delta in client.subscribe(
            "subscribeKvStoreFilter",
            area=args.area,
            prefixes=[args.prefix] if args.prefix else [],
        ):
            for key, val in sorted(delta.get("key_vals", {}).items()):
                print(
                    f"{key} v={val['version']} "
                    f"from={val['originator_id']} ttl={val['ttl']}"
                )
            for key in delta.get("expired_keys", []):
                print(f"{key} EXPIRED")
    elif args.cmd == "subscribe":
        # the streaming control plane's typed frames (docs/Streaming.md):
        # snapshot -> deltas, with marked snapshot-resyncs after a
        # bounded fan-out overflow ("[RESYNC]": replace local state)
        for frame in client.subscribe(
            "subscribeKvStore",
            area=args.area,
            prefixes=[args.prefix] if args.prefix else [],
            originators=args.originator or [],
            client=args.client,
            codec=args.codec,
        ):
            kind = frame.get("type", "delta")
            pub = frame.get("pub", {})
            tag = {"snapshot": "[SNAPSHOT]", "resync": "[RESYNC]"}.get(
                kind, ""
            )
            if tag:
                print(
                    f"{tag} seq={frame.get('seq')} "
                    f"{len(pub.get('key_vals', {}))} key(s)"
                )
            for key, val in sorted(pub.get("key_vals", {}).items()):
                print(
                    f"{key} v={val['version']} "
                    f"from={val['originator_id']} ttl={val['ttl']}"
                )
            for key in pub.get("expired_keys", []):
                print(f"{key} EXPIRED")
    elif args.cmd == "history":
        # journaled publication history of one key (docs/Journal.md)
        report = client.call(
            "getKvStoreKeyHistory", key=args.key, area=args.area
        )
        if args.json:
            _print_json(report)
            return
        if not report.get("enabled"):
            print("state journal not enabled (journal_config.enabled)")
            return
        rows = [
            [
                e["seq"],
                _fmt_ts(e.get("ts")),
                e.get("area", "-"),
                "DELETED" if e.get("deleted") else e.get("version"),
                e.get("ttl_version") if not e.get("deleted") else "-",
                e.get("originator_id") or "-",
            ]
            for e in report.get("history", [])
        ]
        if not rows:
            print(f"no journaled history for {args.key}")
            return
        _print_table(
            ["Seq", "Time", "Area", "Version", "TTL-Version", "Originator"],
            rows,
        )


def _fmt_ts(ts) -> str:
    if ts is None:
        return "-"
    import datetime

    return datetime.datetime.fromtimestamp(ts).strftime("%H:%M:%S.%f")[:-3]


def cmd_decision(client: BlockingCtrlClient, args) -> None:
    if args.cmd == "adj":
        dbs = client.call("getDecisionAdjacencyDbs")
        rows = []
        for node, blob in sorted(dbs.items()):
            db = decode_obj(blob)
            for adj in db.adjacencies:
                rows.append(
                    [
                        node,
                        adj.other_node_name,
                        adj.if_name,
                        adj.metric,
                        "overloaded" if adj.is_overloaded else "",
                    ]
                )
        _print_table(["Node", "Neighbor", "Iface", "Metric", "Flags"], rows)
    elif args.cmd == "prefixes":
        dbs = client.call("getDecisionPrefixDbs")
        rows = []
        for node_area, blob in sorted(dbs.items()):
            db = decode_obj(blob)
            for entry in db.prefix_entries:
                rows.append(
                    [node_area, str(entry.prefix), entry.type.value]
                )
        _print_table(["Node:Area", "Prefix", "Type"], rows)
    elif args.cmd == "routes":
        db = client.call("getRouteDbComputed", node=args.node)
        rows = []
        for blob in db["unicast_routes"]:
            route = decode_obj(blob)
            rows.append([str(route.dest), _fmt_nexthops(route)])
        _print_table(["Prefix", "Nexthops"], rows)
        if db["mpls_routes"]:
            rows = []
            for blob in db["mpls_routes"]:
                route = decode_obj(blob)
                rows.append([route.top_label, _fmt_nexthops(route)])
            _print_table(["Label", "Nexthops"], rows)
    elif args.cmd == "rib-policy":
        _print_json(client.call("getRibPolicy"))
    elif args.cmd == "solver-health":
        health = client.call("getSolverHealth")
        state = "DEGRADED" if health.get("degraded") else "HEALTHY"
        print(f"solver: {state} (breaker: {health.get('breaker_state')})")
        _print_json(health)
    elif args.cmd == "memory":
        snap = client.call("getDeviceMemory", area=args.area)
        if args.json:
            _print_json(snap)
            return
        totals = snap.get("totals", {})
        print(
            f"device memory: {totals.get('live_bytes', 0)} live / "
            f"{totals.get('peak_bytes', 0)} peak bytes, "
            f"accounting {'EXACT' if snap.get('exact') else 'VIOLATED'} "
            f"({totals.get('registers', 0)} registers, "
            f"{totals.get('releases', 0)} releases, "
            f"{totals.get('retained', 0)} retained)"
        )
        cap = snap.get("capacity", {})
        rec = snap.get("reconcile", {})
        print(
            f"capacity: {cap.get('capacity_bytes') or '-'} bytes "
            f"(source: {cap.get('source')}); reconcile via "
            f"{rec.get('source')}: backend={rec.get('backend_bytes')} "
            f"drift={rec.get('drift_bytes')}"
        )
        refusal = snap.get("last_refusal")
        if totals.get("capacity_refusals"):
            print(
                f"capacity refusals: {totals['capacity_refusals']} "
                f"(last: {refusal})"
            )
        _print_table(
            ["Structure", "LiveBytes"],
            [
                [name, nbytes]
                for name, nbytes in sorted(
                    snap.get("structures", {}).items()
                )
                if nbytes
            ],
        )
        rows = [
            [
                e["area"],
                e["structure"],
                e["layout"],
                e["dtype"],
                "x".join(str(s) for s in e["shape"]) or "-",
                e["nbytes"],
                "retained" if e["retained"] else "",
            ]
            for e in snap.get("entries", [])
        ]
        if rows:
            _print_table(
                ["Area", "Structure", "Layout", "Dtype", "Shape",
                 "Bytes", "Flags"],
                rows,
            )
    elif args.cmd == "solve-traces":
        report = client.call(
            "getSolveTraces", area=args.area, last_n=args.last
        )
        if args.json:
            _print_json(report)
            return
        if not report.get("enabled"):
            print("flight recorder not enabled (solver unsupervised)")
            return
        stats = report.get("stats", {})
        print(
            f"flight recorder: {stats.get('recorded', 0)} recorded = "
            f"{stats.get('retained', 0)} retained + "
            f"{stats.get('evicted', 0)} evicted; "
            f"ring {stats.get('ring_size', 0)}/area"
        )
        rows = []
        for t in report.get("traces", []):
            phases = t.get("phases") or {}
            rows.append(
                [
                    t["seq"],
                    t["area"],
                    t["event"],
                    t["layout"],
                    "warm" if t["warm"] else "cold",
                    (
                        f"{t['solve_ms']:.2f}"
                        if t.get("solve_ms") is not None
                        else "-"
                    ),
                    t.get("rounds") if t.get("rounds") is not None else "-",
                    (
                        " ".join(
                            f"{k}={v:.2f}" for k, v in sorted(phases.items())
                        )
                        if phases
                        else ("-" if not t.get("fault_kind")
                              else t["fault_kind"])
                    ),
                ]
            )
        _print_table(
            ["Seq", "Area", "Event", "Layout", "Disp", "ms", "Rounds",
             "Phases(ms) / fault"],
            rows,
        )
        dumps = report.get("forensics", [])
        if dumps:
            print("forensics dumps:")
            _print_table(
                ["Id", "Reason", "Traces", "Path"],
                [
                    [d["id"], d["reason"], d["traces"], d.get("path") or "-"]
                    for d in dumps
                ],
            )
    elif args.cmd == "profile":
        status = client.call(
            "startProfile", seconds=args.seconds, out=args.out
        )
        if status.get("started"):
            print(
                f"profiling window open: {status['seconds']}s -> "
                f"{status['out_dir']} (TensorBoard-compatible)"
            )
        else:
            print(f"profiling not started: {status.get('error')}")
        if args.json:
            _print_json(status)
    elif args.cmd == "profile-status":
        _print_json(client.call("getProfileStatus"))
    elif args.cmd == "te-optimize":
        params = {}
        if args.demands:
            with open(args.demands) as fh:
                params["demands"] = json.load(fh)
        if args.steps is not None:
            params["steps"] = args.steps
        if args.scenarios is not None:
            params["scenarios"] = args.scenarios
        report = client.call("runTeOptimize", **params)
        if args.json:
            _print_json(report)
            return
        state = "DEGRADED cpu" if report.get("degraded") else "ok"
        print(
            f"te-optimize [{state}]: max link util "
            f"{report['initial_max_util']:.3f} -> "
            f"{report['optimized_max_util']:.3f} "
            f"({report['scenarios']} scenario(s), {report['steps']} steps, "
            f"{report['solve_ms']:.1f}ms)"
        )
        if not report["weight_changes"]:
            print("no improving weight change found")
        else:
            _print_table(
                ["Node", "Neighbor", "Iface", "Metric", "Proposed"],
                [
                    [
                        c["node"],
                        c["neighbor"],
                        c["iface"],
                        c["metric_before"],
                        c["metric_after"],
                    ]
                    for c in report["weight_changes"]
                ],
            )
        hottest = report["top_links"]["optimized"]
        if hottest:
            print("hottest links (proposed weights, worst scenario):")
            _print_table(
                ["Src", "Dst", "Util"],
                [[l["src"], l["dst"], l["util"]] for l in hottest],
            )
    elif args.cmd == "subscribe-routes":
        # initial RIB snapshot then per-event DecisionRouteUpdate deltas
        # fed from Decision's DeltaPath stream (docs/Streaming.md)
        for frame in client.subscribe(
            "subscribeRouteDb", client=args.client, codec=args.codec
        ):
            kind = frame.get("type", "delta")
            if kind in ("snapshot", "resync"):
                print(
                    f"[{kind.upper()}] seq={frame.get('seq')} "
                    f"{len(frame.get('unicast_to_update', []))} unicast, "
                    f"{len(frame.get('mpls_to_update', []))} mpls route(s)"
                )
            for blob in frame.get("unicast_to_update", []):
                route = decode_obj(blob)
                print(f"+ {route.dest} via {_fmt_nexthops(route)}")
            for prefix in frame.get("unicast_to_delete", []):
                print(f"- {prefix}")
            for blob in frame.get("mpls_to_update", []):
                route = decode_obj(blob)
                print(
                    f"+ label {route.top_label} via {_fmt_nexthops(route)}"
                )
            for label in frame.get("mpls_to_delete", []):
                print(f"- label {label}")
    elif args.cmd == "explain-route":
        # provenance chain over the state journal (docs/Journal.md):
        # route -> contributing keys -> originating publication -> solve
        report = client.call(
            "explainRoute", prefix=args.prefix, at=args.at
        )
        if args.json:
            _print_json(report)
            return
        if not report.get("enabled"):
            print("state journal not enabled (journal_config.enabled)")
            return
        when = (
            _fmt_ts(report.get("at_ts"))
            if report.get("at_ts") is not None
            else "latest"
        )
        if not report.get("found"):
            print(
                f"{report['prefix']}: no route at {when} "
                f"(seq {report.get('at_seq')})"
            )
            return
        route = report.get("route", {})
        nexthops = ", ".join(
            f"{nh.get('address')}%{nh.get('iface') or '-'}"
            for nh in route.get("nexthops", [])
        )
        chain = "complete" if report.get("complete") else "INCOMPLETE"
        print(
            f"{report['prefix']} at {when} (seq {report.get('at_seq')}) "
            f"via [{nexthops}] — provenance {chain}"
        )
        rows = []
        for info in report.get("prefix_keys", []) + report.get(
            "adjacency_keys", []
        ):
            pub = info.get("publication") or {}
            rows.append(
                [
                    info["key"],
                    info["area"],
                    pub.get("seq", info.get("seq", "-")),
                    _fmt_ts(pub.get("ts")),
                    "DELETED" if pub.get("deleted") else pub.get("version"),
                    pub.get("originator_id") or "-",
                ]
            )
        _print_table(
            ["Contributing key", "Area", "Seq", "Published", "Version",
             "Originator"],
            rows,
        )
        trace = report.get("solve_trace")
        if trace:
            phases = trace.get("phases") or {}
            print(
                f"solve: seq={trace.get('seq')} event={trace.get('event')} "
                f"layout={trace.get('layout')} "
                f"ms={trace.get('solve_ms')}"
                + (
                    "  " + " ".join(
                        f"{k}={v:.2f}" for k, v in sorted(phases.items())
                    )
                    if phases
                    else ""
                )
            )
        if report.get("rib_policy_active"):
            print(
                "note: RibPolicy is active — journaled routes include "
                "policy edits the replay oracle does not model"
            )
    elif args.cmd == "rib-diff":
        report = client.call(
            "getRibDiff", from_ts=args.from_ts, to_ts=args.to_ts
        )
        if args.json:
            _print_json(report)
            return
        if not report.get("enabled"):
            print("state journal not enabled (journal_config.enabled)")
            return
        f, t = report.get("from", {}), report.get("to", {})
        print(
            f"rib-diff: {f.get('routes')} route(s) at seq {f.get('at_seq')}"
            f" -> {t.get('routes')} route(s) at seq {t.get('at_seq')}"
        )
        if not report.get("changed"):
            print("no route changes across the window")
            return
        delta = report.get("delta", {})
        for entry in delta.get("unicast_update", []):
            nexthops = ", ".join(
                f"{nh.get('address')}%{nh.get('iface') or '-'}"
                for nh in entry.get("nexthops", [])
            )
            print(f"+ {entry['prefix']} via [{nexthops}]")
        for prefix in delta.get("unicast_delete", []):
            print(f"- {prefix}")
        for entry in delta.get("mpls_update", []):
            print(f"+ label {entry['label']}")
        for label in delta.get("mpls_delete", []):
            print(f"- label {label}")
    elif args.cmd == "verify-replay":
        report = client.call("verifyJournalReplay", at=args.at)
        if args.json:
            _print_json(report)
            return
        if not report.get("enabled"):
            print("state journal not enabled (journal_config.enabled)")
            return
        verdict = "MATCH" if report.get("match") else "MISMATCH"
        print(
            f"replay audit: {verdict} — {report.get('routes')} journaled "
            f"route(s) vs {report.get('oracle_routes')} oracle route(s) "
            f"({report.get('applied')} record(s) replayed)"
        )
        for mm in report.get("mismatches", []):
            print(f"  {mm}")
    elif args.cmd == "path":
        # all shortest paths src -> dst over the live adjacency dump
        # (py/openr/cli/commands/decision.py PathCmd equivalent)
        dbs = client.call("getDecisionAdjacencyDbs")
        graph = {}  # node -> {neighbor: (metric, iface)}
        for node, blob in dbs.items():
            db = decode_obj(blob)
            for adj in db.adjacencies:
                if adj.is_overloaded:
                    continue
                cur = graph.setdefault(node, {}).get(adj.other_node_name)
                if cur is None or adj.metric < cur[0]:
                    graph[node][adj.other_node_name] = (
                        adj.metric, adj.if_name
                    )
        paths = _all_shortest_paths(graph, args.src, args.dst)
        if not paths:
            print(f"no path from {args.src} to {args.dst}")
            return
        for i, (cost, hops) in enumerate(paths):
            legs = " -> ".join(
                f"{a}[{graph[a][b][1]}]" for a, b in zip(hops, hops[1:])
            )
            print(f"path {i + 1}: cost {cost}: {legs} -> {args.dst}")


def _all_shortest_paths(graph, src, dst, limit=16):
    """Dijkstra from src, then enumerate up to `limit` equal-cost paths by
    walking the shortest-path DAG."""
    import heapq

    dist = {src: 0}
    pq = [(0, src)]
    while pq:
        d, u = heapq.heappop(pq)
        if d > dist.get(u, float("inf")):
            continue
        for v, (w, _) in graph.get(u, {}).items():
            nd = d + w
            if nd < dist.get(v, float("inf")):
                dist[v] = nd
                heapq.heappush(pq, (nd, v))
    if dst not in dist:
        return []
    paths = []

    def walk(node, acc):
        if len(paths) >= limit:
            return
        if node == dst:
            paths.append((dist[dst], acc))
            return
        for v, (w, _) in sorted(graph.get(node, {}).items()):
            if dist.get(v) == dist[node] + w and v not in set(acc):
                walk(v, acc + [v])

    walk(src, [src])
    return [(c, p) for c, p in paths]


def _check_artifact_schema(artifact: dict) -> None:
    """SOAK_r*/fleet artifacts are stamped with schema_version +
    build fingerprint (utils/build_info.py). An unknown version means the
    offline render below may misread fields — warn and render best-effort
    anyway; a missing stamp just gets a note (pre-stamp artifacts stay
    readable)."""
    from openr_tpu.utils.build_info import ARTIFACT_SCHEMA_VERSION

    version = artifact.get("schema_version")
    if version is None:
        print(
            "note: artifact has no schema_version stamp (written by a "
            f"pre-v{ARTIFACT_SCHEMA_VERSION} build); rendering best-effort"
        )
    elif version != ARTIFACT_SCHEMA_VERSION:
        print(
            f"warning: artifact schema_version {version} != supported "
            f"{ARTIFACT_SCHEMA_VERSION} "
            f"(build {artifact.get('build', 'unknown')}): fields may "
            "render incorrectly"
        )


def cmd_soak_report(args) -> None:
    """Render a judged soak report written by the topology-churn harness
    (python -m openr_tpu.testing.soak --out FILE). Offline: reads the
    JSON file, never dials a daemon."""
    with open(args.file) as fh:
        report = json.load(fh)
    _check_artifact_schema(report)
    if "verdict" not in report and isinstance(report.get("soak"), dict):
        report = report["soak"]  # a SOAK_r* artifact wraps the report
    verdict = report.get("verdict", {})
    checks = verdict.get("checks", {})
    state = "PASS" if verdict.get("pass") else "FAIL"
    print(f"soak verdict: {state} ({len(checks)} check(s))")
    for name, check in sorted(checks.items()):
        mark = "ok " if check.get("ok") else "FAIL"
        print(f"  [{mark}] {name}: {check.get('detail', '')}")
    events = report.get("events", {})
    print(
        f"events: {events.get('total', 0)} total = "
        f"{events.get('windowed', 0)} windowed + "
        f"{events.get('evicted_window_events', 0)} window-evicted; "
        f"LogSample rings retained {events.get('spans_in_rings', 0)}"
    )
    waves = report.get("waves", [])
    if waves:
        _print_table(
            ["Wave", "Added", "Removed", "Chaos", "Converged", "ms"],
            [
                [
                    w["index"],
                    ",".join(w["added"]) or "-",
                    ",".join(w["removed"]) or "-",
                    "yes" if w["faulted"] else "",
                    "yes" if w["converged"] else "NO",
                    w["converge_ms"],
                ]
                for w in waves
            ],
        )
    windows = report.get("windows", [])
    if windows:
        print("windowed convergence trend:")
        _print_table(
            ["Window", "Events", "Chaos", "p50 ms", "p95 ms", "max ms"],
            [
                [
                    int(w["start"]),
                    w["events"],
                    "yes" if w["faulted"] else "",
                    f"{w['e2e_p50_ms']:.2f}",
                    f"{w['e2e_p95_ms']:.2f}",
                    f"{w['e2e_max_ms']:.2f}",
                ]
                for w in windows
            ],
        )
    trend = report.get("trend")
    if trend:
        print(
            f"trend: p95 slope {trend['p95_slope_ms_per_window']:+.3f} "
            f"ms/window over {trend['windows']} window(s)"
        )
        step = trend.get("step")
        if step:
            stages = ", ".join(
                s["stage"] for s in trend.get("attributed_stages", [])
            )
            print(
                f"  step break at window {step['index']}: "
                f"{step['before_ms']} -> {step['after_ms']} ms "
                f"({'fault-attributed' if step['faulted'] else 'CLEAN'}"
                + (f"; stages: {stages}" if stages else "")
                + ")"
            )
    stream = report.get("stream")
    if stream and stream.get("enabled"):
        print(
            f"stream scrapes: {stream['frames_total']} frame(s), "
            f"{stream['resyncs_total']} resync(s) over "
            f"{len(stream.get('nodes', {}))} subscription(s)"
        )
    attribution = report.get("attribution")
    if attribution:
        clean = attribution["clean_e2e_ms"]
        faulted = attribution["faulted_e2e_ms"]
        print(
            f"attribution: clean {attribution['clean_windows']} window(s) "
            f"p95 {clean['p95']:.2f}ms vs chaos "
            f"{attribution['faulted_windows']} window(s) "
            f"p95 {faulted['p95']:.2f}ms"
        )
    if args.json:
        _print_json(report)


def cmd_fleet(client: BlockingCtrlClient, args) -> None:
    """Fleet observer surfaces (docs/Monitoring.md "Fleet observer & SLO
    watchdog"): `status` one-shot-scrapes the connected node plus
    --hosts peers and renders the health gauges the standing rules
    watch; `watch` attaches the live observer (scrape + stream + SLO
    watchdog) for --seconds and reports breaches."""
    from openr_tpu.monitor.exporter import parse_metrics_text, prom_name

    endpoints = [h for h in (args.hosts or "").split(",") if h]
    if args.cmd == "status":
        rows = []
        unhealthy = []

        def one(c: BlockingCtrlClient) -> None:
            node = c.call("getMyNodeName")
            parsed = parse_metrics_text(c.call("getMetricsText"))

            def sample(name: str, default=0.0) -> float:
                pname = prom_name(name)
                for view in ("counters", "gauges"):
                    if pname in parsed[view]:
                        return parsed[view][pname]
                return default

            window_p95 = 0.0
            for labels, value in parsed["samples"].get(
                "openr_convergence_window_e2e_ms", {}
            ).items():
                if 'q="p95"' in labels:
                    window_p95 = value
            fallback = int(sample("decision.spf.fallback_active"))
            stale = int(sample("fib.num_stale_routes"))
            flushes = int(sample("fib.stale_deadline_flushes"))
            resyncs = int(sample("ctrl.stream.resyncs"))
            rejected = int(
                sample("ctrl.admission.rejected_queue_full")
                + sample("ctrl.admission.rejected_client_cap")
                + sample("ctrl.admission.timeouts")
            )
            state = "OK"
            if fallback or flushes:
                state = "DEGRADED"
                unhealthy.append(node)
            rows.append(
                [
                    node,
                    state,
                    f"{window_p95:.1f}",
                    fallback,
                    stale,
                    resyncs,
                    rejected,
                    int(sample("process.uptime.seconds")),
                ]
            )

        one(client)
        for endpoint in endpoints:
            host, _, port = endpoint.rpartition(":")
            with BlockingCtrlClient(
                host or "127.0.0.1",
                int(port),
                ssl_context=client.ssl_context,
            ) as peer:
                one(peer)
        _print_table(
            ["Node", "State", "win p95 ms", "Fallback", "Stale",
             "Resyncs", "Rejected", "Uptime s"],
            rows,
        )
        print(
            f"fleet: {len(rows)} node(s), "
            f"{len(unhealthy)} degraded"
            + (f" ({', '.join(unhealthy)})" if unhealthy else "")
        )
        if args.json:
            _print_json({"nodes": rows, "degraded": unhealthy})
    elif args.cmd == "watch":
        from openr_tpu.fleet import FleetConfig, SloConfig, watch_hosts

        hosts = [f"{args.host}:{args.port}"] + endpoints
        report = watch_hosts(
            hosts,
            seconds=args.seconds,
            config=FleetConfig(
                scrape_interval_s=args.interval,
                forensics_dir=args.forensics_dir,
                slo=SloConfig(
                    convergence_p95_budget_ms=args.budget_ms
                ),
            ),
        )
        _render_fleet_report(report, json_too=args.json)


def _render_fleet_report(report: dict, json_too: bool = False) -> None:
    """Shared renderer for `breeze fleet watch` and the offline
    `breeze fleet report FILE` (which must round-trip with --json)."""
    verdict = report.get("verdict", {})
    checks = verdict.get("checks", {})
    state = "PASS" if verdict.get("pass") else "BREACH"
    print(
        f"fleet verdict: {state} ({len(report.get('nodes', []))} node(s), "
        f"{report.get('ticks', 0)} watchdog tick(s))"
    )
    for name, check in sorted(checks.items()):
        mark = "ok " if check.get("ok") else "FAIL"
        print(f"  [{mark}] {name}: {check.get('detail', '')}")
    findings = report.get("findings", [])
    if findings:
        _print_table(
            ["Rule", "Node", "Value", "Budget", "Stages", "Forensics"],
            [
                [
                    f["kind"],
                    f["node"],
                    f["value"],
                    f["budget"],
                    ",".join(
                        s["stage"] for s in f.get("attribution", [])
                    )
                    or "-",
                    f.get("forensics_id") or "-",
                ]
                for f in findings
            ],
        )
    store = report.get("store", {})
    acc = store.get("accounting", {})
    print(
        f"store: {acc.get('recorded', 0)} points = "
        f"{acc.get('retained', 0)} retained + "
        f"{acc.get('evicted', 0)} evicted over {acc.get('rings', 0)} "
        f"ring(s); {store.get('gaps_marked', 0)} gap(s) marked"
    )
    if json_too:
        _print_json(report)


def cmd_fleet_report(args) -> None:
    """Offline: render a fleet report JSON written by the observer
    (`python -m openr_tpu.fleet --out` / a SOAK_r* artifact's `fleet`
    section). Never dials a daemon; --json re-emits the full report
    (the round-trip the FLEET_SMOKE pins)."""
    with open(args.file) as fh:
        report = json.load(fh)
    _check_artifact_schema(report)
    if "findings" not in report:
        # also accept a soak report / SOAK_r* artifact: render the
        # embedded fleet section
        if isinstance(report.get("fleet"), dict):
            report = report["fleet"]
        elif isinstance(report.get("soak"), dict) and isinstance(
            report["soak"].get("fleet"), dict
        ):
            report = report["soak"]["fleet"]
    _render_fleet_report(report, json_too=args.json)


def cmd_perf(client: BlockingCtrlClient, args) -> None:
    if getattr(args, "cmd", None) == "report":
        _perf_report(client, args)
        return
    perf_db = client.call("getPerfDb")
    for blob in perf_db:
        perf = decode_obj(blob)  # PerfEvents; unix_ts already in ms
        print("PerfEvents:")
        base = None
        for ev in perf.events:
            if base is None:
                base = ev.unix_ts
            print(
                f"  {ev.event_descr:<40} {ev.node_name:<16} "
                f"+{ev.unix_ts - base}ms"
            )


def _perf_report(client: BlockingCtrlClient, args) -> None:
    """Network-wide convergence report: collect getConvergenceReport from
    every named node (--hosts host:port,... — or just the connected one)
    and render the aggregate (monitor/report.py)."""
    from openr_tpu.monitor.report import aggregate_convergence_reports
    from openr_tpu.monitor.spans import account_line

    reports = [client.call("getConvergenceReport")]
    for endpoint in [h for h in (args.hosts or "").split(",") if h]:
        host, _, port = endpoint.rpartition(":")
        with BlockingCtrlClient(
            host or "127.0.0.1", int(port), ssl_context=client.ssl_context
        ) as peer:
            reports.append(peer.call("getConvergenceReport"))
    agg = aggregate_convergence_reports(reports)

    def ms(value: float) -> str:
        return f"{value:.3f}"

    print(
        f"network-wide convergence: {agg['nodes']} node(s), "
        f"{agg['spans_total']} finished span(s)"
    )
    e2e = agg["e2e_ms"]
    _print_table(
        ["Metric", "Count", "p50", "p95", "Max"],
        [
            [
                "node-to-converge e2e_ms",
                e2e["count"],
                ms(e2e["p50"]),
                ms(e2e["p95"]),
                ms(e2e["max"]),
            ]
        ]
        + [
            [f"stage {stage}_ms", s["count"], ms(s["p50"]), ms(s["p95"]),
             ms(s["max"])]
            for stage, s in agg["stages"].items()
        ],
    )
    slowest = agg.get("slowest_stage")
    if slowest:
        print(
            f"slowest hop: {slowest['stage']} on {slowest['node']} "
            f"({ms(slowest['ms'])}ms)"
        )
    for sample in agg.get("slowest") or []:
        # the slowest events the nodes' rollup windows kept, each with
        # the account its Fib closed (monitor/spans.py account_line)
        print(f"slow event on {sample.get('node_name', '?')}: {account_line(sample)}")
    flood = agg["flood"]
    print(
        f"flood: {flood['received']} received, "
        f"{flood['duplicates']} redundant "
        f"(ratio {flood['duplicate_ratio']:.2f}), "
        f"max hop count {flood['hop_count_max']}, "
        f"per-hop p50/p95/max "
        f"{ms(flood['hop_ms']['p50'])}/{ms(flood['hop_ms']['p95'])}/"
        f"{ms(flood['hop_ms']['max'])}ms"
    )
    if args.json:
        _print_json(agg)


def cmd_config(client: BlockingCtrlClient, args) -> None:
    if args.cmd == "show":
        _print_json(client.call("getRunningConfig"))
    elif args.cmd == "dryrun":
        with open(args.file) as fh:
            text = fh.read()
        _print_json(client.call("dryrunConfig", file=text))
        print("config OK", file=sys.stderr)


def _dump_all_areas(client: BlockingCtrlClient):
    def dump():
        areas = client.call("getAreasConfig")["areas"]
        return {
            area: client.call(
                "getKvStoreKeyValsFiltered", area=area, prefixes=[]
            )
            for area in areas
        }

    return dump


def cmd_tech_support(client: BlockingCtrlClient, args) -> None:
    """One-shot dump of everything an operator needs for a bug report
    (py/openr/cli/clis/tech_support.py equivalent)."""
    sections = [
        ("version", lambda: VERSION),
        ("node", lambda: client.call("getMyNodeName")),
        ("config", lambda: client.call("getRunningConfig")),
        ("counters", lambda: client.call("getCounters")),
        ("interfaces", lambda: client.call("getInterfaces")),
        ("adjacencies", lambda: client.call("getLinkMonitorAdjacencies")),
        ("routes", lambda: client.call("getRouteDb")),
        ("kvstore-keys", _dump_all_areas(client)),
        ("event-logs", lambda: client.call("getEventLogs")),
    ]
    for title, fn in sections:
        print(f"\n==== {title} ====")
        try:
            _print_json(fn())
        except Exception as exc:  # a module may not be wired in
            print(f"<unavailable: {exc}>")


def cmd_fib(client: BlockingCtrlClient, args) -> None:
    if args.cmd in ("routes", "unicast-routes"):
        routes = client.call(
            "getUnicastRoutesFiltered", prefixes=args.prefixes or []
        )
        rows = []
        for blob in routes:
            route = decode_obj(blob)
            rows.append([str(route.dest), _fmt_nexthops(route)])
        _print_table(["Prefix", "Nexthops"], rows)
    elif args.cmd == "mpls-routes":
        routes = client.call("getMplsRoutesFiltered", labels=[])
        rows = []
        for blob in routes:
            route = decode_obj(blob)
            rows.append([route.top_label, _fmt_nexthops(route)])
        _print_table(["Label", "Nexthops"], rows)
    elif args.cmd == "counters":
        counters = client.call("getCounters")
        fib_counters = {
            k: v for k, v in sorted(counters.items()) if k.startswith("fib.")
        }
        _print_json(fib_counters)


def cmd_lm(client: BlockingCtrlClient, args) -> None:
    if args.cmd == "links":
        ifaces = client.call("getInterfaces")
        rows = [
            [
                name,
                "UP" if info["is_up"] else "DOWN",
                "active" if info["is_active"] else "dampened",
                ",".join(info["addresses"]) or "-",
            ]
            for name, info in sorted(ifaces.items())
        ]
        _print_table(["Interface", "Status", "Dampening", "Addresses"], rows)
    elif args.cmd == "set-node-overload":
        client.call("setNodeOverload")
        print("node overload: SET")
    elif args.cmd == "unset-node-overload":
        client.call("unsetNodeOverload")
        print("node overload: UNSET")
    elif args.cmd == "set-link-overload":
        client.call("setInterfaceOverload", interface=args.interface)
        print(f"link overload SET on {args.interface}")
    elif args.cmd == "unset-link-overload":
        client.call("unsetInterfaceOverload", interface=args.interface)
        print(f"link overload UNSET on {args.interface}")
    elif args.cmd == "set-link-metric":
        client.call(
            "setInterfaceMetric",
            interface=args.interface,
            metric=args.metric,
        )
        print(f"metric {args.metric} SET on {args.interface}")
    elif args.cmd == "unset-link-metric":
        client.call("unsetInterfaceMetric", interface=args.interface)
        print(f"metric override UNSET on {args.interface}")


def cmd_prefixmgr(client: BlockingCtrlClient, args) -> None:
    from openr_tpu.types import IpPrefix, PrefixEntry, PrefixType

    if args.cmd == "view":
        entries = [decode_obj(b) for b in client.call("getPrefixes")]
        _print_table(
            ["Prefix", "Type", "Forwarding"],
            [
                [str(e.prefix), e.type.value, e.forwarding_type.name]
                for e in entries
            ],
        )
    elif args.cmd == "advertise":
        entries = [
            PrefixEntry(
                prefix=IpPrefix(p), type=PrefixType(args.prefix_type)
            )
            for p in args.prefixes
        ]
        client.call(
            "advertisePrefixes",
            prefixes=[encode_obj(e) for e in entries],
        )
        print(f"advertised {len(entries)} prefixes")
    elif args.cmd == "withdraw":
        entries = [
            PrefixEntry(
                prefix=IpPrefix(p), type=PrefixType(args.prefix_type)
            )
            for p in args.prefixes
        ]
        client.call(
            "withdrawPrefixes",
            prefixes=[encode_obj(e) for e in entries],
        )
        print(f"withdrew {len(entries)} prefixes")
    elif args.cmd == "sync":
        entries = [
            PrefixEntry(
                prefix=IpPrefix(p), type=PrefixType(args.prefix_type)
            )
            for p in args.prefixes
        ]
        client.call(
            "syncPrefixesByType",
            type=args.prefix_type,
            prefixes=[encode_obj(e) for e in entries],
        )
        print(f"synced {len(entries)} prefixes of type {args.prefix_type}")


def cmd_monitor(client: BlockingCtrlClient, args) -> None:
    if args.cmd == "counters":
        _print_json(client.call("getCounters"))
    elif args.cmd == "histograms":
        # --reset: reset-on-read windowing — this export clears the
        # sources, so the next call describes a fresh window (rates)
        hists = client.call("getHistograms", reset=bool(args.reset))

        def ms(v: float) -> str:
            return f"{v:.3f}"

        rows = [
            [
                name,
                h["count"],
                ms(h["avg"]),
                ms(h["p50"]),
                ms(h["p95"]),
                ms(h["p99"]),
                ms(h["max"]),
            ]
            for name, h in sorted(hists.items())
        ]
        _print_table(
            ["Histogram", "Count", "Avg", "p50", "p95", "p99", "Max"], rows
        )
    elif args.cmd == "logs":
        for log_json in client.call("getEventLogs"):
            print(log_json)
    elif args.cmd == "scrape":
        # the full registry in Prometheus text exposition format — the
        # same bytes GET /metrics on the ctrl port serves (the scrape
        # endpoint a stock Prometheus instance polls)
        sys.stdout.write(client.call("getMetricsText"))
    elif args.cmd == "stream-stats":
        # live fan-out + admission state (docs/Streaming.md)
        _print_json(client.call("getStreamStats"))


def cmd_openr(client: BlockingCtrlClient, args) -> None:
    if args.cmd == "version":
        print(VERSION)
        print("node:", client.call("getMyNodeName"))
        build_info = client.call("getBuildInfo")
        for k, v in sorted(build_info.items()):
            print(f"{k}: {v}")
        if "build_analysis_version" not in build_info:
            # older daemon: report the CLI side's own lint contract
            from openr_tpu.utils.build_info import get_analysis_build_info

            for k, v in sorted(get_analysis_build_info().items()):
                print(f"{k} (local): {v}")
    elif args.cmd == "config":
        _print_json(client.call("getRunningConfig"))


# ---------------------------------------------------------------------------
# parser
# ---------------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="breeze", description="openr-tpu operator CLI"
    )
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=2018)
    # mutual TLS against a secured daemon (--enable_secure_thrift_server)
    parser.add_argument("--x509_ca_path", default=None)
    parser.add_argument("--x509_cert_path", default=None)
    parser.add_argument("--x509_key_path", default=None)
    sub = parser.add_subparsers(dest="module", required=True)

    kv = sub.add_parser("kvstore").add_subparsers(dest="cmd", required=True)
    p = kv.add_parser("keys")
    p.add_argument("--prefix", default="")
    p.add_argument("--area", default="0")
    p = kv.add_parser("keyvals")
    p.add_argument("keys", nargs="+")
    p.add_argument("--area", default="0")
    p = kv.add_parser("peers")
    p.add_argument("--area", default="0")
    p = kv.add_parser("peer-health")
    p.add_argument("--area", default="0")
    kv.add_parser("areas")
    p = kv.add_parser("snoop")
    p.add_argument("--prefix", default="")
    p.add_argument("--area", default="0")
    p = kv.add_parser("history")
    p.add_argument("key", help="exact key, e.g. adj:r1")
    p.add_argument(
        "--area", default=None, help="area filter (all areas when omitted)"
    )
    p.add_argument("--json", action="store_true")
    p = kv.add_parser("subscribe")
    p.add_argument("--prefix", default="")
    p.add_argument(
        "--originator",
        action="append",
        default=None,
        help="originator-id filter (repeatable)",
    )
    p.add_argument("--area", default="0")
    p.add_argument(
        "--client",
        default="breeze",
        help="client label (admission fairness / stream stats)",
    )
    p.add_argument(
        "--codec",
        default="json",
        choices=["json", "binary"],
        help="stream frame codec; binary negotiates length-prefixed "
        "frames, falling back to JSON on old servers",
    )

    dec = sub.add_parser("decision").add_subparsers(dest="cmd", required=True)
    dec.add_parser("adj")
    dec.add_parser("prefixes")
    p = dec.add_parser("routes")
    p.add_argument("--node", default=None)
    dec.add_parser("rib-policy")
    dec.add_parser("solver-health")
    p = dec.add_parser("memory")
    p.add_argument("--area", default=None)
    p.add_argument(
        "--json", action="store_true", help="dump the raw ledger snapshot"
    )
    p = dec.add_parser("solve-traces")
    p.add_argument("--area", default=None)
    p.add_argument(
        "--last", type=int, default=None, help="most recent N traces"
    )
    p.add_argument(
        "--json", action="store_true", help="dump raw trace records"
    )
    p = dec.add_parser("profile")
    p.add_argument(
        "--seconds", type=float, default=5.0,
        help="profiling window duration (clamped to [0.1, 600])",
    )
    p.add_argument(
        "--out", default=None,
        help="TensorBoard trace directory (temp dir when omitted)",
    )
    p.add_argument("--json", action="store_true")
    dec.add_parser("profile-status")
    p = dec.add_parser("te-optimize")
    p.add_argument(
        "--demands",
        default=None,
        help="JSON demand spec file (docs/TrafficEngineering.md format)",
    )
    p.add_argument("--steps", type=int, default=None)
    p.add_argument("--scenarios", type=int, default=None)
    p.add_argument(
        "--json", action="store_true", help="dump the full report"
    )
    p = dec.add_parser("subscribe-routes")
    p.add_argument(
        "--client",
        default="breeze",
        help="client label (admission fairness / stream stats)",
    )
    p.add_argument(
        "--codec",
        default="json",
        choices=["json", "binary"],
        help="stream frame codec; binary negotiates length-prefixed "
        "frames, falling back to JSON on old servers",
    )
    p = dec.add_parser("explain-route")
    p.add_argument("prefix", help="route prefix, e.g. 10.0.0.0/24")
    p.add_argument(
        "--at", type=float, default=None,
        help="replay instant: unix seconds, negative = seconds before "
        "now (default: latest journaled state)",
    )
    p.add_argument("--json", action="store_true")
    p = dec.add_parser("rib-diff")
    p.add_argument(
        "--from", dest="from_ts", type=float, default=None,
        help="window start (unix seconds; negative = relative to now)",
    )
    p.add_argument(
        "--to", dest="to_ts", type=float, default=None,
        help="window end (same axis; default: latest)",
    )
    p.add_argument("--json", action="store_true")
    p = dec.add_parser("verify-replay")
    p.add_argument("--at", type=float, default=None)
    p.add_argument("--json", action="store_true")
    p = dec.add_parser("path")
    p.add_argument("src")
    p.add_argument("dst")

    fib = sub.add_parser("fib").add_subparsers(dest="cmd", required=True)
    p = fib.add_parser("routes")
    p.add_argument("prefixes", nargs="*")
    p = fib.add_parser("unicast-routes")
    p.add_argument("prefixes", nargs="*")
    fib.add_parser("mpls-routes")
    fib.add_parser("counters")

    lm = sub.add_parser("lm").add_subparsers(dest="cmd", required=True)
    lm.add_parser("links")
    lm.add_parser("set-node-overload")
    lm.add_parser("unset-node-overload")
    for name in ("set-link-overload", "unset-link-overload",
                 "unset-link-metric"):
        p = lm.add_parser(name)
        p.add_argument("interface")
    p = lm.add_parser("set-link-metric")
    p.add_argument("interface")
    p.add_argument("metric", type=int)

    pm = sub.add_parser("prefixmgr").add_subparsers(dest="cmd", required=True)
    pm.add_parser("view")
    for name in ("advertise", "withdraw", "sync"):
        p = pm.add_parser(name)
        p.add_argument("prefixes", nargs="+")
        p.add_argument("--prefix-type", default="BREEZE")

    mon = sub.add_parser("monitor").add_subparsers(dest="cmd", required=True)
    mon.add_parser("counters")
    p = mon.add_parser("histograms")
    p.add_argument("--reset", action="store_true")
    mon.add_parser("logs")
    mon.add_parser("scrape")
    mon.add_parser("stream-stats")

    op = sub.add_parser("openr").add_subparsers(dest="cmd", required=True)
    op.add_parser("version")
    op.add_parser("config")

    perf = sub.add_parser("perf").add_subparsers(dest="cmd", required=True)
    perf.add_parser("view")
    p = perf.add_parser("report")
    p.add_argument(
        "--hosts",
        default="",
        help="additional host:port ctrl endpoints to fold into the "
        "network-wide report (comma-separated)",
    )
    p.add_argument(
        "--json", action="store_true", help="dump the full aggregate too"
    )
    p = perf.add_parser("soak-report")
    p.add_argument("file", help="JSON soak report (testing/soak.py --out)")
    p.add_argument(
        "--json", action="store_true", help="dump the full report too"
    )

    fleet = sub.add_parser("fleet").add_subparsers(dest="cmd", required=True)
    p = fleet.add_parser("status")
    p.add_argument(
        "--hosts",
        default="",
        help="additional host:port ctrl endpoints (comma-separated)",
    )
    p.add_argument("--json", action="store_true")
    p = fleet.add_parser("watch")
    p.add_argument(
        "--hosts",
        default="",
        help="additional host:port ctrl endpoints (comma-separated)",
    )
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--interval", type=float, default=1.0)
    p.add_argument(
        "--budget-ms",
        type=float,
        default=1000.0,
        help="convergence e2e p95 SLO budget",
    )
    p.add_argument(
        "--forensics-dir", default=None, help="write breach dumps here"
    )
    p.add_argument("--json", action="store_true")
    p = fleet.add_parser("report")
    p.add_argument(
        "file", help="fleet report JSON (python -m openr_tpu.fleet --out)"
    )
    p.add_argument(
        "--json", action="store_true", help="re-emit the full report"
    )

    cfg = sub.add_parser("config").add_subparsers(dest="cmd", required=True)
    cfg.add_parser("show")
    p = cfg.add_parser("dryrun")
    p.add_argument("file")

    sub.add_parser("tech-support")

    return parser


_HANDLERS = {
    "kvstore": cmd_kvstore,
    "decision": cmd_decision,
    "fib": cmd_fib,
    "lm": cmd_lm,
    "prefixmgr": cmd_prefixmgr,
    "monitor": cmd_monitor,
    "openr": cmd_openr,
    "perf": cmd_perf,
    "fleet": cmd_fleet,
    "config": cmd_config,
    "tech-support": cmd_tech_support,
}


def main(argv=None) -> int:
    from openr_tpu.ctrl.client import CtrlError

    args = build_parser().parse_args(argv)
    if args.module == "perf" and getattr(args, "cmd", None) == "soak-report":
        # offline renderer: reads a report file, never dials a daemon
        cmd_soak_report(args)
        return 0
    if args.module == "fleet" and getattr(args, "cmd", None) == "report":
        # offline renderer: reads a fleet report file, never dials a daemon
        cmd_fleet_report(args)
        return 0
    ssl_ctx = None
    if args.x509_ca_path:
        from openr_tpu.utils.tls import client_ssl_context

        ssl_ctx = client_ssl_context(
            args.x509_ca_path, args.x509_cert_path, args.x509_key_path
        )
    try:
        with BlockingCtrlClient(
            args.host, args.port, ssl_context=ssl_ctx
        ) as client:
            _HANDLERS[args.module](client, args)
        return 0
    except CtrlError as exc:
        if exc.server_busy:
            # typed admission rejection: the daemon is shedding load, not
            # broken — report the backoff hint and exit distinctly
            retry = exc.retry_after_ms or 0
            print(
                f"server busy: {exc} (retry in ~{retry}ms)",
                file=sys.stderr,
            )
            return 2
        raise
    except ConnectionRefusedError:
        print(
            f"cannot connect to openr-tpu at {args.host}:{args.port}",
            file=sys.stderr,
        )
        return 1
    except BrokenPipeError:
        # distinguish a closed stdout (pager/head quit: quiet success) from
        # a broken daemon socket (real RPC failure: report it)
        try:
            sys.stdout.flush()
        except (BrokenPipeError, ValueError):
            try:
                sys.stdout.close()
            except Exception:
                pass
            return 0
        print(
            f"connection to openr-tpu at {args.host}:{args.port} broke",
            file=sys.stderr,
        )
        return 1


if __name__ == "__main__":
    sys.exit(main())
