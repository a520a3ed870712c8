"""chipbench: one cell of BENCHMARK.json, one run, one JSON line.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which also holds the chip; the only children are `make`
building native/. It refuses to measure unless JAX's first device is a
TPU and there are as many as the cell asks for (`--allow-cpu` is for the
rehearsal and the tests: the printed device then says `cpu`, and nothing
it prints is a device number).

  set-up   (process start -> window start, `setup_s`) compile cache, native
           build, the LSDB from the configuration and --seed, one
           OpenrDaemon with the benchmark's platform agent, every key
           through ctrl setKvStoreKeyVals, the first full table at the
           agent, `warmup_events` events of the cell's own mix.
  window   (--seconds) closed loop, one event outstanding: t0 just before
           the ctrl write, the event's latency is the agent's last stamp
           for it minus t0. Nothing else runs between events.
  after    histograms and counters; the daemon stops; the plain reference
           that the configuration names replays the events from the seed
           and compare.py decides `correct`; the last line of standard
           output is the result.

What belongs to one configuration, one traffic mix, one cell or one
per-layer metric is a file found by its name in BENCHMARK.json:
configs/<config>.json, traffic/<traffic>.json, cells/<cell>.json,
metrics/<metric>.json (see README.md).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, f".chipbench_trace.{os.getpid()}")
TRACE_SECONDS = 1.0  # of the window, in a --trace 1 run
LOAD_WRITE_BYTES = 24 << 20  # of encoded values in one ctrl write of the load

# `event_to_fib_ms.p<NN>` of BENCHMARK.json: that percentile over ALL events
# completed in the window
E2E_PERCENTILE = re.compile(r"^event_to_fib_ms\.p(\d{1,2})$")

# moved inside the window = the event was not served by the device path
OFF_DEVICE_COUNTERS = (
    "decision.spf.fallback_solves",
    "decision.spf.breaker_trips",
    "decision.spf.solver_retries",
    "decision.route_build_errors",
    "decision.route_build_delta_errors",
)
OFF_DEVICE_PREFIX = "decision.spf.solver_failures"


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:7.2f}s] {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


def resolve_cell(name: str) -> dict:
    """The cell's entry of BENCHMARK.json with its files' contents."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    rehearsal = cell is None
    if rehearsal:
        # a configuration.traffic pair that no cell of BENCHMARK.json uses
        # (the rehearsal's toy): its files alone describe it
        config, _, traffic = name.partition(".")
        cell = {"name": name, "config": config, "traffic": traffic, "chips": 1}
    params = load_json("traffic", f"{cell['traffic']}.json")
    cell_file = os.path.join(HERE, "cells", f"{cell['name']}.json")
    if os.path.exists(cell_file):
        params.update(load_json("cells", f"{cell['name']}.json"))

    def reports(metric: dict) -> bool:
        return rehearsal or name in metric.get("workloads", [name])

    return {
        **cell,
        "config_data": load_json("configs", f"{cell['config']}.json"),
        "params": params,
        "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
        # each per-layer metric with its own file's contents under "spec"
        "per_layer": [
            dict(m, spec=load_json("metrics", f"{m['name']}.json"))
            for m in bench["per_layer"]
            if reports(m)
        ],
    }


def device_info(chips: int, allow_cpu: bool) -> dict:
    import jax

    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if allow_cpu:
        return info
    if info["platform"] != "tpu" or info["count"] < chips:
        raise SystemExit(
            f"chipbench: refusing to measure: JAX found {info}, the cell "
            f"needs {chips} TPU chip(s)"
        )
    return info


def memory_peak_bytes() -> int:
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()
    ]
    return int(max(peaks))


def percentile(sorted_values: List[float], share: float) -> float:
    """Linear interpolation between closest ranks, over all the sample."""
    if len(sorted_values) == 1:
        return sorted_values[0]
    pos = share * (len(sorted_values) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_values) - 1)
    return sorted_values[lo] + (sorted_values[hi] - sorted_values[lo]) * (pos - lo)


class Window:
    """What the window's loop leaves behind."""

    def __init__(self) -> None:
        self.latencies_s: List[float] = []
        self.slices: List[Tuple[int, int]] = []  # agent.log ranges per event
        self.updates: List[int] = []  # route updates published per event
        self.sent: List[object] = []  # the events, for the slowest's names
        self.off_delta: List[int] = []  # events that left DeltaPath
        self.attempted = 0
        self.timed_out = 0
        self.length_s = 0.0
        self.gauges: Dict[str, List[float]] = {}
        self.trace_window_s = 0.0


async def run_cell(cell: dict, seed: int, seconds: float, trace: bool) -> dict:
    import jax

    from chipbench import compare, layer_metrics
    from chipbench.agent import StampingAgent
    from chipbench.lsdb import AREA, Lsdb, WireEncoder
    from chipbench.topologies import build_edges
    from openr_tpu.config import Config
    from openr_tpu.ctrl.client import CtrlClient
    from openr_tpu.kvstore.transport import InProcessTransport
    from openr_tpu.openr import OpenrDaemon
    from openr_tpu.platform import FIB_CLIENT_OPENR
    from openr_tpu.spark.io_provider import MockIoNetwork
    from openr_tpu.utils.compile_cache import (
        ensure_compile_cache,
        persistent_cache_counts,
    )
    from openr_tpu.utils.native_build import build_native

    config, params = cell["config_data"], cell["params"]
    kind = importlib.import_module(f"chipbench.traffic_kinds.{params['kind']}")
    me = config["vantage"]

    # -- set-up -----------------------------------------------------------
    cache_dir = ensure_compile_cache()
    # every program, however quick to compile, is kept: the second run of a
    # cell in a checkout then compiles nothing, and set-up repeats
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for lib in ("libopenr_spf.so", "libopenr_kv.so"):
        build_native(lib)
    say(f"compile cache {cache_dir}; native/ built")

    lsdb = Lsdb(build_edges(config["topology"]))  # the daemon's side
    wire = WireEncoder(lsdb, config.get("prefix_forwarding"))
    say(f"LSDB {len(lsdb.nodes)} nodes, {lsdb.n_links} links, vantage {me}")
    agent = StampingAgent()
    daemon_config = dict(config["daemon"], node_name=me)
    daemon = OpenrDaemon(
        Config.from_dict(daemon_config),
        io_provider=MockIoNetwork().provider(me),
        kv_transport=InProcessTransport(),
        fib_service=agent,
        ctrl_port=0,
    )
    port = await daemon.start()
    dcount, fcount = daemon.decision.counters, daemon.fib.counters
    timeout_s = float(params["event_timeout_s"])
    win = Window()
    gauge_names = layer_metrics.gauges_wanted(cell["per_layer"]) if trace else []

    def count(counters: dict, name: str) -> int:
        return counters.get(name, 0)

    async def settled(done, deadline: float) -> bool:
        """Waits until `done()` holds. How completion is noticed does not
        enter a latency: that ends at the agent's own stamp."""
        while not done():
            if time.monotonic() > deadline:
                return False
            agent.programmed.clear()
            try:
                await asyncio.wait_for(agent.programmed.wait(), 0.002)
            except asyncio.TimeoutError:
                pass
        return True

    async def send(keys: List[str], timed: bool) -> Optional[float]:
        """The keys of one event through the ctrl socket, awaited until
        Decision has built routes for them and Fib has made its last
        programming call for what Decision published: Fib bumps
        `fib.num_of_route_updates` after that call, where it bumps
        `fib.process_route_db` before the first. Returns the event's
        latency, or None where it timed out."""
        payload = wire.key_vals(keys)
        runs0 = count(dcount, "decision.route_build_runs")
        pub0 = count(dcount, "decision.route_updates_published")
        upd0 = count(fcount, "fib.num_of_route_updates")
        log0 = len(agent.log)

        def done() -> bool:
            published = count(dcount, "decision.route_updates_published")
            return (
                count(dcount, "decision.route_build_runs") > runs0
                and count(fcount, "fib.process_route_db") == published
                and (
                    published == pub0
                    or count(fcount, "fib.num_of_route_updates") > upd0
                )
            )

        deadline = time.monotonic() + timeout_s
        t0 = time.perf_counter()
        await client.call("setKvStoreKeyVals", area=AREA, key_vals=payload)
        if not await settled(done, deadline):
            return None
        t_seen = time.perf_counter()
        if timed:
            win.slices.append((log0, len(agent.log)))
            win.updates.append(
                count(dcount, "decision.route_updates_published") - pub0
            )
        return (agent.log[-1][0] if len(agent.log) > log0 else t_seen) - t0

    def first_table() -> bool:
        # Fib's first full sync is scheduled, not immediate, and may come
        # before Decision's first routes: the load is done when the agent
        # holds a route to every other node and Fib has taken every update
        # that Decision published
        routes = agent.unicast_routes.get(FIB_CLIENT_OPENR, {})
        return len(routes) >= int(config["nodes"]) - 1 and count(
            fcount, "fib.process_route_db"
        ) == count(dcount, "decision.route_updates_published")

    def load_writes() -> List[Dict[str, dict]]:
        """The whole LSDB as ctrl writes under the ctrl socket's line limit
        (64 MiB), the vantage's own adjacencies in the last: until they
        come Decision has no node to build routes from, so no solve ever
        sees a part of the LSDB and set-up does the same work every time."""
        own = f"adj:{me}"
        writes: List[Dict[str, dict]] = [{}]
        size = 0
        for key in [k for k in wire.all_keys() if k != own] + [own]:
            key_val = wire.key_vals([key])
            if size > LOAD_WRITE_BYTES:
                writes.append({})
                size = 0
            writes[-1].update(key_val)
            size += len(key_val[key]["value"]) + len(key) + 64
        return writes

    async with CtrlClient(port=port) as client:
        t0 = time.perf_counter()
        # the load: ingest, first compile, cold solve, full route build
        for write in load_writes():
            await client.call("setKvStoreKeyVals", area=AREA, key_vals=write)
        if not await settled(first_table, time.monotonic() + 900.0):
            raise SystemExit("chipbench: no first table at the agent")
        say(
            f"LSDB loaded and first full table at the agent in "
            f"{time.perf_counter() - t0:.1f}s "
            f"({len(agent.unicast_routes.get(FIB_CLIENT_OPENR, {}))} routes)"
        )
        events = kind.generate(params, seed)
        t0 = time.perf_counter()
        for _ in range(int(params["warmup_events"])):
            if await send(next(events).apply(lsdb), timed=False) is None:
                raise SystemExit("chipbench: a warm-up event timed out")
        say(
            f"{params['warmup_events']} warm-up events in "
            f"{time.perf_counter() - t0:.1f}s"
        )
        await client.call("getHistograms", reset=True)
        counters0 = await client.call("getCounters")
        cache0 = persistent_cache_counts()
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        tracing = trace

        # -- the window ---------------------------------------------------
        setup_s = time.perf_counter() - T_START
        t_window = time.perf_counter()
        while time.perf_counter() - t_window < seconds:
            win.attempted += 1
            span = (
                jax.profiler.TraceAnnotation("chipbench.event")
                if tracing else contextlib.nullcontext()
            )
            event = next(events)
            delta0 = count(dcount, "decision.route_build_delta_runs")
            with span:
                latency = await send(event.apply(lsdb), timed=True)
            if latency is None:
                win.timed_out += 1
                say(f"event {win.attempted} not programmed in {timeout_s:.0f}s")
                break
            win.latencies_s.append(latency)
            win.sent.append(event)
            if count(dcount, "decision.route_build_delta_runs") == delta0:
                win.off_delta.append(len(win.sent) - 1)
            for name in gauge_names:
                if name in dcount:
                    win.gauges.setdefault(name, []).append(dcount[name])
            if tracing and time.perf_counter() - t_window >= TRACE_SECONDS:
                # the profiler takes many seconds to hand over what it
                # collected; no event is outstanding, and the stall is
                # taken out of the window's clock
                t_stop = time.perf_counter()
                win.trace_window_s = t_stop - t_window
                jax.profiler.stop_trace()
                tracing = False
                stall_s = time.perf_counter() - t_stop
                t_window += stall_s
                say(f"traced {win.trace_window_s:.2f}s; stop_trace took {stall_s:.1f}s")
        win.length_s = time.perf_counter() - t_window
        if tracing:
            win.trace_window_s = win.length_s
            jax.profiler.stop_trace()

        # -- after the window ---------------------------------------------
        await asyncio.sleep(0.05)
        hists = await client.call("getHistograms")
        counters1 = await client.call("getCounters")
        cache1 = persistent_cache_counts()
    final = (
        compare.routes_as_table(
            agent.unicast_routes.get(FIB_CLIENT_OPENR, {}).values()
        ),
        compare.mpls_routes_as_table(
            agent.mpls_routes.get(FIB_CLIENT_OPENR, {}).values()
        ),
    )
    agent_events = [
        [(name, payload) for _, name, payload in agent.log[a:b]]
        for a, b in win.slices
    ]
    device = {"memory_peak_bytes": memory_peak_bytes()}
    await daemon.stop()
    say(
        f"window {win.length_s:.2f}s: {len(win.latencies_s)} events, "
        f"{win.timed_out} timed out; persistent cache requests "
        f"{cache1['requests'] - cache0['requests']} in the window "
        f"({cache1['requests']} this process, {cache1['hits']} hits, "
        f"{cache1['misses']} written)"
    )

    counter_moves = {
        name: counters1.get(name, 0) - counters0.get(name, 0)
        for name in set(counters0) | set(counters1)
        if name in OFF_DEVICE_COUNTERS or name.startswith(OFF_DEVICE_PREFIX)
    }
    builds = counters1.get("decision.route_build_runs", 0) - counters0.get(
        "decision.route_build_runs", 0
    )
    if builds != win.attempted:
        say(f"note: {builds} route builds for {win.attempted} events")

    # -- the comparison that decides `correct` ----------------------------
    t0 = time.perf_counter()
    n_warm, n_events = int(params["warmup_events"]), len(agent_events)
    verify = compare.choose_events(n_events, int(params["verify_events"]), seed)
    at_index = compare.replay_reference(
        config, params, seed, n_warm, n_events, verify
    )
    correct, compared, notes = compare.compare(
        final=final,
        agent_events=agent_events,
        tables=at_index.__getitem__,
        verify=verify,
        updates_per_event=win.updates,
        counter_moves=counter_moves,
        segment_routing=compare.segment_routing(config),
    )
    failed = win.timed_out + compared["served_off_device"]["value"]
    correct = correct and win.timed_out == 0 and n_events > 0
    say(
        f"reference: {len(at_index)} tables of {len(final[0])} routes and "
        f"{len(final[1])} label routes, "
        f"{len(verify)} of {n_events} events verified, "
        f"{time.perf_counter() - t0:.1f}s"
    )

    # -- metrics ----------------------------------------------------------
    metrics: Dict[str, dict] = {}
    breakdown = None
    if not trace:
        lat_ms = sorted(x * 1e3 for x in win.latencies_s)
        values = {"setup_s": setup_s}
        if lat_ms:
            for m in cell["end_to_end"]:
                named = E2E_PERCENTILE.match(m["name"])
                if named:
                    values[m["name"]] = percentile(lat_ms, int(named.group(1)) / 100)
            values["events_per_s"] = len(lat_ms) / win.length_s
        for m in cell["end_to_end"]:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        from chipbench import trace_reduce

        summary = trace_reduce.read_trace(TRACE_DIR, win.trace_window_s)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = summary.breakdown()
        ctx = layer_metrics.Context(
            hists=hists,
            counters0=counters0,
            counters1=counters1,
            n_events=len(win.latencies_s),
            gauges=win.gauges,
            trace=summary,
            config=config,
            device_kind=jax.devices()[0].device_kind,
        )
        for m in cell["per_layer"]:
            value, note = layer_metrics.read(m["spec"], ctx)
            if note:
                say(f"{m['name']}: {note}")
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    say(f"sample: {len(win.latencies_s)} event latencies in the window")
    slowest = sorted(range(len(win.sent)), key=lambda i: -win.latencies_s[i])[:3]
    for i in sorted(set(slowest) | set(win.off_delta[:5])):
        say(
            f"event {i}: {win.latencies_s[i] * 1e3:.1f} ms, "
            f"{'full route build' if i in win.off_delta else 'DeltaPath'}: "
            f"{win.sent[i]}"
        )

    for note in notes:
        say(f"compare: {note}")
    for name, pair in compared.items():
        print(
            f"compared {name} = {pair['value']} (limit {pair['limit']})",
            file=sys.stderr, flush=True,
        )
    result = {
        "correct": bool(correct),
        "attempted": win.attempted,
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--allow-cpu", action="store_true",
        help="rehearsal only: run on whatever JAX finds; NOT a chip run",
    )
    args = parser.parse_args(argv)
    cell = resolve_cell(args.workload)
    info = device_info(int(cell["chips"]), args.allow_cpu)
    if info["platform"] != "tpu":
        say("CPU REHEARSAL: nothing printed below is a device number")
    result = asyncio.run(
        run_cell(cell, args.seed, args.seconds, bool(args.trace))
    )
    result["device"] = {**info, **result["device"]}  # keeps its place
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
