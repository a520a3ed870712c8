"""Topology-churn soak harness (the FastReChain-style scenario).

Reconfigurable fabrics (OCS-based, https://arxiv.org/pdf/2507.12265)
don't fail one link at a time — they retune in *waves*: bulk link
add/remove batches land together, repeatedly, for hours, while ordinary
faults keep firing underneath. `run_soak` drives a long-running
`VirtualNetwork` through exactly that:

  - a **base line topology** n0–n1–…–n(k-1) that is never touched (the
    graph stays connected, so convergence is always well-defined), plus
    a pool of **chord links** (i, i+2) standing in for the optical
    circuit inventory;
  - scheduled **reconfiguration waves**: each wave removes a batch of
    currently-up chords and adds a batch of currently-down ones (the
    OCS bulk add/remove), then waits for the adjacency view and routes
    to settle;
  - a **chaos overlay**: on designated waves, `testing/faults.py`
    schedules fire at the production fault seams (fib.program,
    kvstore.flood_send, spark.packet_send, ...) while the wave is in
    flight, and the harness records the wall-clock fault intervals for
    window attribution;
  - a **scrape loop**: after every wave each node's exporter renders the
    Prometheus exposition; the harness parses it back, times the render,
    and checks counter monotonicity + registry coverage — the continuous
    telemetry path exercised end to end, not just at shutdown;
  - a **judged report**: per-window convergence trend (p50/p95/max from
    the eviction-proof rollup), fault-vs-clean attribution, and a
    verdict block whose checks include the no-eviction-loss invariant
    (rollup events == spans Fib ever closed, even though the LogSample
    rings only hold the tail) and a monotonic-regression test over the
    windowed p95 series.

`run_soak_smoke` is the SOAK_SMOKE tier-1 mode (seconds, not hours):
a 3-node line, one wave, one injected fault, a deliberately tiny
`max_event_log` so ring eviction provably happens — asserting the whole
verdict machinery runs end to end. `python -m openr_tpu.testing.soak`
runs a configurable soak and writes the JSON report
(`breeze perf soak-report` renders it).
"""

from __future__ import annotations

import asyncio
import json
import os
import random
import time
from dataclasses import asdict, dataclass
from typing import Any, Dict, List, Optional, Tuple

from openr_tpu.monitor.exporter import (
    CounterEpochTracker,
    parse_metrics_text,
    prom_name,
)
from openr_tpu.monitor.report import (
    ConvergenceRollup,
    merge_rollup_snapshots,
    percentile_summary,
)
from openr_tpu.testing.faults import FaultInjector, injected
from openr_tpu.utils.counters import Histogram


@dataclass
class SoakConfig:
    nodes: int = 6
    waves: int = 4
    wave_links: int = 1  # chords added + chords removed per wave
    settle_s: float = 1.0  # dwell after each wave before scraping
    converge_timeout_s: float = 60.0
    # chaos overlay: every fault_every-th wave runs with armed schedules
    # (0 disables); fault_budget bounds firings per chaos wave
    fault_every: int = 2
    fault_budget: int = 2
    fault_probability: float = 0.5
    # restart waves: every restart_every-th wave additionally restarts a
    # random interior node through VirtualNetwork.restart_node (graceful
    # restart + warm boot — the whole-node churn class; 0 disables).
    # Nodes get per-run configstore files and GR enabled when armed.
    restart_every: int = 0
    # partition waves: every partition_every-th wave asymmetrically
    # blackholes one direction of a random line edge through the chaos
    # mesh (testing/chaos.py) for partition_hold_s, then heals — the
    # verdict gains `partitions_recovered` (convergence returns after
    # heal) and `flood_health_attributed` (no fleet flood_health breach
    # outside a fault/partition interval); 0 disables
    partition_every: int = 0
    partition_hold_s: float = 0.5
    seed: int = 7
    # telemetry knobs pushed into every node's monitor_config
    max_event_log: int = 100
    window_s: float = 1.0
    max_windows: int = 600
    # streaming scrape mode (docs/Streaming.md): every node gets a
    # `subscribeKvStore` adj-delta subscription over its real ctrl
    # socket, wave scrapes trigger on stream activity instead of a poll,
    # and the report gains a `stream` section (frames/resyncs per node)
    stream_scrapes: bool = False
    # attach the fleet observer (openr_tpu/fleet) to the run over the
    # real ctrl sockets: continuous scrape+stream collection + the SLO
    # watchdog; the judged report gains a `fleet` section with the
    # observer's verdict embedded (docs/Monitoring.md "Fleet observer")
    fleet_observer: bool = False
    fleet_budget_ms: float = 2000.0  # convergence p95 SLO for the watchdog
    fleet_interval_s: float = 0.5


def _chord_pool(n: int) -> List[Tuple[int, int]]:
    return [(i, i + 2) for i in range(n - 2)]


def _chord_ifaces(a: int, b: int) -> Tuple[str, str]:
    return f"s{a}_{b}a", f"s{a}_{b}b"


class _ScrapeLog:
    """Per-node scrape bookkeeping: render latency, parse errors, counter
    monotonicity (the exporter's cumulative view must never go
    backwards), registry coverage (every counter/histogram the monitor
    knows must appear in the exposition).

    Restart waves are first-class, not forgiven ad hoc: `note_restart`
    opens a restart window for a node, and within it (a) a node that
    dies mid-scrape is *attributed* to the restart (`restart_attributed`)
    instead of failing scrape health, and (b) the post-boot counter
    reset is consumed as a typed epoch (`CounterEpochTracker`,
    monitor/exporter.py) counted in `epoch_resets`. A counter decrease
    with no restart window to blame is still a monotonicity violation —
    the check the typed epoch sharpens rather than waters down."""

    def __init__(self) -> None:
        self.count = 0
        self.errors = 0
        self.monotonic_violations = 0
        self.coverage_misses = 0
        self.restart_attributed = 0
        self.epoch_resets = 0
        self.render_ms: List[float] = []
        self._epochs = CounterEpochTracker()
        self._restarting: set = set()

    def note_restart(self, node: str) -> None:
        """A controlled restart of `node` is in flight: attribute the
        next scrape failure and/or counter epoch to it."""
        self._restarting.add(node)

    def scrape(self, node: str, daemon) -> None:
        self.count += 1
        try:
            # registry snapshot BEFORE the render: the exporter's own
            # overhead metrics are recorded during the render itself, so
            # (like Prometheus's scrape_duration) they appear one scrape
            # late — the exported set must be a superset of this snapshot
            expected = {
                prom_name(name) for name in daemon.monitor.get_counters()
            }
            expected.update(
                prom_name(name) + "_count"
                for name in daemon.monitor.get_cumulative_histograms()
            )
            t0 = time.perf_counter()
            text = daemon.exporter.render()
            self.render_ms.append((time.perf_counter() - t0) * 1e3)
            parsed = parse_metrics_text(text)
        except Exception:
            # a node that died mid-scrape (connection refused / stopped
            # daemon) during its restart window is expected churn
            if node in self._restarting:
                self.restart_attributed += 1
            else:
                self.errors += 1
            return
        obs = self._epochs.observe(node, dict(parsed["counters"]))
        if obs["reset"]:
            if node in self._restarting:
                self.epoch_resets += 1
                self._restarting.discard(node)
            else:
                self.monotonic_violations += len(obs["decreased"])
        self.coverage_misses += len(expected - set(parsed["samples"]))

    def summary(self) -> Dict[str, Any]:
        return {
            "count": self.count,
            "errors": self.errors,
            "monotonic_violations": self.monotonic_violations,
            "coverage_misses": self.coverage_misses,
            "restart_attributed": self.restart_attributed,
            "epoch_resets": self.epoch_resets,
            "render_ms": percentile_summary(self.render_ms),
        }


def _window_overlaps(
    start: float, width: float, intervals: List[Tuple[float, float]]
) -> bool:
    end = start + width
    return any(t0 < end and start < t1 for t0, t1 in intervals)


def series_slope(series: List[float]) -> float:
    """Least-squares slope (ms per window) of a windowed series — the
    drift detector: a sustained positive slope over a long soak means
    convergence latency is trending up even if no single window broke."""
    n = len(series)
    if n < 2:
        return 0.0
    xs = range(n)
    mean_x = (n - 1) / 2.0
    mean_y = sum(series) / n
    num = sum((x - mean_x) * (y - mean_y) for x, y in zip(xs, series))
    den = sum((x - mean_x) ** 2 for x in xs)
    return num / den if den else 0.0


def detect_step(
    series: List[float],
    *,
    min_side: int = 2,
    min_ratio: float = 2.0,
    min_delta_ms: float = 5.0,
) -> Optional[Dict[str, float]]:
    """Step-change detector over a windowed p95 series: the split point
    maximizing the after-mean/before-mean jump, reported only when the
    jump clears BOTH a relative (`min_ratio`) and an absolute
    (`min_delta_ms`) threshold with at least `min_side` windows on each
    side — double-gating keeps µs-scale emulator noise from flagging.
    Returns {"index", "before_ms", "after_ms", "ratio"} or None."""
    n = len(series)
    best: Optional[Dict[str, float]] = None
    for split in range(min_side, n - min_side + 1):
        before = series[:split]
        after = series[split:]
        mean_b = sum(before) / len(before)
        mean_a = sum(after) / len(after)
        delta = mean_a - mean_b
        if delta < min_delta_ms:
            continue
        ratio = mean_a / mean_b if mean_b > 0 else float("inf")
        if ratio < min_ratio:
            continue
        if best is None or delta > best["after_ms"] - best["before_ms"]:
            best = {
                "index": split,
                "before_ms": round(mean_b, 3),
                "after_ms": round(mean_a, 3),
                "ratio": round(ratio, 3) if ratio != float("inf") else -1.0,
            }
    return best


def analyze_trend(
    windows: List[Dict[str, Any]],
    stage_series: Dict[str, List[float]],
    fault_intervals: List[Tuple[float, float]],
    window_s: float,
) -> Dict[str, Any]:
    """The sharpened soak judge: windowed p95 slope + step detection on
    the end-to-end series, with per-stage attribution of a detected
    break — the stages whose own p95 series step at (or within one
    window of) the same split are the likely cause, turning "p95 got
    worse" into "fib.program regressed at wave 7"."""
    p95_series = [w["e2e_p95_ms"] for w in windows if w["events"]]
    live = [w for w in windows if w["events"]]
    trend: Dict[str, Any] = {
        "windows": len(p95_series),
        "p95_slope_ms_per_window": round(series_slope(p95_series), 4),
        "step": None,
        "attributed_stages": [],
    }
    step = detect_step(p95_series)
    if step is not None:
        idx = int(step["index"])
        window = live[min(idx, len(live) - 1)]
        step["window_start"] = window["start"]
        step["faulted"] = _window_overlaps(
            window["start"], window_s, fault_intervals
        )
        trend["step"] = step
        for stage, series in sorted(stage_series.items()):
            stage_step = detect_step(series)
            if stage_step is not None and abs(
                int(stage_step["index"]) - idx
            ) <= 1:
                trend["attributed_stages"].append(
                    {"stage": stage, **stage_step}
                )
    return trend


def _judge(
    merged: Dict[str, Any],
    fault_intervals: List[Tuple[float, float]],
    *,
    fib_spans_closed: int,
    spans_in_rings: int,
    waves: List[Dict[str, Any]],
    scrapes: Dict[str, Any],
    fleet_findings: Optional[List[Dict[str, Any]]] = None,
) -> Dict[str, Any]:
    """Fold the merged rollup + wave/scrape evidence into the judged
    sections of the soak report (windows, attribution, verdict)."""
    window_s = merged["window_s"] or 1.0
    windows = []
    clean = Histogram()
    faulted = Histogram()
    clean_windows = faulted_windows = 0
    p95_series: List[float] = []
    stage_series: Dict[str, List[float]] = {}
    for window in merged["windows"]:
        total = window["stages"].get(ConvergenceRollup.TOTAL_STAGE)
        is_faulted = _window_overlaps(
            window["start"], window_s, fault_intervals
        )
        stats = (total or Histogram()).to_dict()
        windows.append(
            {
                "start": window["start"],
                "events": window["events"],
                "faulted": is_faulted,
                "e2e_p50_ms": stats["p50"],
                "e2e_p95_ms": stats["p95"],
                "e2e_max_ms": stats["max"],
            }
        )
        if total is not None and window["events"]:
            p95_series.append(stats["p95"])
            # aligned per-stage p95 series (0.0-filled where a stage had
            # no samples) so a step in the e2e series can be attributed
            # to the pipeline stage that broke at the same window
            seen = set()
            for stage, hist in window["stages"].items():
                if stage == ConvergenceRollup.TOTAL_STAGE:
                    continue
                seen.add(stage)
                stage_series.setdefault(
                    stage, [0.0] * (len(p95_series) - 1)
                ).append(hist.percentile(95))
            for stage, series in stage_series.items():
                if stage not in seen:
                    series.append(0.0)
            if is_faulted:
                faulted.merge(total)
                faulted_windows += 1
            else:
                clean.merge(total)
                clean_windows += 1
    trend = analyze_trend(windows, stage_series, fault_intervals, window_s)

    checks: Dict[str, Dict[str, Any]] = {}

    def check(name: str, ok: bool, detail: str) -> None:
        checks[name] = {"ok": bool(ok), "detail": detail}

    windowed = sum(w["events"] for w in merged["windows"])
    accounted = windowed + merged["evicted_events"]
    check(
        "windowed_accounting",
        accounted == merged["events_total"],
        f"windows hold {windowed} + {merged['evicted_events']} evicted "
        f"of {merged['events_total']} events",
    )
    check(
        "no_eviction_loss",
        merged["events_total"] == fib_spans_closed,
        f"rollup counted {merged['events_total']} of {fib_spans_closed} "
        f"spans Fib closed (rings retain only {spans_in_rings})",
    )
    check(
        "waves_converged",
        all(w["converged"] for w in waves),
        f"{sum(1 for w in waves if w['converged'])}/{len(waves)} waves "
        f"converged within deadline",
    )
    partition_waves = [w for w in waves if w.get("partitioned")]
    check(
        "partitions_recovered",
        all(w["converged"] for w in partition_waves),
        f"{sum(1 for w in partition_waves if w['converged'])}/"
        f"{len(partition_waves)} partition wave(s) re-converged after "
        f"heal",
    )
    flood = [
        f
        for f in (fleet_findings or [])
        if f.get("kind") == "flood_health"
    ]
    unattributed = [
        f
        for f in flood
        if not _window_overlaps(
            float(f.get("ts") or 0.0), 0.0, fault_intervals
        )
    ]
    check(
        "flood_health_attributed",
        not unattributed,
        f"{len(flood)} flood_health breach(es), {len(unattributed)} "
        f"outside any fault/partition interval",
    )
    check(
        "scrape_health",
        scrapes["errors"] == 0
        and scrapes["monotonic_violations"] == 0
        and scrapes["coverage_misses"] == 0,
        f"{scrapes['count']} scrapes, {scrapes['errors']} errors, "
        f"{scrapes['monotonic_violations']} monotonicity violations, "
        f"{scrapes['coverage_misses']} registry-coverage misses",
    )
    regression = len(p95_series) >= 3 and all(
        b > a for a, b in zip(p95_series, p95_series[1:])
    )
    check(
        "no_monotonic_regression",
        not regression,
        f"windowed e2e p95 trend over {len(p95_series)} non-empty "
        f"window(s): "
        + "/".join(f"{v:.1f}" for v in p95_series[:16]),
    )
    step = trend["step"]
    clean_break = step is not None and not step["faulted"]
    check(
        "no_clean_trend_break",
        not clean_break,
        (
            "no p95 step break detected"
            if step is None
            else (
                f"p95 step at window {step['index']} "
                f"({step['before_ms']:.1f} -> {step['after_ms']:.1f}ms, "
                f"{'fault-attributed' if step['faulted'] else 'CLEAN'}"
                + (
                    ", stages: "
                    + ",".join(
                        s["stage"] for s in trend["attributed_stages"]
                    )
                    if trend["attributed_stages"]
                    else ""
                )
                + f"); slope "
                f"{trend['p95_slope_ms_per_window']:+.3f}ms/window"
            )
        ),
    )
    from openr_tpu.utils.build_info import (
        ARTIFACT_SCHEMA_VERSION,
        build_fingerprint,
    )

    return {
        "schema_version": ARTIFACT_SCHEMA_VERSION,
        "build": build_fingerprint(),
        "windows": windows,
        "trend": trend,
        "attribution": {
            "clean_windows": clean_windows,
            "faulted_windows": faulted_windows,
            "clean_e2e_ms": clean.to_dict(),
            "faulted_e2e_ms": faulted.to_dict(),
        },
        "cumulative_e2e_ms": (
            merged["cumulative"]
            .get(ConvergenceRollup.TOTAL_STAGE, Histogram())
            .to_dict()
        ),
        "verdict": {
            "pass": all(c["ok"] for c in checks.values()),
            "checks": checks,
        },
    }


def run_soak(
    cfg: SoakConfig, arm_chaos=None
) -> Dict[str, Any]:
    """Run one soak to completion; returns the judged report dict.

    `arm_chaos(injector, wave_index, cfg)` overrides the default chaos
    schedule armed on fault waves (the smoke uses it to inject exactly
    one deterministic fault)."""
    from openr_tpu.testing.wrapper import VirtualNetwork, wait_until

    n = max(3, cfg.nodes)
    rng = random.Random(cfg.seed)
    chords = _chord_pool(n)
    chord_state: Dict[Tuple[int, int], str] = {c: "new" for c in chords}

    def default_chaos(inj: FaultInjector, wave: int, _cfg) -> None:
        inj.arm("fib.program", times=1)
        inj.arm(
            "kvstore.flood_send",
            probability=_cfg.fault_probability,
            times=_cfg.fault_budget,
        )

    arm = arm_chaos if arm_chaos is not None else default_chaos

    async def body(store_dir: Optional[str]) -> Dict[str, Any]:
        mesh = None
        if cfg.partition_every > 0:
            from openr_tpu.testing.chaos import ChaosMesh

            mesh = ChaosMesh(seed=cfg.seed)
        net = VirtualNetwork(chaos=mesh)
        overrides: Dict[str, Any] = {
            "monitor_config": {
                "max_event_log": cfg.max_event_log,
                "rollup_window_s": cfg.window_s,
                "rollup_max_windows": cfg.max_windows,
            }
        }
        if cfg.restart_every:
            # restart waves need graceful restart on the wire and a
            # durable configstore per node (warm-boot version floors)
            overrides["spark_config"] = {"graceful_restart_enabled": True}
        for i in range(n):
            net.add_node(
                f"n{i}",
                loopback_prefix=f"10.{i}.0.0/24",
                config_overrides=overrides,
                config_store_path=(
                    None
                    if store_dir is None
                    else f"{store_dir}/n{i}.bin"
                ),
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

        def chords_applied(toggles) -> bool:
            for (a, b), up in toggles:
                adjacent = net.wrappers[f"n{a}"].adjacent_nodes()
                if up != (f"n{b}" in adjacent):
                    return False
            return True

        scrapes = _ScrapeLog()
        wave_log: List[Dict[str, Any]] = []
        fault_intervals: List[Tuple[float, float]] = []
        fired: Dict[str, int] = {}

        # fleet observer (openr_tpu/fleet): continuous scrape+stream
        # collection over the real ctrl sockets + the SLO watchdog,
        # verdict embedded in the report's `fleet` section
        observer = None
        if cfg.fleet_observer:
            from openr_tpu.fleet import FleetConfig, FleetObserver, SloConfig

            observer = FleetObserver.for_network(
                net,
                config=FleetConfig(
                    scrape_interval_s=cfg.fleet_interval_s,
                    slo=SloConfig(
                        convergence_p95_budget_ms=cfg.fleet_budget_ms
                    ),
                ),
            )

        def scrape_all() -> None:
            for name, wrapper in net.wrappers.items():
                scrapes.scrape(name, wrapper.daemon)

        # streaming scrape mode: each node carries a live
        # `subscribeKvStore` adj-delta subscription over its real ctrl
        # socket; wave scrapes trigger on delivered stream frames
        # instead of polling (docs/Streaming.md)
        stream_counts: Dict[str, Dict[str, int]] = {}
        stream_tasks: List[asyncio.Task] = []
        stream_clients: List[Any] = []

        async def _watch_stream(name: str, client) -> None:
            try:
                async for frame in client.subscribe(
                    "subscribeKvStore",
                    area="0",
                    prefixes=["adj:"],
                    client="soak-scrape",
                ):
                    stream_counts[name]["frames"] += 1
                    if frame.get("type") == "resync":
                        stream_counts[name]["resyncs"] += 1
            except Exception:
                stream_counts[name]["errors"] = (
                    stream_counts[name].get("errors", 0) + 1
                )

        async def _start_streams() -> None:
            from openr_tpu.ctrl.client import CtrlClient

            for name, wrapper in net.wrappers.items():
                client = await CtrlClient(
                    "127.0.0.1", wrapper.ctrl_port
                ).connect()
                stream_clients.append(client)
                stream_counts[name] = {"frames": 0, "resyncs": 0}
                stream_tasks.append(
                    asyncio.get_running_loop().create_task(
                        _watch_stream(name, client)
                    )
                )

        def stream_frames_total() -> int:
            return sum(c["frames"] for c in stream_counts.values())

        with injected(FaultInjector(seed=cfg.seed)) as inj:
            try:
                await wait_until(
                    converged, timeout=cfg.converge_timeout_s
                )
                if cfg.stream_scrapes:
                    await _start_streams()
                    # the initial snapshot frames prove every stream is up
                    await wait_until(
                        lambda: all(
                            c["frames"] >= 1 for c in stream_counts.values()
                        ),
                        timeout=cfg.converge_timeout_s,
                    )
                if observer is not None:
                    await observer.start()
                scrape_all()
                for wave_i in range(cfg.waves):
                    chaos = (
                        cfg.fault_every > 0
                        and (wave_i + 1) % cfg.fault_every == 0
                    )
                    if chaos:
                        arm(inj, wave_i, cfg)
                        fault_t0 = time.time()
                    # partition wave: asymmetrically blackhole one
                    # direction of a random line edge through the chaos
                    # mesh, hold, heal — the wave's convergence wait
                    # below then proves recovery after heal
                    partitioned: List[str] = []
                    if (
                        mesh is not None
                        and (wave_i + 1) % cfg.partition_every == 0
                    ):
                        from openr_tpu.testing.chaos import ChaosLinkSpec

                        edge = rng.randrange(0, n - 1)
                        src, dst = f"n{edge}", f"n{edge + 1}"
                        part_t0 = time.time()
                        mesh.set_link(
                            src,
                            dst,
                            ChaosLinkSpec(
                                partition=True, spark_loss=0.0
                            ),
                        )
                        partitioned.append(f"{src}->{dst}")
                        await asyncio.sleep(cfg.partition_hold_s)
                        mesh.clear_link(src, dst)
                    # the OCS bulk reconfiguration: remove up-chords,
                    # add down-chords, all in one batch
                    frames_before = stream_frames_total()
                    ups = [c for c in chords if chord_state[c] == "up"]
                    downs = [c for c in chords if chord_state[c] != "up"]
                    rng.shuffle(ups)
                    rng.shuffle(downs)
                    removed = ups[: cfg.wave_links]
                    added = downs[: cfg.wave_links]
                    toggles = []
                    for a, b in removed:
                        ia, ib = _chord_ifaces(a, b)
                        net.fail_link(f"n{a}", ia, f"n{b}", ib)
                        chord_state[(a, b)] = "down"
                        toggles.append(((a, b), False))
                    for a, b in added:
                        ia, ib = _chord_ifaces(a, b)
                        if chord_state[(a, b)] == "new":
                            net.connect(f"n{a}", ia, f"n{b}", ib)
                        else:
                            net.restore_link(f"n{a}", ia, f"n{b}", ib)
                        chord_state[(a, b)] = "up"
                        toggles.append(((a, b), True))
                    # restart wave: after the chord batch lands, bounce a
                    # random interior node through the graceful-restart
                    # warm-boot path — the wave only converges once the
                    # respawn has resynced and reprogrammed
                    restarted: List[str] = []
                    if (
                        cfg.restart_every > 0
                        and (wave_i + 1) % cfg.restart_every == 0
                    ):
                        victim = f"n{rng.randrange(1, n - 1)}"
                        # open the restart windows FIRST: a scrape/stream
                        # racing the bounce is attributed, not an error
                        scrapes.note_restart(victim)
                        if observer is not None:
                            observer.note_restart(victim)
                        await net.restart_node(victim)
                        restarted.append(victim)
                    t0 = time.time()
                    wave_ok = True
                    try:
                        await wait_until(
                            lambda: chords_applied(toggles)
                            and converged(),
                            timeout=cfg.converge_timeout_s,
                        )
                    except AssertionError:
                        wave_ok = False
                    converge_ms = (time.time() - t0) * 1e3
                    if cfg.stream_scrapes and wave_ok:
                        # scrape on push, not poll: the wave's adjacency
                        # deltas must arrive over the subscription
                        # streams before the post-wave scrape fires
                        await wait_until(
                            lambda: stream_frames_total() > frames_before,
                            timeout=cfg.converge_timeout_s,
                        )
                    await asyncio.sleep(cfg.settle_s)
                    if chaos:
                        for point in ("fib.program", "kvstore.flood_send",
                                      "spark.packet_send"):
                            fired[point] = fired.get(point, 0) + inj.fired(
                                point
                            )
                            inj.disarm(point)
                        fault_intervals.append((fault_t0, time.time()))
                    if partitioned:
                        # cover the hold AND the settle: a flood_health
                        # breach the watchdog stamps just after heal is
                        # still partition-attributed
                        fault_intervals.append((part_t0, time.time()))
                    scrape_all()
                    wave_log.append(
                        {
                            "index": wave_i,
                            "added": [f"n{a}-n{b}" for a, b in added],
                            "removed": [
                                f"n{a}-n{b}" for a, b in removed
                            ],
                            "restarted": restarted,
                            "partitioned": partitioned,
                            "faulted": chaos,
                            "converged": wave_ok,
                            "converge_ms": round(converge_ms, 2),
                        }
                    )

                # let the monitor queues drain every closed span into the
                # rollups before judging (record-time fold, async drain)
                def fib_spans() -> int:
                    return sum(
                        w.daemon.fib.counters.get(
                            "fib.convergence_spans", 0
                        )
                        for w in net.wrappers.values()
                    )

                def rollup_events() -> int:
                    return sum(
                        w.daemon.monitor.rollup.events_total
                        for w in net.wrappers.values()
                    )

                try:
                    await wait_until(
                        lambda: rollup_events() >= fib_spans(),
                        timeout=20.0,
                    )
                except AssertionError:
                    pass  # the no_eviction_loss check will report it
                scrape_all()
                fib_spans_closed = fib_spans()
                reports = net.node_reports()
            finally:
                fleet_report = None
                if observer is not None:
                    await observer.stop()
                    fleet_report = observer.report()
                for task in stream_tasks:
                    task.cancel()
                if stream_tasks:
                    await asyncio.gather(
                        *stream_tasks, return_exceptions=True
                    )
                for client in stream_clients:
                    await client.close()
                await net.stop_all()

        merged = merge_rollup_snapshots(
            r["rollup"] for r in reports if r.get("rollup")
        )
        spans_in_rings = sum(len(r["spans"]) for r in reports)
        judged = _judge(
            merged,
            fault_intervals,
            fib_spans_closed=fib_spans_closed,
            spans_in_rings=spans_in_rings,
            waves=wave_log,
            scrapes=scrapes.summary(),
            fleet_findings=(fleet_report or {}).get("findings"),
        )
        return {
            "config": asdict(cfg),
            "nodes": n,
            "waves": wave_log,
            "faults": {
                "fired": fired,
                "intervals": [list(iv) for iv in fault_intervals],
            },
            "scrapes": scrapes.summary(),
            "stream": {
                "enabled": cfg.stream_scrapes,
                "nodes": dict(stream_counts),
                "frames_total": stream_frames_total(),
                "resyncs_total": sum(
                    c["resyncs"] for c in stream_counts.values()
                ),
            },
            "events": {
                "total": merged["events_total"],
                "windowed": sum(
                    w["events"] for w in merged["windows"]
                ),
                "evicted_window_events": merged["evicted_events"],
                "spans_in_rings": spans_in_rings,
                "fib_spans_closed": fib_spans_closed,
            },
            "fleet": fleet_report,
            **judged,
        }

    loop = asyncio.new_event_loop()
    try:
        if cfg.restart_every:
            import tempfile

            with tempfile.TemporaryDirectory() as td:
                return loop.run_until_complete(body(td))
        return loop.run_until_complete(body(None))
    finally:
        loop.close()


def run_soak_smoke() -> Dict[str, Any]:
    """SOAK_SMOKE tier-1 (the churn sibling of FAULT_SMOKE/TRACE_SMOKE):
    a 3-node line, ONE reconfiguration wave (the n0–n2 chord comes up),
    ONE injected fault (fib.program), and a max_event_log small enough
    that ring eviction provably happens — asserting the judged-report
    machinery end to end: windowed totals account for 100% of events
    (the acceptance invariant), every scrape parses with full registry
    coverage, and the verdict block carries every check. Topology size
    scales via SOAK_SMOKE_NODES; returns the report."""
    import os

    n = max(3, int(os.environ.get("SOAK_SMOKE_NODES", "3")))
    cfg = SoakConfig(
        nodes=n,
        waves=1,
        wave_links=1,
        settle_s=0.3,
        fault_every=1,  # the single wave is a fault wave
        seed=3,
        max_event_log=3,  # force ring eviction: rings hold only a tail
        window_s=0.5,
        max_windows=240,
    )

    def one_fault(inj: FaultInjector, wave: int, _cfg) -> None:
        inj.arm("fib.program", times=1)

    report = run_soak(cfg, arm_chaos=one_fault)
    events = report["events"]
    assert events["total"] > cfg.max_event_log, events
    assert (
        events["windowed"] + events["evicted_window_events"]
        == events["total"]
    ), events
    assert events["spans_in_rings"] < events["total"], events
    assert report["faults"]["fired"].get("fib.program") == 1, report[
        "faults"
    ]
    checks = report["verdict"]["checks"]
    for name in (
        "windowed_accounting",
        "no_eviction_loss",
        "waves_converged",
        "scrape_health",
        "no_monotonic_regression",
        "no_clean_trend_break",
    ):
        assert name in checks, sorted(checks)
        assert checks[name]["ok"], (name, checks[name])
    assert report["verdict"]["pass"], checks
    assert report["scrapes"]["count"] >= 2 * n, report["scrapes"]
    return report


def run_soak_round(
    round_index: int = 1,
    cfg: Optional[SoakConfig] = None,
    fanout_subscribers: int = 2048,
    fanout_nodes: int = 8,
    fanout_flaps: int = 2,
    fanout_inproc: Optional[int] = None,
    out_dir: str = ".",
) -> Dict[str, Any]:
    """The soak round that writes a `SOAK_r<NN>.json` artifact: one full
    chord+chaos+restart soak with stream-mode scrapes AND the fleet
    observer attached (its verdict embedded in the artifact), followed
    by `fanout_scale`, the 100k-subscriber push (docs/Streaming.md
    "Fan-out at scale"): a flap batch
    (decision_harness.run_flap_batch) enriched with production-sized
    key churn and served to the socket cohort (mixed JSON/binary
    codecs, admission control live, one subscriber deliberately stalled
    into overflow→resync) plus the in-process cohort (`fanout_inproc`,
    testing/fanout.py — the fd limit forbids 100k real sockets; the
    artifact reports the split honestly), with the fleet observer
    attached as SLO judge: every `stream_backpressure` breach must be
    attributable to the stalled subscriber's node, anything else fails
    the round.

    `fanout_inproc` defaults to SOAK_FANOUT_INPROC (98304: with the
    2048-socket cohort the total crosses 100k). Returns the artifact
    dict."""
    from openr_tpu.testing.decision_harness import run_flap_batch

    if cfg is None:
        nodes = int(os.environ.get("SOAK_ROUND_NODES", "96"))
        cfg = SoakConfig(
            nodes=nodes,
            waves=int(os.environ.get("SOAK_ROUND_WAVES", "12")),
            wave_links=2,
            # per-wave drain time: the judged trend must measure the
            # protocol, not cross-wave monitor-queue backlog
            settle_s=2.0,
            # a deep line topology floods adjacency across its whole
            # diameter per wave: scale the deadline with the fleet
            converge_timeout_s=max(120.0, 2.5 * nodes),
            fault_every=3,
            restart_every=4,
            # partition waves ride the round too: one asymmetric
            # line-edge split per 5th wave, healed after half a second
            partition_every=5,
            partition_hold_s=0.5,
            seed=11,
            window_s=8.0,
            stream_scrapes=True,
            fleet_observer=True,
            # the SLO budget is an operator choice per fleet: a deep
            # line emulated on shared CPU converges in seconds, not ms
            fleet_budget_ms=float(
                os.environ.get("SOAK_ROUND_BUDGET_MS", "15000")
            ),
        )
    if fanout_inproc is None:
        fanout_inproc = int(os.environ.get("SOAK_FANOUT_INPROC", "98304"))

    t0 = time.time()
    soak_report = run_soak(cfg)
    soak_s = time.time() - t0

    # the 100k hybrid cohort with the fleet observer as judge
    t0 = time.time()
    fanout_scale = run_flap_batch(
        nodes=fanout_nodes,
        flaps=fanout_flaps,
        subscribers=fanout_subscribers,
        inproc_subscribers=fanout_inproc,
        codec="mixed",
        churn_keys=int(os.environ.get("SOAK_FANOUT_CHURN_KEYS", "8")),
        churn_value_bytes=int(
            os.environ.get("SOAK_FANOUT_CHURN_BYTES", "16384")
        ),
    )
    scale_s = time.time() - t0
    inproc_subs = fanout_scale["stream_inproc_subscribers"]
    total_subs = fanout_subscribers + inproc_subs
    # the stalled socket subscriber is index 0 -> node n0: any
    # stream_backpressure finding elsewhere is an UNATTRIBUTED breach
    backpressure_nodes = fanout_scale["fleet_findings_by_kind"].get(
        "stream_backpressure", []
    )
    unattributed = [nd for nd in backpressure_nodes if nd != "n0"]
    fanout_scale["verdict"] = (
        f"{total_subs} total subscribers "
        f"({fanout_subscribers} real sockets, mixed JSON/binary codecs, "
        f"{inproc_subs} in-process "
        f"via testing/fanout.py) across {fanout_nodes} nodes with one "
        f"deliberately stalled socket subscriber: encode share "
        f"{fanout_scale['stream_encode_share'] * 100:.1f}%, "
        f"class hit rate "
        f"{fanout_scale['stream_class_hit_rate']:.3f}, "
        f"stream_backpressure findings on "
        f"{backpressure_nodes or 'no nodes'} — "
        + (
            "every breach attributable to the stalled subscriber's "
            "node; admission control and slow-client isolation held "
            "at scale"
            if not unattributed
            else f"UNATTRIBUTED breach on {unattributed}: sharing leaked "
            "backpressure across subscribers"
        )
    )
    fanout_scale["backpressure_attributed"] = not unattributed

    from openr_tpu.utils.build_info import (
        ARTIFACT_SCHEMA_VERSION,
        build_fingerprint,
    )

    artifact = {
        "schema_version": ARTIFACT_SCHEMA_VERSION,
        "build": build_fingerprint(),
        "round": round_index,
        "kind": "SOAK",
        "config": asdict(cfg),
        "soak_wall_s": round(soak_s, 1),
        "fanout_scale_wall_s": round(scale_s, 1),
        "soak": soak_report,
        "fleet_verdict": (soak_report.get("fleet") or {}).get("verdict"),
        "fanout_scale": fanout_scale,
        "fanout_total_subscribers": total_subs,
        "fanout_socket_subscribers": fanout_subscribers,
        "fanout_inproc_subscribers": inproc_subs,
    }
    path = os.path.join(out_dir, f"SOAK_r{round_index:02d}.json")
    with open(path, "w") as fh:
        json.dump(artifact, fh, indent=2, sort_keys=True, default=str)
    artifact["path"] = path
    return artifact


def main(argv: Optional[List[str]] = None) -> int:
    """CLI soak driver: python -m openr_tpu.testing.soak --nodes 8
    --waves 12 --out soak.json (render with `breeze perf soak-report`);
    `--round N` runs the full artifact round (soak + fleet observer +
    fan-out push) and writes SOAK_rNN.json instead."""
    import argparse

    parser = argparse.ArgumentParser(
        prog="soak", description="topology-churn soak harness"
    )
    parser.add_argument("--nodes", type=int, default=6)
    parser.add_argument("--waves", type=int, default=4)
    parser.add_argument("--wave-links", type=int, default=1)
    parser.add_argument("--settle-s", type=float, default=1.0)
    parser.add_argument("--fault-every", type=int, default=2)
    parser.add_argument("--restart-every", type=int, default=0)
    parser.add_argument(
        "--partition-every",
        type=int,
        default=0,
        help=(
            "every Nth wave asymmetrically partitions one line-edge "
            "direction via the chaos mesh, then heals (0 disables)"
        ),
    )
    parser.add_argument("--partition-hold-s", type=float, default=0.5)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--window-s", type=float, default=1.0)
    parser.add_argument("--max-event-log", type=int, default=100)
    parser.add_argument(
        "--fleet-observer",
        action="store_true",
        help="attach the fleet observer (verdict embedded in the report)",
    )
    parser.add_argument(
        "--round",
        type=int,
        default=None,
        help="run the full SOAK_rNN.json artifact round instead",
    )
    parser.add_argument(
        "--fanout-subscribers",
        type=int,
        default=2048,
        help="fan-out push socket-subscriber count for the artifact round",
    )
    parser.add_argument(
        "--fanout-inproc",
        type=int,
        default=None,
        help=(
            "in-process cohort size for the scale run (default "
            "SOAK_FANOUT_INPROC or 98304; sockets+inproc >= 100k)"
        ),
    )
    parser.add_argument("--out", default=None, help="JSON report path")
    args = parser.parse_args(argv)
    if args.round is not None:
        artifact = run_soak_round(
            round_index=args.round,
            fanout_subscribers=args.fanout_subscribers,
            fanout_inproc=args.fanout_inproc,
        )
        verdict = artifact["soak"]["verdict"]
        fleet = artifact.get("fleet_verdict") or {}
        attributed = artifact["fanout_scale"]["backpressure_attributed"]
        print(
            json.dumps(
                {
                    "soak": "PASS" if verdict["pass"] else "FAIL",
                    "fleet": "PASS" if fleet.get("pass") else "BREACH",
                    "total_subscribers": artifact[
                        "fanout_total_subscribers"
                    ],
                    "backpressure": (
                        "ATTRIBUTED" if attributed else "UNATTRIBUTED"
                    ),
                    "artifact": artifact["path"],
                }
            )
        )
        return 0 if (verdict["pass"] and attributed) else 1
    cfg = SoakConfig(
        nodes=args.nodes,
        waves=args.waves,
        wave_links=args.wave_links,
        settle_s=args.settle_s,
        fault_every=args.fault_every,
        restart_every=args.restart_every,
        partition_every=args.partition_every,
        partition_hold_s=args.partition_hold_s,
        seed=args.seed,
        window_s=args.window_s,
        max_event_log=args.max_event_log,
        fleet_observer=args.fleet_observer,
    )
    report = run_soak(cfg)
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
    verdict = report["verdict"]
    print(
        json.dumps(
            {
                "soak": "PASS" if verdict["pass"] else "FAIL",
                "events_total": report["events"]["total"],
                "waves": len(report["waves"]),
                "windows": len(report["windows"]),
            }
        )
    )
    return 0 if verdict["pass"] else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())
