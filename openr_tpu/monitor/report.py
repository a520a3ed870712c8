"""Cross-node convergence reports.

The per-node trace substrate — CONVERGENCE_TRACE spans (monitor/spans.py)
and FLOOD_TRACE samples + `kvstore.flood.*` stats (kvstore/store.py) —
answers "how fast did THIS node converge". The network-wide question
("after one link flap, when did the LAST node program routes, and which
hop was slowest?") needs an aggregation layer:

  - `node_convergence_report(...)` distills one node's monitor ring and
    kvstore flood stats into a JSON-serializable report (served by ctrl
    `getConvergenceReport`);
  - `aggregate_convergence_reports(...)` folds the reports of every node
    of an emulator / VirtualNetwork run (or a `breeze perf report
    --hosts ...` sweep) into network-wide convergence percentiles
    (p50/p95/max node-to-converge), per-stage latency distributions with
    slowest-hop attribution, and flood-health stats (hop latencies,
    hop-count spread, redundant-flood ratio).

This is the instrument DeltaPath (PAPERS.md) argues for: the metric that
validates an accelerated SPF backend is event-to-network-wide-programmed-
routes latency, not local solve time.
"""

from __future__ import annotations

import heapq
import math
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

from openr_tpu.monitor.spans import SPAN_EVENT, sample_stage_durations
from openr_tpu.utils.counters import Histogram

FLOOD_TRACE_EVENT = "FLOOD_TRACE"  # mirrors kvstore/store.py (no import
# cycle: kvstore.store already imports monitor.monitor)


def percentile_summary(values: Iterable[float]) -> Dict[str, float]:
    """count/min/avg/p50/p95/max over a raw sample list (nearest-rank
    percentiles — report sample sets are small, no bucketing needed)."""
    samples = sorted(float(v) for v in values)
    if not samples:
        return {
            "count": 0,
            "min": 0.0,
            "avg": 0.0,
            "p50": 0.0,
            "p95": 0.0,
            "max": 0.0,
        }

    def rank(p: float) -> float:
        idx = max(0, math.ceil(p / 100.0 * len(samples)) - 1)
        return samples[min(idx, len(samples) - 1)]

    return {
        "count": len(samples),
        "min": samples[0],
        "avg": sum(samples) / len(samples),
        "p50": rank(50),
        "p95": rank(95),
        "max": samples[-1],
    }


# ---------------------------------------------------------------------------
# eviction-proof windowed rollups
# ---------------------------------------------------------------------------


class ConvergenceRollup:
    """Fixed-cost, eviction-proof aggregation of convergence spans.

    The monitor's event-log ring keeps the last `max_event_log` LogSamples
    of ANY kind, so on a busy node a span sample lives seconds before
    FLOOD_TRACEs push it out — which is why every convergence claim so far
    covered single flaps only. The rollup folds each finished span into
    two aggregate layers AT RECORD TIME (Monitor.add_event_log), before
    the ring can evict it:

      - **cumulative**: one mergeable Histogram per stage (plus the
        `total` end-to-end pseudo-stage) covering every span since
        process start — the layer the exporter serves and the layer that
        must account for 100% of events regardless of ring size;
      - **windowed**: the same per-stage histograms bucketed into
        `window_s`-wide wall-clock windows, kept in a bounded ring of
        `max_windows` (evicted windows fold their event count into
        `evicted_events`; their samples stay in the cumulative layer, so
        window eviction loses trend resolution, never data).

    Beside the aggregates each window keeps the whole samples of its
    `SLOWEST_KEPT` slowest spans (by `total_ms`; a bounded heap), with the
    account Fib closed for them: `gc_full_ms`, `unstaged_ms`, the
    `stage.<name>_ms` of the stages that ran under the event's build, what
    Decision noted of the build. The ring evicts a sample within seconds;
    the one record that says between which marks an event of seconds fell
    stays for as long as its window does (`slowest()`, `snapshot()`).

    Memory is O(max_windows x stages), independent of event rate; one
    record is O(stages) Histogram.record calls. Snapshots are
    JSON-serializable (sparse histograms) and merge across nodes —
    wall-clock window starts align inside an emulator host and are
    NTP-close across real hosts.
    """

    TOTAL_STAGE = "total"
    SLOWEST_KEPT = 8

    def __init__(
        self,
        window_s: float = 60.0,
        max_windows: int = 120,
        clock=time.time,
    ) -> None:
        assert window_s > 0 and max_windows >= 1
        self.window_s = float(window_s)
        self.max_windows = int(max_windows)
        self._clock = clock
        self.events_total = 0
        self.evicted_events = 0
        self.window_evictions = 0
        self.cumulative: Dict[str, Histogram] = {}
        # ordered oldest->newest: (window index, {"events": n, stages,
        # "slowest": heap of (total_ms, events_total at record, values)})
        self._windows: List[Tuple[int, Dict[str, Any]]] = []

    def record_span(
        self, values: Dict[str, Any], ts: Optional[float] = None
    ) -> None:
        """Fold one finished span's value map (LogSample shape) into the
        cumulative and windowed layers."""
        stages = sample_stage_durations(values)
        if not stages:
            return
        when = self._clock() if ts is None else float(ts)
        window = self._window_for(when)
        self.events_total += 1
        if window is None:  # stamp predates the retained window ring
            self.evicted_events += 1
        else:
            window["events"] += 1
            total = stages.get(self.TOTAL_STAGE)
            if total is not None:
                heap = window["slowest"]
                item = (total, self.events_total, values)
                if len(heap) < self.SLOWEST_KEPT:
                    heapq.heappush(heap, item)
                elif total > heap[0][0]:
                    heapq.heapreplace(heap, item)
        for stage, ms in stages.items():
            cum = self.cumulative.get(stage)
            if cum is None:
                cum = self.cumulative[stage] = Histogram()
            cum.record(ms)
            if window is None:
                continue
            win = window["stages"].get(stage)
            if win is None:
                win = window["stages"][stage] = Histogram()
            win.record(ms)

    def _window_for(self, when: float) -> Optional[Dict[str, Any]]:
        """Retained window for a wall-clock stamp; None when the stamp's
        window already left the bounded ring (the sample then counts as
        evicted and lands only in the cumulative layer). Out-of-order
        stamps (monitor-queue drain lag) fold into their retained window
        rather than tearing the ring order."""
        index = int(when // self.window_s)
        if self._windows:
            if self._windows[-1][0] == index:
                return self._windows[-1][1]
            for idx, window in reversed(self._windows):
                if idx == index:
                    return window
                if idx < index:
                    break
            if (
                index < self._windows[0][0]
                and len(self._windows) >= self.max_windows
            ):
                return None
        window: Dict[str, Any] = {"events": 0, "stages": {}, "slowest": []}
        self._windows.append((index, window))
        self._windows.sort(key=lambda iw: iw[0])
        while len(self._windows) > self.max_windows:
            _, evicted = self._windows.pop(0)
            self.window_evictions += 1
            self.evicted_events += evicted["events"]
        return window

    def windowed_events(self) -> int:
        """Events still resolvable to a retained window; plus
        `evicted_events` this always equals `events_total` — the
        no-eviction-loss invariant the soak verdict checks."""
        return sum(w["events"] for _, w in self._windows)

    def last_window(self) -> Optional[Dict[str, Any]]:
        """Newest window (may still be filling): {"start", "events",
        "stages": {stage: Histogram}} — the exporter's windowed gauges."""
        if not self._windows:
            return None
        index, window = self._windows[-1]
        return {
            "start": index * self.window_s,
            "events": window["events"],
            "stages": window["stages"],
        }

    def slowest(self) -> List[Dict[str, Any]]:
        """The samples of the `SLOWEST_KEPT` slowest spans of the retained
        windows, slowest first (what `getConvergenceReport` serves as
        `slowest`)."""
        return _slowest_samples(
            values for _, w in self._windows for _, _, values in w["slowest"]
        )

    def snapshot(self) -> Dict[str, Any]:
        """JSON-serializable export (sparse histograms; each window with
        the samples of its slowest spans, slowest first), the shape served
        inside node_convergence_report and merged network-wide by
        merge_rollup_snapshots."""
        return {
            "window_s": self.window_s,
            "max_windows": self.max_windows,
            "events_total": self.events_total,
            "evicted_events": self.evicted_events,
            "window_evictions": self.window_evictions,
            "cumulative": {
                stage: h.to_sparse()
                for stage, h in sorted(self.cumulative.items())
            },
            "windows": [
                {
                    "start": index * self.window_s,
                    "events": window["events"],
                    "stages": {
                        stage: h.to_sparse()
                        for stage, h in sorted(window["stages"].items())
                    },
                    "slowest": _slowest_samples(
                        values for _, _, values in window["slowest"]
                    ),
                }
                for index, window in self._windows
            ],
        }


def _slowest_samples(
    samples: Iterable[Dict[str, Any]], keep: int = ConvergenceRollup.SLOWEST_KEPT
) -> List[Dict[str, Any]]:
    """Span samples by `total_ms`, the `keep` slowest first."""
    return sorted(
        samples, key=lambda values: values.get("total_ms", 0.0), reverse=True
    )[:keep]


def merge_rollup_snapshots(
    snapshots: Iterable[Dict[str, Any]],
) -> Dict[str, Any]:
    """Fold per-node rollup snapshots into one network-wide rollup with
    live Histogram objects: same-start windows merge across nodes (the
    wall clock is the shared axis). Returns {"window_s", "events_total",
    "evicted_events", "window_evictions", "cumulative": {stage: Histogram},
    "windows": [{"start", "events", "stages": {stage: Histogram},
    "slowest": [sample values]}]}."""
    window_s = 0.0
    events_total = evicted = window_evictions = 0
    cumulative: Dict[str, Histogram] = {}
    windows: Dict[float, Dict[str, Any]] = {}
    for snap in snapshots:
        if not snap:
            continue
        window_s = window_s or float(snap.get("window_s", 0.0))
        events_total += int(snap.get("events_total", 0))
        evicted += int(snap.get("evicted_events", 0))
        window_evictions += int(snap.get("window_evictions", 0))
        for stage, sparse in (snap.get("cumulative") or {}).items():
            hist = Histogram.from_sparse(sparse)
            if stage in cumulative:
                cumulative[stage].merge(hist)
            else:
                cumulative[stage] = hist
        for window in snap.get("windows") or []:
            start = float(window.get("start", 0.0))
            merged = windows.setdefault(
                start,
                {"start": start, "events": 0, "stages": {}, "slowest": []},
            )
            merged["events"] += int(window.get("events", 0))
            merged["slowest"] = _slowest_samples(
                merged["slowest"] + list(window.get("slowest") or [])
            )
            for stage, sparse in (window.get("stages") or {}).items():
                hist = Histogram.from_sparse(sparse)
                if stage in merged["stages"]:
                    merged["stages"][stage].merge(hist)
                else:
                    merged["stages"][stage] = hist
    return {
        "window_s": window_s,
        "events_total": events_total,
        "evicted_events": evicted,
        "window_evictions": window_evictions,
        "cumulative": cumulative,
        "windows": [windows[start] for start in sorted(windows)],
    }


def node_convergence_report(
    node_name: str, monitor, kvstore=None
) -> Dict[str, Any]:
    """One node's convergence evidence: finished spans and flood traces
    from the monitor's event-log ring, plus the kvstore flood counters and
    histogram exports. Everything in the result is JSON-serializable."""
    spans: List[Dict[str, Any]] = []
    floods: List[Dict[str, Any]] = []
    for sample in monitor.get_event_logs():
        event = sample.get("event")
        if event == SPAN_EVENT:
            spans.append(sample.values())
        elif event == FLOOD_TRACE_EVENT:
            floods.append(sample.values())
    flood_stats: Dict[str, Any] = {"received": 0, "duplicates": 0}
    if kvstore is not None:
        counters = kvstore.counters
        flood_stats["received"] = counters.get("kvstore.flood.received", 0)
        flood_stats["duplicates"] = counters.get(
            "kvstore.flood.duplicates", 0
        )
        flood_stats["hop_count_last"] = counters.get(
            "kvstore.flood.hop_count_last", 0
        )
        histograms = getattr(kvstore, "histograms", None) or {}
        for name in (
            "kvstore.flood.hop_ms",
            "kvstore.flood.e2e_ms",
            "kvstore.flood.buffer_delay_ms",
        ):
            hist = histograms.get(name)
            if hist is not None:
                flood_stats[name.rsplit(".", 1)[-1]] = hist.to_dict()
    received = flood_stats["received"]
    flood_stats["duplicate_ratio"] = (
        flood_stats["duplicates"] / received if received else 0.0
    )
    # eviction-proof layer: the record-time windowed rollup covers every
    # span since start even after the ring above evicted its sample
    rollup = getattr(monitor, "rollup", None)
    return {
        "node": node_name,
        "spans": spans,
        "e2e_ms": [
            s["total_ms"] for s in spans if s.get("total_ms") is not None
        ],
        "floods": floods,
        "flood": flood_stats,
        "rollup": rollup.snapshot() if rollup is not None else None,
        # out of the ring's reach: the slowest spans of the retained
        # windows, each with the account Fib closed for it
        "slowest": rollup.slowest() if rollup is not None else [],
    }


def _span_stages(span: Dict[str, Any]) -> Dict[str, float]:
    stages = sample_stage_durations(span)
    stages.pop(ConvergenceRollup.TOTAL_STAGE, None)  # not a pipeline stage
    return stages


def _aggregate_rollups(reports: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Network-wide cumulative-vs-windowed split from the per-node rollup
    snapshots: unlike the ring-derived sections (bounded by max_event_log),
    `events_total` here accounts for every span since node start."""
    merged = merge_rollup_snapshots(
        r.get("rollup") for r in reports if r.get("rollup")
    )
    return {
        "window_s": merged["window_s"],
        "events_total": merged["events_total"],
        "evicted_events": merged["evicted_events"],
        "window_evictions": merged["window_evictions"],
        "cumulative": {
            stage: hist.to_dict()
            for stage, hist in sorted(merged["cumulative"].items())
        },
        "windows": [
            {
                "start": window["start"],
                "events": window["events"],
                "e2e_ms": (
                    window["stages"][ConvergenceRollup.TOTAL_STAGE].to_dict()
                    if ConvergenceRollup.TOTAL_STAGE in window["stages"]
                    else Histogram().to_dict()
                ),
            }
            for window in merged["windows"]
        ],
    }


def aggregate_convergence_reports(
    reports: Iterable[Dict[str, Any]],
) -> Dict[str, Any]:
    """Fold per-node reports into the network-wide convergence view."""
    reports = list(reports)
    all_e2e: List[float] = []
    node_e2e: Dict[str, Dict[str, float]] = {}
    stage_samples: Dict[str, List[float]] = {}
    slowest: Optional[Dict[str, Any]] = None
    hop_ms: List[float] = []
    hop_counts: List[int] = []
    received = duplicates = 0
    for report in reports:
        node = report.get("node", "")
        e2e = [float(v) for v in report.get("e2e_ms", [])]
        all_e2e.extend(e2e)
        node_e2e[node] = percentile_summary(e2e)
        for span in report.get("spans", []):
            for stage, ms in _span_stages(span).items():
                stage_samples.setdefault(stage, []).append(ms)
                if slowest is None or ms > slowest["ms"]:
                    slowest = {"node": node, "stage": stage, "ms": ms}
        for flood in report.get("floods", []):
            if flood.get("hop_ms") is not None:
                hop_ms.append(float(flood["hop_ms"]))
            hop_counts.append(int(flood.get("hop_count", 0)))
        flood_stats = report.get("flood", {})
        received += int(flood_stats.get("received", 0))
        duplicates += int(flood_stats.get("duplicates", 0))
    return {
        "nodes": len(reports),
        "spans_total": sum(len(r.get("spans", [])) for r in reports),
        "e2e_ms": percentile_summary(all_e2e),
        "node_e2e_ms": node_e2e,
        "stages": {
            stage: percentile_summary(samples)
            for stage, samples in sorted(stage_samples.items())
        },
        "slowest_stage": slowest,
        "slowest": _slowest_samples(
            sample for r in reports for sample in r.get("slowest") or []
        ),
        "rollup": _aggregate_rollups(reports),
        "flood": {
            "received": received,
            "duplicates": duplicates,
            "duplicate_ratio": duplicates / received if received else 0.0,
            "hop_ms": percentile_summary(hop_ms),
            "hop_count_max": max(hop_counts, default=0),
        },
    }
