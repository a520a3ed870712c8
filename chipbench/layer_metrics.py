"""Per-layer metrics, read by the kind of source their file names.

A metric is `metrics/<name>.json`: `layer`, `unit`, `moves` and a `source`,
one of

  {"histogram": <name>, "stat": "p50"|"p95"|"p99"|"avg"|"max"|"count"}
      the daemon's histogram over the window (ctrl getHistograms, reset at
      the window's start)
  {"counter_delta": <name>, "per": "event"|"window"}
      how far the counter moved inside the window (ctrl getCounters)
  {"gauge_mean": <name>}
      a counter the daemon overwrites on every solve, read in-process at
      each completed event; the mean over the window
  {"trace": <reducer>, ...}
      chipbench/reducers/<reducer>.py, `reduce(ctx, source) -> number or
      None`, over the profiler trace of the window; further keys of the
      source are the reducer's own parameters

A reader that finds nothing to read returns None, and the run's line
leaves the metric out; it never stands a 0 in for a missing reading.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass
class Context:
    hists: Dict[str, dict]
    counters0: Dict[str, int]
    counters1: Dict[str, int]
    n_events: int
    gauges: Dict[str, List[float]]
    trace: object  # trace_reduce.TraceSummary
    config: dict
    device_kind: str


def gauges_wanted(per_layer: List[dict]) -> List[str]:
    """Names of the gauges that this cell's metric files read (each entry
    holds its file's contents under "spec")."""
    return [
        metric["spec"]["source"]["gauge_mean"]
        for metric in per_layer
        if "gauge_mean" in metric["spec"]["source"]
    ]


def read(spec: dict, ctx: Context) -> Tuple[Optional[float], str]:
    """(value or None, a note for standard error or '')."""
    source = spec["source"]
    if "histogram" in source:
        hist = ctx.hists.get(source["histogram"])
        if not hist or not hist.get("count"):
            return None, f"histogram {source['histogram']} has no sample"
        return hist[source["stat"]], f"over {hist['count']} samples"
    if "counter_delta" in source:
        name = source["counter_delta"]
        if name not in ctx.counters1:
            return None, f"counter {name} does not exist"
        delta = ctx.counters1[name] - ctx.counters0.get(name, 0)
        if source["per"] == "event":
            if not ctx.n_events:
                return None, "no event in the window"
            return delta / ctx.n_events, ""
        return delta, ""
    if "gauge_mean" in source:
        values = ctx.gauges.get(source["gauge_mean"])
        if not values:
            return None, f"gauge {source['gauge_mean']} never read"
        return sum(values) / len(values), f"over {len(values)} readings"
    if "trace" in source:
        reducer = importlib.import_module(f"chipbench.reducers.{source['trace']}")
        return reducer.reduce(ctx, source), ""
    raise ValueError(f"unknown source kind in {spec}")
