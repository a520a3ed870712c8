"""Convergence spans: structured stage traces of one LSDB event.

PerfEvents (types.py) ride LSDB values across nodes with wall-clock ms
stamps — right for cross-node convergence reports (`breeze perf view`),
wrong for local latency histograms: an NTP step mid-event skews every
duration derived from them. A Span is the local monotonic-clock sibling of
that trace: created when Decision keeps the oldest event of a debounce
batch (seeded from the KvStore publication stamp when one rode along),
marked at each pipeline stage —

    spark.neighbor_event → linkmonitor.adj_advertised
    → [kvstore.flood.origin → kvstore.flood.hop1..k]   (remote events)
    → kvstore.publish → decision recv → debounce fire → route build
    → fib recv → fib program

— and finished by Fib once routes are programmed. The pre-publish stages
arrive either as monotonic `Publication.span_stages` marks (the local
origin chain) or are reconstructed from wall-clock PerfEvents (flood-hop
traces from remote nodes); from kvstore.publish on, every mark is taken
live on this process's monotonic clock. Stage durations feed the `*_ms`
histograms (decision.debounce_ms, decision.spf.solve_ms, fib.program_ms,
convergence.e2e_ms) and the finished span is emitted as one
CONVERGENCE_TRACE LogSample through the monitor queue.

A Span times whole layers from outside. `stage` times one stretch of work
where it happens: the duration goes into the owning module's histogram
and, while a profiler runs, the same stretch lands in the trace's host
plane on the profiler's own clock, the clock of the device plane.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from openr_tpu.monitor.monitor import LogSample
from openr_tpu.utils.counters import Histogram, observe

SPAN_EVENT = "CONVERGENCE_TRACE"

# finished-span sample keys that are not per-stage durations ("total_ms"
# is the end-to-end duration, exposed as the "total" pseudo-stage)
_NON_STAGE_KEYS = {"event", "span", "node_name"}


def sample_stage_durations(values: Dict[str, float]) -> Dict[str, float]:
    """stage -> ms from one finished span's LogSample value map (the
    CONVERGENCE_TRACE export shape produced by Span.to_log_sample).
    Shared by the point-in-time convergence report and the windowed
    rollup so both read the same stage vocabulary; the end-to-end
    `total_ms` field maps to the `total` pseudo-stage."""
    out: Dict[str, float] = {}
    for key, value in values.items():
        if (
            key.endswith("_ms")
            and key not in _NON_STAGE_KEYS
            and isinstance(value, (int, float))
        ):
            out[key[: -len("_ms")]] = float(value)
    return out


class stage:
    """One named stretch of the served path, as a context manager.

    On exit the duration is recorded into `histograms["<name>_ms"]` (the
    dict of the module that owns the stretch; None records nowhere: the
    stretch already feeds a histogram of its own) and kept as `.ms`.
    Around the same stretch it holds a `jax.profiler.TraceAnnotation` of
    the same name: under a microsecond while no profiler runs, a host
    event on the profiler's clock while one does (`chipbench --trace 1`,
    ctrl `startProfile`). A span's name is its histogram's name without
    `_ms`.

    Spans that reach the profiler tile an event and do not nest: a reader
    names an idle gap of the device by the span that overlaps it most, so
    an umbrella span would name every gap. `build` (Decision's
    `decision.route_build_runs` for the event) is the identifier that one
    event's spans share. A stretch that ends in another callback than it
    began in (the debounce wait) calls `start()` / `stop()` itself.
    """

    __slots__ = ("name", "histograms", "ms", "_t0", "_annotation")

    def __init__(
        self,
        name: str,
        histograms: Optional[Dict[str, Histogram]] = None,
        build: Optional[int] = None,
    ) -> None:
        self.name = name
        self.histograms = histograms
        self.ms = 0.0
        self._t0 = 0.0
        self._annotation = (
            TraceAnnotation(name)
            if build is None
            else TraceAnnotation(name, build=build)
        )

    def start(self) -> "stage":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def stop(self) -> float:
        """Ends the stretch; returns its milliseconds."""
        self.ms = (time.perf_counter() - self._t0) * 1e3
        self._annotation.__exit__(None, None, None)
        if self.histograms is not None:
            observe(self.histograms, f"{self.name}_ms", self.ms)
        return self.ms

    __enter__ = start

    def __exit__(self, *exc) -> None:
        self.stop()


class GcWatch:
    """`process.gc`: every full (generation 2) collection of this process
    as a stage. Collections are the process's, so there is one watch
    (`GC_WATCH`); each daemon registers it with its monitor as a module,
    `acquire`s it on start and `release`s it on stop, and the hook sits
    in `gc.callbacks` while any owner holds it."""

    def __init__(self) -> None:
        self.histograms: Dict[str, Histogram] = {}
        self._owners: set = set()
        self._open: Optional[stage] = None

    def acquire(self, owner: object) -> None:
        if not self._owners:
            gc.callbacks.append(self._on_gc)
        self._owners.add(id(owner))

    def release(self, owner: object) -> None:
        """No-op for an owner that does not hold the watch."""
        if id(owner) not in self._owners:
            return
        self._owners.discard(id(owner))
        if not self._owners:
            gc.callbacks.remove(self._on_gc)
            self._open = None

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._open = stage("process.gc", self.histograms).start()
        elif self._open is not None:
            self._open.stop()
            self._open = None


GC_WATCH = GcWatch()


class Span:
    """Ordered (stage, monotonic-ts) marks over one event's pipeline pass.

    Spans never cross a process boundary (monotonic clocks don't compare
    across hosts) — they ride in-process queue payloads only, as the
    `span` attribute next to `perf_events`.
    """

    __slots__ = ("name", "t0", "marks", "build")

    def __init__(self, name: str, t0: Optional[float] = None) -> None:
        self.name = name
        self.t0 = time.monotonic() if t0 is None else t0
        self.marks: List[Tuple[str, float]] = []
        # Decision's route build number for this event, once it has one:
        # the identifier the event's profiler stages share (`stage`)
        self.build: Optional[int] = None

    def mark(self, stage: str, ts: Optional[float] = None) -> float:
        """Append a stage boundary; returns the stage's duration in ms
        (time since the previous mark, or since t0 for the first).

        `ts` replays a mark that already happened at a known monotonic
        time — the span-stage handoff (Publication.span_stages) and the
        reconstructed flood-hop stages use it. Marks are kept monotonic:
        a ts behind the previous mark (reconstruction jitter, cross-host
        wall-clock skew) is clamped to it, yielding a zero-length stage
        rather than a negative one."""
        now = time.monotonic() if ts is None else ts
        prev = self.marks[-1][1] if self.marks else self.t0
        if now < prev:
            now = prev
        self.marks.append((stage, now))
        return (now - prev) * 1e3

    def elapsed_ms(self) -> float:
        """End-to-end ms since the span started (t0 → now)."""
        return (time.monotonic() - self.t0) * 1e3

    def stage_durations_ms(self) -> Dict[str, float]:
        """stage -> ms from the previous mark (t0 for the first)."""
        out: Dict[str, float] = {}
        prev = self.t0
        for stage, ts in self.marks:
            out[stage] = (ts - prev) * 1e3
            prev = ts
        return out

    def to_log_sample(self) -> LogSample:
        sample = LogSample()
        sample.add_string("event", SPAN_EVENT)
        sample.add_string("span", self.name)
        total = 0.0
        for stage, ms in self.stage_durations_ms().items():
            sample.add_double(f"{stage}_ms", ms)
            total += ms
        sample.add_double("total_ms", total)
        return sample
