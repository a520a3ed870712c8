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

What interrupts an event is counted where it happens. `GcWatch` counts and
times every collection of the process and charges a full one to the stage
it cut into (the stages that are open are a list of this module); a stage
that ran under an event's build leaves its interval behind, so that the
Span's finisher can say how much of the event no stage and no queue hop
owns (`Span.unstaged_ms`, Fib's `convergence.unstaged_ms`).
"""

from __future__ import annotations

import gc
import time
from collections import deque
from typing import Deque, Dict, Iterable, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from openr_tpu.monitor.monitor import LogSample
from openr_tpu.utils.counters import Histogram, observe

SPAN_EVENT = "CONVERGENCE_TRACE"

# finished-span sample keys that are not per-stage durations ("total_ms"
# is the end-to-end duration, exposed as the "total" pseudo-stage). The
# event's account (Fib._finish_span) is no mark-to-mark stage either: the
# pauses inside the event, what no stage owns, and each `stage` that ran
# under the event's build as `stage.<name>_ms`
_NON_STAGE_KEYS = {"event", "span", "node_name", "gc_full_ms", "unstaged_ms"}
ACCOUNT_STAGE_PREFIX = "stage."

# the stages that are started and not stopped, oldest first: one or two,
# since stages tile and only the debounce wait stays open across callbacks
_OPEN_STAGES: List["stage"] = []
# build -> (name, start, end on time.monotonic) of the stages that ran
# under it, until the event's span finishes and takes them out
_BUILD_STAGES: Dict[int, Deque[Tuple[str, float, float]]] = {}
MAX_BUILDS = 64  # the newest kept; a build that never reaches Fib goes
MAX_BUILD_STAGES = 256  # of one build (many publications in one debounce)


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
            and not key.startswith(ACCOUNT_STAGE_PREFIX)
            and isinstance(value, (int, float))
        ):
            out[key[: -len("_ms")]] = float(value)
    return out


def account_line(values: Dict[str, object]) -> str:
    """One finished span's account on one line, from its sample's value
    map: the total, the full collections inside it, what no stage owns,
    between which marks the largest share fell, and the stages that ran
    under its build. `breeze perf report` prints it for the slowest
    events of a window, and Fib logs it for a slow event that nothing
    explains."""
    marks = sample_stage_durations(values)
    total = marks.pop("total", 0.0)
    parts = [
        f"total {total:.3f} ms",
        f"build {values.get('build', '-')}"
        f" ({'full' if values.get('full_build') else 'delta'} build)",
        f"gc_full {values.get('gc_full_ms', 0.0):.3f}",
        f"unstaged {values.get('unstaged_ms', 0.0):.3f}",
        f"compile_misses {values.get('compile_misses', 0)}",
        f"device_syncs {values.get('device_syncs', 0)}",
    ]
    if marks:
        names = list(marks)
        longest = max(names, key=marks.__getitem__)
        at = names.index(longest)
        parts.append(
            f"longest {names[at - 1] if at else 'start'} -> {longest} "
            f"{marks[longest]:.3f}"
        )
    stages = [
        f"{key[len(ACCOUNT_STAGE_PREFIX):-len('_ms')]} {ms:.3f}"
        for key, ms in values.items()
        if key.startswith(ACCOUNT_STAGE_PREFIX)
    ]
    if stages:
        parts.append("stages " + ", ".join(stages))
    return "; ".join(parts)


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

    Between `start()` and `stop()` the stage is in the module's list of
    open stages: a full collection is charged to the last of them
    (`GcWatch`). A stage with a `build` leaves `(name, start, end)` under
    it when it stops (`take_build_stages`), on `time.monotonic`, the
    Span's clock, so that the intervals compare with the span's marks.
    """

    __slots__ = ("name", "histograms", "ms", "build", "_t0", "_annotation")

    def __init__(
        self,
        name: str,
        histograms: Optional[Dict[str, Histogram]] = None,
        build: Optional[int] = None,
    ) -> None:
        self.name = name
        self.histograms = histograms
        self.ms = 0.0
        self.build = build
        self._t0 = 0.0
        self._annotation = (
            TraceAnnotation(name)
            if build is None
            else TraceAnnotation(name, build=build)
        )

    def start(self) -> "stage":
        self._annotation.__enter__()
        _OPEN_STAGES.append(self)
        self._t0 = time.monotonic()
        return self

    def stop(self) -> float:
        """Ends the stretch; returns its milliseconds."""
        end = time.monotonic()
        self.ms = (end - self._t0) * 1e3
        self._annotation.__exit__(None, None, None)
        try:
            _OPEN_STAGES.remove(self)
        except ValueError:
            pass  # stopped twice, or never started
        if self.build is not None:
            _keep_build_stage(self.build, (self.name, self._t0, end))
        if self.histograms is not None:
            observe(self.histograms, f"{self.name}_ms", self.ms)
        return self.ms

    __enter__ = start

    def __exit__(self, *exc) -> None:
        self.stop()


def _keep_build_stage(build: int, interval: Tuple[str, float, float]) -> None:
    kept = _BUILD_STAGES.get(build)
    if kept is None:
        kept = _BUILD_STAGES[build] = deque(maxlen=MAX_BUILD_STAGES)
        if len(_BUILD_STAGES) > MAX_BUILDS:
            del _BUILD_STAGES[next(iter(_BUILD_STAGES))]
    kept.append(interval)


def take_build_stages(build: int, now: float) -> List[Tuple[str, float, float]]:
    """`(name, start, end)` of every stage that ran under `build`, taken
    out of the map. A stage of the build that is still open (a span
    finishes inside `fib.program`) counts up to `now` and leaves nothing
    behind when it stops. Builds are numbered by each Decision: a process
    that holds several daemons (the emulator) mixes the stages of builds
    of one number, and an account there reads low."""
    taken = list(_BUILD_STAGES.pop(build, ()))
    for open_stage in _OPEN_STAGES:
        if open_stage.build == build:
            taken.append((open_stage.name, open_stage._t0, now))
            open_stage.build = None
    return taken


def covered_s(
    intervals: Iterable[Tuple[float, float]], start: float, end: float
) -> float:
    """Seconds of [start, end] that lie in the union of `intervals`."""
    covered = 0.0
    reach = start
    for lo, hi in sorted(intervals):
        lo, hi = max(lo, reach), min(hi, end)
        if hi > lo:
            covered += hi - lo
            reach = hi
    return covered


GC_FULL_COLLECTIONS = "process.gc.full_collections"
GC_FULL_PAUSE_US = "process.gc.full_pause_us"
GC_YOUNG_COLLECTIONS = "process.gc.young_collections"
GC_YOUNG_PAUSE_US = "process.gc.young_pause_us"


def gc_charge_counter(stage_name: str) -> str:
    """The counter that holds the full collections' pauses charged to the
    stage they cut into (`none`: no stage was open)."""
    return f"process.gc.full_pause_us_in.{stage_name}"


# the stages whose charge is present at 0 from `acquire`; any other
# stage's counter appears when it is first charged
GC_CHARGED_FROM_START = (
    "decision.full_build",
    "decision.delta_build",
    "fib.program",
    "fib.apply",
    "none",
)


class GcWatch:
    """`process.gc`: every collection of this process, counted where it
    happens. Collections are the process's, so there is one watch
    (`GC_WATCH`); each daemon registers it with its monitor as the module
    `process`, `acquire`s it on start and `release`s it on stop, and the
    hook sits in `gc.callbacks` while any owner holds it.

    A full (generation 2) collection is a stage (`process.gc`, histogram
    `process.gc_ms`, a host event while a profiler runs), one bump of
    `process.gc.full_collections`, its pause in whole microseconds in
    `process.gc.full_pause_us`, and the same microseconds once more in
    `process.gc.full_pause_us_in.<stage>`: the most recently started
    stage that was open when the collection started, `none` where none
    was. So the charges add up to the pause, and a stage's histogram less
    its charge is the stage without the collections that fell into it.
    (Charged to `decision.debounce` means: triggered by whatever ran on
    the loop during the wait.) Generations 0 and 1 together are
    `process.gc.young_collections` and `process.gc.young_pause_us`, timed
    by two clock reads and with no profiler event: an event that leaves
    DeltaPath runs on the order of a hundred. All are present at 0 from
    the first `acquire`. `pauses` keeps the last 256 full collections as
    (start on time.monotonic, ms) for `full_pause_ms_between`."""

    def __init__(self) -> None:
        self.histograms: Dict[str, Histogram] = {}
        self.counters: Dict[str, int] = {}
        self.pauses: Deque[Tuple[float, float]] = deque(maxlen=256)
        self._owners: set = set()
        self._open: Optional[stage] = None
        self._cut_into = "none"  # the stage the open collection is charged to
        self._young_t0 = 0.0

    def acquire(self, owner: object) -> None:
        if not self._owners:
            for name in (
                GC_FULL_COLLECTIONS,
                GC_FULL_PAUSE_US,
                GC_YOUNG_COLLECTIONS,
                GC_YOUNG_PAUSE_US,
                *map(gc_charge_counter, GC_CHARGED_FROM_START),
            ):
                self.counters.setdefault(name, 0)
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

    def full_pause_ms_between(self, start: float, end: float) -> float:
        """Milliseconds of the full collections that started in
        [start, end] on time.monotonic (of the last 256)."""
        total = 0.0
        for began, ms in reversed(self.pauses):
            if began < start:
                break
            if began <= end:
                total += ms
        return total

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        counters = self.counters
        if info.get("generation") != 2:
            if phase == "start":
                self._young_t0 = time.perf_counter()
            else:
                counters[GC_YOUNG_COLLECTIONS] += 1
                counters[GC_YOUNG_PAUSE_US] += round(
                    (time.perf_counter() - self._young_t0) * 1e6
                )
        elif phase == "start":
            self._cut_into = _OPEN_STAGES[-1].name if _OPEN_STAGES else "none"
            self._open = stage("process.gc", self.histograms).start()
        elif self._open is not None:
            ms = self._open.stop()
            self.pauses.append((self._open._t0, ms))
            self._open = None
            pause_us = round(ms * 1e3)
            counters[GC_FULL_COLLECTIONS] += 1
            counters[GC_FULL_PAUSE_US] += pause_us
            charged = gc_charge_counter(self._cut_into)
            counters[charged] = counters.get(charged, 0) + pause_us


GC_WATCH = GcWatch()


class Span:
    """Ordered (stage, monotonic-ts) marks over one event's pipeline pass.

    Spans never cross a process boundary (monotonic clocks don't compare
    across hosts) — they ride in-process queue payloads only, as the
    `span` attribute next to `perf_events`.
    """

    __slots__ = ("name", "t0", "marks", "build", "notes")

    def __init__(self, name: str, t0: Optional[float] = None) -> None:
        self.name = name
        self.t0 = time.monotonic() if t0 is None else t0
        self.marks: List[Tuple[str, float]] = []
        # Decision's route build number for this event, once it has one:
        # the identifier the event's profiler stages share (`stage`)
        self.build: Optional[int] = None
        # what Decision knows of the build and the marks do not say
        # (full_build, compile_misses, device_syncs): whole numbers that
        # ride into the finished sample under their names
        self.notes: Dict[str, int] = {}

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

    def unstaged_ms(self, stages: Iterable[Tuple[str, float, float]]) -> float:
        """Milliseconds of t0 → the last mark that nothing owns: neither
        one of `stages` (the `(name, start, end)` that ran under the
        event's build, `take_build_stages`) nor a stretch that ends at a
        `*.recv` mark (t0 → `decision.recv`: the pre-publish chain and
        KvStore's queue hop; the mark before → `fib.recv`: Fib's queue
        hop). `decision.route_build` is marked inside `decision.emit`,
        and a batch's later ingests run inside the debounce wait, so
        stretches overlap: what is taken off is their union, and the
        union and the rest add up to `total_ms`."""
        end = self.marks[-1][1] if self.marks else self.t0
        owned = [(lo, hi) for _, lo, hi in stages]
        prev = self.t0
        for name, ts in self.marks:
            if name == "decision.recv":
                owned.append((self.t0, ts))
            elif name == "fib.recv":
                owned.append((prev, ts))
            prev = ts
        rest = (end - self.t0) - covered_s(owned, self.t0, end)
        return max(0.0, rest) * 1e3

    def to_log_sample(self) -> LogSample:
        sample = LogSample()
        sample.add_string("event", SPAN_EVENT)
        sample.add_string("span", self.name)
        total = 0.0
        for stage, ms in self.stage_durations_ms().items():
            sample.add_double(f"{stage}_ms", ms)
            total += ms
        sample.add_double("total_ms", total)
        if self.build is not None:
            sample.add_int("build", self.build)
        for name, value in self.notes.items():
            sample.add_int(name, value)
        return sample
