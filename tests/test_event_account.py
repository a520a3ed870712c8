"""What interrupts an event is counted where it happens (ISSUE 37): the
collection watch's counters and their charge to the open stage, the event's
account closed by Fib (`convergence.unstaged_ms`), the slow rule with its
two counters and its warning, and the rollup's slowest spans, which outlive
the monitor's ring."""

import gc
import logging

import pytest

from openr_tpu.fib import fib as fib_module
from openr_tpu.fib.fib import Fib, FibConfig
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.monitor import spans
from openr_tpu.monitor.monitor import Monitor
from openr_tpu.monitor.report import node_convergence_report
from openr_tpu.platform import MockFibHandler

FULL, FULL_US = spans.GC_FULL_COLLECTIONS, spans.GC_FULL_PAUSE_US
YOUNG, YOUNG_US = spans.GC_YOUNG_COLLECTIONS, spans.GC_YOUNG_PAUSE_US
IN = spans.gc_charge_counter("")


class RecordedAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: the names entered."""

    entered = []

    def __init__(self, name, **kwargs):
        self.name = name

    def __enter__(self):
        self.entered.append(self.name)

    def __exit__(self, *exc):
        pass


@pytest.fixture(autouse=True)
def no_collection_but_the_tests_own(monkeypatch):
    """Only the collections a test forces: an automatic one would be
    counted, and so would one that an earlier test file's daemon left in
    the process's watch a moment ago."""
    monkeypatch.setattr(fib_module, "GC_WATCH", spans.GcWatch())
    gc.disable()
    yield
    gc.enable()


@pytest.fixture
def annotations(monkeypatch):
    RecordedAnnotation.entered = []
    monkeypatch.setattr(spans, "TraceAnnotation", RecordedAnnotation)
    return RecordedAnnotation.entered


@pytest.fixture
def watch():
    """A watch of its own, held for the test; the module's lists as found."""
    open_before = list(spans._OPEN_STAGES)
    callbacks_before = list(gc.callbacks)
    watch, owner = spans.GcWatch(), object()
    watch.acquire(owner)
    yield watch
    watch.release(owner)
    assert spans._OPEN_STAGES == open_before
    assert gc.callbacks == callbacks_before


def charges(watch):
    return {k[len(IN):]: v for k, v in watch.counters.items() if k.startswith(IN)}


# -- GcWatch -----------------------------------------------------------------


def test_every_counter_reads_0_right_after_acquire(watch):
    assert watch.counters == {
        FULL: 0, FULL_US: 0, YOUNG: 0, YOUNG_US: 0,
        IN + "decision.full_build": 0, IN + "decision.delta_build": 0,
        IN + "fib.program": 0, IN + "fib.apply": 0, IN + "none": 0,
    }
    assert not watch.pauses and not watch.histograms


@pytest.mark.parametrize("inside, charged", [
    ("decision.full_build", "decision.full_build"),
    (None, "none"),
    # a stage whose counter is not there from the start appears when charged
    ("decision.debounce", "decision.debounce"),
])
def test_a_full_collection_is_counted_timed_and_charged_to_the_open_stage(
    watch, annotations, inside, charged
):
    if inside is None:
        gc.collect(2)
    else:
        with spans.stage(inside, build=3):
            gc.collect(2)
    pause_us = watch.counters[FULL_US]
    assert watch.counters[FULL] == 1 and pause_us > 0
    assert watch.histograms["process.gc_ms"].count == 1
    assert pause_us == round(watch.histograms["process.gc_ms"].sum * 1e3)
    assert [name for name in annotations if name == "process.gc"] == ["process.gc"]
    by_stage = charges(watch)
    assert by_stage.pop(charged) == pause_us
    assert set(by_stage.values()) == {0}  # `none` among them, where a stage took it
    assert watch.counters[YOUNG] == 0 and watch.counters[YOUNG_US] == 0
    (began, ms), = watch.pauses
    assert round(ms * 1e3) == pause_us
    assert watch.full_pause_ms_between(began - 1.0, began + 1.0) == ms
    assert watch.full_pause_ms_between(began + 0.5, began + 1.0) == 0.0
    assert watch.full_pause_ms_between(began - 1.0, began - 0.5) == 0.0


def test_the_charge_goes_to_the_most_recently_started_open_stage_and_adds_up(watch):
    waiting = spans.stage("decision.debounce", build=4).start()  # open across callbacks
    with spans.stage("decision.ingest", build=4):
        gc.collect(2)
    gc.collect(2)  # only the wait is open
    waiting.stop()
    gc.collect(2)
    by_stage = charges(watch)
    assert by_stage["decision.ingest"] > 0 and by_stage["decision.debounce"] > 0
    assert by_stage["none"] > 0
    assert watch.counters[FULL] == 3
    assert sum(by_stage.values()) == watch.counters[FULL_US]


def test_a_young_collection_moves_only_the_young_pair_and_opens_no_annotation(
    watch, annotations
):
    with spans.stage("decision.full_build", build=5):
        del annotations[:]
        gc.collect(0)
        gc.collect(1)
    assert watch.counters[YOUNG] == 2 and watch.counters[YOUNG_US] >= 0
    assert watch.counters[FULL] == 0 and watch.counters[FULL_US] == 0
    assert set(charges(watch).values()) == {0}
    assert annotations == [] and not watch.histograms and not watch.pauses


def test_release_of_the_last_owner_takes_the_hook_away_and_keeps_the_counts():
    watch, first, second = spans.GcWatch(), object(), object()
    before = list(gc.callbacks)
    watch.acquire(first)
    watch.acquire(second)
    gc.collect(2)
    watch.release(first)
    assert len(gc.callbacks) == len(before) + 1
    watch.release(second)
    assert gc.callbacks == before and watch._open is None
    gc.collect(2)  # nobody holds it: not counted
    assert watch.counters[FULL] == 1
    watch.acquire(first)  # the process's counts go on, they do not restart
    assert watch.counters[FULL] == 1
    watch.release(first)
    assert gc.callbacks == before


# -- the open stages and the builds' intervals ----------------------------------


def test_open_stages_are_listed_between_start_and_stop():
    base = list(spans._OPEN_STAGES)
    outer = spans.stage("decision.debounce").start()
    with spans.stage("decision.ingest") as inner:
        assert spans._OPEN_STAGES == base + [outer, inner]
    assert spans._OPEN_STAGES == base + [outer]
    outer.stop()
    outer.stop()  # a second stop finds nothing to undo
    assert spans._OPEN_STAGES == base


def test_a_build_that_never_reaches_fib_is_evicted_at_the_65th(monkeypatch):
    monkeypatch.setattr(spans, "_BUILD_STAGES", {})
    for build in range(1, 65):
        with spans.stage("decision.ingest", build=build):
            pass
    assert list(spans._BUILD_STAGES) == list(range(1, 65))
    with spans.stage("decision.ingest", build=65):
        pass
    assert list(spans._BUILD_STAGES) == list(range(2, 66))  # the oldest went
    with spans.stage("decision.emit", build=40):  # a kept build gains, none goes
        pass
    assert [name for name, *_ in spans._BUILD_STAGES[40]] == [
        "decision.ingest", "decision.emit",
    ]
    assert len(spans._BUILD_STAGES) == 64
    # a stage without a build leaves nothing behind
    with spans.stage("kvstore.set_key_vals"):
        pass
    assert len(spans._BUILD_STAGES) == 64
    # taking a build's stages takes its entry out; one still open counts to `now`
    still_open = spans.stage("fib.program", build=40).start()
    taken = spans.take_build_stages(40, still_open._t0 + 0.25)
    assert [name for name, *_ in taken] == ["decision.ingest", "decision.emit", "fib.program"]
    assert taken[-1][2] - taken[-1][1] == pytest.approx(0.25)
    still_open.stop()
    assert 40 not in spans._BUILD_STAGES and len(spans._BUILD_STAGES) == 63
    # and no more than MAX_BUILD_STAGES of one build are kept
    for _ in range(spans.MAX_BUILD_STAGES + 10):
        with spans.stage("decision.ingest", build=7):
            pass
    assert len(spans._BUILD_STAGES[7]) == spans.MAX_BUILD_STAGES


# -- the account ---------------------------------------------------------------


def fake_span(marks, t0=100.0, build=9):
    """A span with marks at known times (seconds after t0)."""
    span = spans.Span("convergence", t0=t0)
    for name, at in marks:
        span.mark(name, ts=t0 + at)
    span.build = build
    return span


MARKS = [
    ("kvstore.publish", 0.0), ("decision.recv", 0.0004), ("decision.debounce", 0.0120),
    ("decision.route_build", 0.0300), ("fib.recv", 0.0303), ("fib.program", 0.0400),
]


def test_unstaged_is_the_total_less_the_union_of_stages_and_queue_hops():
    span = fake_span(MARKS)
    t0 = span.t0
    stages = [
        # the ingest runs on while the debounce is armed: 0.5 ms of overlap
        ("decision.ingest", t0 + 0.0003, t0 + 0.0015),
        ("decision.debounce", t0 + 0.0010, t0 + 0.0120),
        ("decision.spf.phase.relax", t0 + 0.0125, t0 + 0.0200),
        ("decision.full_build", t0 + 0.0210, t0 + 0.0290),
        # the route_build mark lies inside the emit: the hop overlaps its tail
        ("decision.emit", t0 + 0.0295, t0 + 0.0302),
        ("fib.apply", t0 + 0.0304, t0 + 0.0330),
        ("fib.program", t0 + 0.0335, t0 + 0.0400),
        # an ingest of the same build number from long before the event
        ("decision.ingest", t0 - 5.0, t0 - 4.9),
    ]
    # owned: [0, .0004] hop, [.0003, .0120], [.0125, .0200], [.0210, .0290],
    # [.0295, .0303] emit + hop, [.0304, .0330], [.0335, .0400]
    union_ms = 12.0 + 7.5 + 8.0 + 0.8 + 2.6 + 6.5
    total_ms = span.to_log_sample().get("total_ms")
    assert total_ms == pytest.approx(40.0)
    unstaged = span.unstaged_ms(stages)
    assert unstaged == pytest.approx(40.0 - union_ms, abs=1e-6)
    assert unstaged + union_ms == pytest.approx(total_ms, abs=1e-3)  # to the microsecond
    # the sum of the durations would take the overlaps off twice
    durations_ms = sum((hi - lo) * 1e3 for _, lo, hi in stages[:-1]) + 0.4 + 0.3
    assert durations_ms > union_ms
    # stages that cover more than the event never drive the rest below 0
    assert span.unstaged_ms([("decision.debounce", t0 - 1.0, t0 + 1.0)]) == 0.0
    # no stage at all: what the two hops do not own
    assert span.unstaged_ms([]) == pytest.approx(40.0 - 0.4 - 0.3, abs=1e-6)


def test_covered_s_clips_to_the_window_and_counts_overlaps_once():
    assert spans.covered_s([(1.0, 2.0), (1.5, 3.0), (5.0, 9.0)], 0.0, 6.0) == pytest.approx(3.0)
    assert spans.covered_s([], 0.0, 6.0) == 0.0
    assert spans.covered_s([(-3.0, -1.0), (7.0, 8.0)], 0.0, 6.0) == 0.0


# -- Fib closes the account and applies the slow rule ------------------------------


@pytest.fixture
def fib(monkeypatch):
    """A Fib that finishes spans into a list; no loop is needed for that."""
    monkeypatch.setattr(spans, "_BUILD_STAGES", {})
    samples = []
    fib = Fib(
        FibConfig(my_node_name="n", dryrun=True),
        MockFibHandler(),
        ReplicateQueue().get_reader(),
        log_sample_fn=samples.append,
    )
    fib.samples = samples
    return fib


class ClosingSpan(spans.Span):
    """A span whose closing mark falls at exactly t0 + `total_ms`."""

    def __init__(self, total_ms):
        super().__init__("convergence")
        self.t0 -= total_ms / 1e3
        self.end = self.t0 + total_ms / 1e3

    def mark(self, name, ts=None):
        return super().mark(name, ts=self.end if name == "fib.program" else ts)


def finish(fib, total_ms, build, notes=None, stages=()):
    """One finished span of `total_ms`, whose last stretch is fib.program."""
    span = ClosingSpan(total_ms)
    span.mark("kvstore.publish", ts=span.t0)
    span.mark("decision.recv", ts=span.t0 + 0.0002)
    span.build = build
    span.notes = dict(notes or {})
    for name, lo, hi in stages:
        spans._keep_build_stage(build, (name, span.t0 + lo, span.t0 + hi))
    fib._finish_span(span, 0.0)
    return fib.samples[-1].values()


def test_counters_are_present_at_0_and_the_account_rides_in_the_sample(fib):
    assert fib.counters["convergence.slow_events"] == 0
    assert fib.counters["convergence.slow_events_unexplained"] == 0
    values = finish(
        fib, 20.0, build=11,
        notes={"full_build": 1, "compile_misses": 0, "device_syncs": 2},
        stages=[("decision.full_build", 0.004, 0.012), ("fib.apply", 0.013, 0.015),
                ("fib.apply", 0.016, 0.017)],
    )
    assert values["event"] == "CONVERGENCE_TRACE" and values["build"] == 11
    assert values["total_ms"] == pytest.approx(20.0)
    assert values["full_build"] == 1 and values["device_syncs"] == 2
    assert values["stage.decision.full_build_ms"] == pytest.approx(8.0)
    assert values["stage.fib.apply_ms"] == pytest.approx(3.0)  # both stretches
    assert values["unstaged_ms"] == pytest.approx(20.0 - 0.2 - 8.0 - 3.0)
    assert values["gc_full_ms"] == 0.0 and values["slow"] == 0
    assert fib.histograms["convergence.unstaged_ms"].sum == pytest.approx(values["unstaged_ms"])
    assert fib.histograms["convergence.e2e_ms"].sum == pytest.approx(values["total_ms"])
    assert 11 not in spans._BUILD_STAGES  # taken out
    # the account's keys are no mark-to-mark stages of the report
    assert set(spans.sample_stage_durations(values)) == {
        "kvstore.publish", "decision.recv", "fib.program", "total",
    }


@pytest.mark.parametrize("last_ms, slow", [(31.0, True), (29.0, False)])
def test_slow_is_over_three_times_the_median_of_the_spans_before(fib, caplog, last_ms, slow):
    with caplog.at_level(logging.WARNING, logger=fib_module.__name__):
        for build in range(1, 41):
            assert finish(fib, 10.0, build)["slow"] == 0
        values = finish(fib, last_ms, 41, notes={"compile_misses": 0})
    assert values["slow"] == int(slow)
    assert fib.counters["convergence.slow_events"] == int(slow)
    # no collection inside and no compile: nothing explains it
    assert fib.counters["convergence.slow_events_unexplained"] == int(slow)
    warnings = [r.getMessage() for r in caplog.records if "slow event" in r.getMessage()]
    assert len(warnings) == int(slow)
    if slow:
        assert f"total {last_ms:.3f} ms" in warnings[0] and "build 41" in warnings[0]
        assert "longest decision.recv -> fib.program" in warnings[0]


def test_the_first_spans_are_never_slow(fib):
    for build, ms in enumerate([1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 500.0], start=1):
        assert finish(fib, ms, build)["slow"] == 0  # 7 before the 8th
    assert finish(fib, 500.0, 9)["slow"] == 1  # 8 have finished: median 1
    assert fib.counters["convergence.slow_events"] == 1


@pytest.mark.parametrize("why", ["collection", "compile"])
def test_a_slow_event_with_a_collection_or_a_compile_in_it_is_explained(
    fib, caplog, monkeypatch, why
):
    watch = spans.GcWatch()
    monkeypatch.setattr(fib_module, "GC_WATCH", watch)
    with caplog.at_level(logging.WARNING, logger=fib_module.__name__):
        for build in range(1, 41):
            finish(fib, 10.0, build)
        if why == "collection":
            # a full collection of 25 ms that started 30 ms ago: inside the event
            watch.pauses.append((spans.time.monotonic() - 0.030, 25.0))
            values = finish(fib, 40.0, 41)
            assert values["gc_full_ms"] == 25.0
        else:
            values = finish(fib, 40.0, 41, notes={"compile_misses": 1})
            assert values["gc_full_ms"] == 0.0
    assert values["slow"] == 1
    assert fib.counters["convergence.slow_events"] == 1
    assert fib.counters["convergence.slow_events_unexplained"] == 0
    assert not [r for r in caplog.records if "slow event" in r.getMessage()]


def test_an_unexplained_slow_event_is_logged_where_nobody_takes_samples(caplog):
    fib = Fib(
        FibConfig(my_node_name="n", dryrun=True), MockFibHandler(),
        ReplicateQueue().get_reader(),
    )
    fib.samples = [None]
    real = fib._finish_span
    with caplog.at_level(logging.WARNING, logger=fib_module.__name__):
        for build in range(1, 10):
            span = spans.Span("convergence")
            span.t0 -= 0.010
            real(span, 0.0)
        span = spans.Span("convergence")
        span.t0 -= 1.0
        real(span, 0.0)
    assert fib.counters["convergence.slow_events_unexplained"] == 1
    assert len([r for r in caplog.records if "slow event" in r.getMessage()]) == 1


# -- the rollup keeps the slowest ----------------------------------------------------


def test_200_spans_through_a_ring_of_100_still_return_the_slowest_8(fib):
    monitor = Monitor("n", max_event_log=100)
    totals = [10.0 + (i % 7) for i in range(200)]
    for at in (3, 50, 51, 77, 120, 160, 161, 199):
        totals[at] = 1000.0 + at  # eight that stand out, most of them early
    for i, total in enumerate(totals):
        finish(fib, total, i + 1, notes={"full_build": 0, "compile_misses": 0, "device_syncs": 1},
               stages=[("decision.delta_build", 0.001, 0.002)])
        monitor.add_event_log(fib.samples[-1])
    assert len(monitor.get_event_logs()) == 100
    assert monitor.counters["monitor.event_log_evictions"] == 100
    report = node_convergence_report("n", monitor)
    slowest = report["slowest"]
    assert [round(s["total_ms"]) for s in slowest] == [1199, 1161, 1160, 1120, 1077, 1051, 1050, 1003]
    assert [s["build"] for s in slowest] == [200, 162, 161, 121, 78, 52, 51, 4]
    ring_builds = {s["build"] for s in report["spans"]}
    assert {4, 51, 52, 78}.isdisjoint(ring_builds)  # the ring let them go
    for sample in slowest:
        assert sample["event"] == "CONVERGENCE_TRACE" and sample["node_name"] == "n"
        assert sample["stage.decision.delta_build_ms"] == pytest.approx(1.0)
        assert {"gc_full_ms", "unstaged_ms", "slow", "full_build", "compile_misses",
                "device_syncs", "fib.program_ms"} <= set(sample)
        assert "total 1" in spans.account_line(sample)
    # the windows of the snapshot hold theirs, slowest first, eight at most
    windows = report["rollup"]["windows"]
    assert sum(w["events"] for w in windows) == 200
    for window in windows:
        kept = [s["total_ms"] for s in window["slowest"]]
        assert kept == sorted(kept, reverse=True) and len(kept) <= 8
    assert max(s["total_ms"] for w in windows for s in w["slowest"]) == slowest[0]["total_ms"]
    # neither the account's keys nor the notes became stages of the aggregates
    assert set(monitor.rollup.cumulative) == {
        "kvstore.publish", "decision.recv", "fib.program", "total",
    }
