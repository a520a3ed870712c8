"""The twelve per-layer metrics of ISSUE 37: what interrupts an event, read
where the program counts it. Each entry and its file agree, in the form that
stays true when a later PR appends again; every one reads a counter that is
present at 0 or a histogram that every event fills, so a sound run leaves
none out, and a parent without them leaves out exactly these. One traced CPU
rehearsal prints all twelve (a rehearsal: nothing here is a device number)
and its trace's host plane holds one `fib.apply` event an event, under the
event's build, and none for a young collection."""

import json
import os

import pytest

from chipbench import layer_metrics, trace_reduce
from chipbench import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PROCESS = "process (Python runtime)"
P50, RATE = "event_to_fib_ms.p50", "events_per_s"
CHARGE = "process.gc.full_pause_us_in."
# index, name, unit, layer, moves, the file's source
ENTRIES = [
    (51, "gc_full_collections_in_window", "collections", PROCESS, RATE,
     {"counter_delta": "process.gc.full_collections", "per": "window"}),
    (52, "gc_full_pause_us_per_event", "us", PROCESS, RATE,
     {"counter_delta": "process.gc.full_pause_us", "per": "event"}),
    (53, "gc_young_collections_per_event", "collections", PROCESS, RATE,
     {"counter_delta": "process.gc.young_collections", "per": "event"}),
    (54, "gc_young_pause_us_per_event", "us", PROCESS, RATE,
     {"counter_delta": "process.gc.young_pause_us", "per": "event"}),
    (55, "gc_full_pause_us_in_full_build_per_event", "us", "route build", P50,
     {"counter_delta": CHARGE + "decision.full_build", "per": "event"}),
    (56, "gc_full_pause_us_in_delta_build_per_event", "us", "route build", P50,
     {"counter_delta": CHARGE + "decision.delta_build", "per": "event"}),
    (57, "gc_full_pause_us_in_fib_program_per_event", "us", "Fib program", P50,
     {"counter_delta": CHARGE + "fib.program", "per": "event"}),
    (58, "fib_apply_ms.avg", "ms", "Fib program", P50,
     {"histogram": "fib.apply_ms", "stat": "avg"}),
    (59, "fib_queue_wait_ms.avg", "ms", "Fib program", P50,
     {"histogram": "fib.queue_wait_ms", "stat": "avg"}),
    (60, "event_unstaged_ms.avg", "ms", "ctrl RPC / KvStore merge + publish", P50,
     {"histogram": "convergence.unstaged_ms", "stat": "avg"}),
    (61, "slow_events_in_window", "events", PROCESS, RATE,
     {"counter_delta": "convergence.slow_events", "per": "window"}),
    (62, "slow_events_unexplained_in_window", "events", PROCESS, RATE,
     {"counter_delta": "convergence.slow_events_unexplained", "per": "window"}),
]
NAMES = [name for _, name, *_ in ENTRIES]
# the seven cells in `workloads`' order when these came
CELLS = [
    "fabric9976.metric_flaps", "grid10000.metric_flaps", "fabric9976.prefix_churn",
    "fabric9976.own_link_flaps", "fabric9976_ssw.metric_flaps",
    "fabric9976_ssw.own_link_flaps", "wan65536.listed_metric_flaps",
]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _context(counters0, counters1, hists=None):
    return layer_metrics.Context(
        hists=hists or {}, counters0=counters0, counters1=counters1, n_events=8,
        gauges={}, trace=None, config={}, device_kind="cpu",
    )


def test_the_twelve_follow_label_sets_made_and_nothing_before_them_moved():
    bench = _bench()
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("label_sets_made_per_event") == 50
    assert names[51:63] == NAMES
    assert [w["name"] for w in bench["workloads"]][: len(CELLS)] == CELLS
    # gc_pause_ms.max stays beside them on the one cell whose window always
    # holds full collections; the three whose window may hold none left it
    pause = next(m for m in bench["per_layer"] if m["name"] == "gc_pause_ms.max")
    assert pause["workloads"] == ["fabric9976.own_link_flaps"]
    # none moves the p95, which two cells do not report
    assert {m["moves"] for m in bench["per_layer"][51:63]} == {P50, RATE}


@pytest.mark.parametrize("index, name, unit, layer, moves, source", ENTRIES, ids=NAMES)
def test_entry_and_file_read_what_the_program_counts(index, name, unit, layer, moves, source):
    entry = dict(_bench()["per_layer"][index])
    assert entry.pop("workloads")[: len(CELLS)] == CELLS
    assert entry == {
        "name": name, "unit": unit, "better": "lower", "layer": layer, "moves": moves,
        "source": "program_span" if "histogram" in source else "program_counter",
    }
    spec = bench_run.load_json("metrics", name + ".json")
    assert spec == {"name": name, "layer": layer, "unit": unit, "moves": moves, "source": source}
    if "counter_delta" in source:
        counter = source["counter_delta"]
        moved = 16 if source["per"] == "window" else 2
        assert layer_metrics.read(spec, _context({counter: 8}, {counter: 24}))[0] == moved
        # present at 0 from the start: a window in which it stood still reads 0
        value, _ = layer_metrics.read(spec, _context({counter: 0}, {counter: 0}))
        assert value == 0 and value is not None
        # a program without the counter (this PR's parent): left out, no error
        value, note = layer_metrics.read(spec, _context({}, {}))
        assert value is None and note == f"counter {counter} does not exist"
    else:
        hist = source["histogram"]
        read = layer_metrics.read(spec, _context({}, {}, {hist: {"count": 4, "avg": 1.25}}))
        assert read == (1.25, "over 4 samples")
        value, note = layer_metrics.read(spec, _context({}, {}))
        assert value is None and note == f"histogram {hist} has no sample"


def test_traced_rehearsal_prints_all_twelve_and_fib_apply_lies_on_the_host_plane(
    capsys, monkeypatch, tmp_path
):
    monkeypatch.setattr(bench_run, "TRACE_DIR", str(tmp_path / "trace"))
    host = []  # (name, start_ns, build or None) of the trace's host plane
    read_trace = trace_reduce.read_trace

    def keeping_the_host_plane(trace_dir, window_s):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(trace_reduce._trace_file(trace_dir))
        for plane in data.planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        stats = dict(ev.stats)
                        host.append((ev.name, ev.start_ns, stats.get("build")))
        return read_trace(trace_dir, window_s)

    monkeypatch.setattr(trace_reduce, "read_trace", keeping_the_host_plane)
    rc = bench_run.main(
        ["--workload", "rehearsal_fabric.own_link_flaps", "--seed", str(2**31 + 371),
         "--seconds", "1.5", "--allow-cpu", "--trace", "1"]
    )
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    assert [name for name in NAMES if name not in metrics] == []
    for name in NAMES:
        assert metrics[name]["value"] >= 0
    # the charges add up to the pause, and no event here is a delta build
    events = line["attempted"]
    charged = sum(
        metrics[name]["value"] for name in NAMES if name.startswith("gc_full_pause_us_in_")
    )
    assert charged <= metrics["gc_full_pause_us_per_event"]["value"] + 1e-9
    assert metrics["gc_full_pause_us_in_delta_build_per_event"]["value"] == 0
    if "gc_pause_ms.max" in metrics:  # the histogram's count is the counter
        count = metrics["gc_full_collections_in_window"]["value"]
        assert f"gc_pause_ms.max: over {count} samples" in err
    else:
        assert metrics["gc_full_collections_in_window"]["value"] == 0
    assert metrics["fib_apply_ms.avg"]["value"] > 0
    assert metrics["fib_queue_wait_ms.avg"]["value"] > 0
    assert metrics["event_unstaged_ms.avg"]["value"] < metrics["kvstore_to_fib_ms.avg"]["value"]
    assert events >= 8

    # the traced second's host plane: one fib.apply an event, between the
    # event's full build and its fib.program, under the same build
    def of(name):
        return sorted((start, build) for n, start, build in host if n == name)

    applies, builds, programs = of("fib.apply"), of("decision.full_build"), of("fib.program")
    assert len(applies) >= 8 and len(applies) == len(programs)
    assert abs(len(builds) - len(applies)) <= 1  # the event the trace ends in
    assert all(build is not None for _, build in applies)
    assert len({build for _, build in applies}) == len(applies)
    full_at = {build: start for start, build in builds}
    program_at = {build: start for start, build in programs}
    for start, build in applies:
        assert program_at[build] > start
        if build in full_at:
            assert full_at[build] < start
    # a young collection is two clock reads and no host event; a full one is
    # `process.gc` as before
    names = {name for name, *_ in host}
    assert not {n for n in names if "young" in n}
    assert {n for n in names if n.startswith("process.")} <= {"process.gc"}
