"""`table_reads_per_event` (ISSUE 38): its entry and its file agree, in the
form that stays true when a later PR appends again, and traced CPU
rehearsals read it (rehearsals: nothing here is a device number). A route
build asks the next-hop table for all its plain unicast routes in one read
and for each node-label route in one more, so the reads stay under the
routes that `table_routes_per_event` counts: by the unicast routes less
one. A program without the counter leaves the metric out."""

import json
import os

import pytest

from chipbench import layer_metrics
from chipbench import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "table_reads_per_event"
COUNTER = "decision.route_build_table_reads"
# the seven cells in `workloads`' order when this came
CELLS = [
    "fabric9976.metric_flaps", "grid10000.metric_flaps", "fabric9976.prefix_churn",
    "fabric9976.own_link_flaps", "fabric9976_ssw.metric_flaps",
    "fabric9976_ssw.own_link_flaps", "wan65536.listed_metric_flaps",
]


def _context(counters0, counters1):
    return layer_metrics.Context(
        hists={}, counters0=counters0, counters1=counters1, n_events=8,
        gauges={}, trace=None, config={}, device_kind="cpu",
    )


def test_entry_and_file_read_the_programs_counter_per_event():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    names = [m["name"] for m in bench["per_layer"]]
    # appended behind PR 37's last; nothing before it moved
    assert names.index(NAME) == names.index("slow_events_unexplained_in_window") + 1 == 63
    entry = dict(bench["per_layer"][63])
    assert entry.pop("workloads")[: len(CELLS)] == CELLS
    assert entry == {
        "name": NAME, "unit": "reads", "better": "lower",
        "source": "program_counter", "layer": "route build",
        "moves": "event_to_fib_ms.p50",
    }
    # beside the routes it is read against, in the same cells
    routes = next(m for m in bench["per_layer"] if m["name"] == "table_routes_per_event")
    assert routes["workloads"][: len(CELLS)] == CELLS
    spec = bench_run.load_json("metrics", NAME + ".json")
    assert spec == {
        "name": NAME, "layer": "route build", "unit": "reads",
        "moves": "event_to_fib_ms.p50",
        "source": {"counter_delta": COUNTER, "per": "event"},
    }
    assert layer_metrics.read(spec, _context({COUNTER: 8}, {COUNTER: 24}))[0] == 2
    # there from the solver's start: a window that asked no table reads 0
    assert layer_metrics.read(spec, _context({COUNTER: 8}, {COUNTER: 8}))[0] == 0


def test_a_program_without_the_counter_leaves_the_metric_out():
    spec = bench_run.load_json("metrics", NAME + ".json")
    # this PR's parent counts the routes and not the reads
    parent = {"decision.route_build_table_routes": 80}
    value, note = layer_metrics.read(spec, _context(parent, parent))
    assert value is None and COUNTER in note


@pytest.mark.parametrize("cell, seed, unicast_share", [
    # every event a full build of the toy's 37 prefixes and 37 foreign
    # labels: one read for the prefixes, one for each label
    ("rehearsal_fabric.own_link_flaps", 2**31 + 381, 0.45),
    # a delta build: one read for the changed columns' prefixes together,
    # one for each of their labels
    ("rehearsal_fabric.metric_flaps", 2**31 + 382, 0.2),
])
def test_traced_rehearsal_reads_fewer_than_its_routes(
    cell, seed, unicast_share, capsys, monkeypatch, tmp_path
):
    monkeypatch.setattr(bench_run, "TRACE_DIR", str(tmp_path / "trace"))
    rc = bench_run.main(
        ["--workload", cell, "--seed", str(seed), "--seconds", "1.5",
         "--allow-cpu", "--trace", "1"]
    )
    out, _ = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    metrics = line["metrics"]
    assert metrics[NAME]["unit"] == "reads"
    reads = metrics[NAME]["value"]
    routes = metrics["table_routes_per_event"]["value"]
    assert metrics["generic_routes_per_event"]["value"] == 0
    # at least one read an event, and fewer than the routes by the share
    # of them that are unicast and were asked for together
    assert 1 <= reads <= routes * (1 - unicast_share)
