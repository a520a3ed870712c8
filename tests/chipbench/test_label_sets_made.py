"""`label_sets_made_per_event` (ISSUE 36): its entry and its file agree, and
traced CPU rehearsals read it (rehearsals: nothing here is a device
number). A node-label route from the next-hop table makes its next hops
when somebody reads them; with segment routing off, as every configuration
here runs, no stage of an event does, and the counter says so: 0 beside a
`table_routes_per_event` that counts those label routes. The tests hold the
entry in the form that stays true when a later PR appends again."""

import json
import os

import pytest

from chipbench import layer_metrics
from chipbench import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "label_sets_made_per_event"
COUNTER = "decision.route_build_label_sets_made"
# the seven cells that listed `table_routes_per_event` when this came
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
    # appended behind PR 35's last; nothing before it moved
    assert names.index(NAME) == names.index("delta_columns_per_event") + 1 == 50
    entry = dict(bench["per_layer"][50])
    assert entry.pop("workloads")[: len(CELLS)] == CELLS
    assert entry == {
        "name": NAME, "unit": "sets", "better": "lower",
        "source": "program_counter", "layer": "route build",
        "moves": "event_to_fib_ms.p50",
    }
    table = next(m for m in bench["per_layer"] if m["name"] == "table_routes_per_event")
    assert table["workloads"][: len(CELLS)] == CELLS
    spec = bench_run.load_json("metrics", NAME + ".json")
    assert spec == {
        "name": NAME, "layer": "route build", "unit": "sets",
        "moves": "event_to_fib_ms.p50",
        "source": {"counter_delta": COUNTER, "per": "event"},
    }
    assert layer_metrics.read(spec, _context({COUNTER: 8}, {COUNTER: 24}))[0] == 2
    # there from the solver's start: a window in which nobody read a label
    # route's next hops reads 0, not nothing
    assert layer_metrics.read(spec, _context({COUNTER: 8}, {COUNTER: 8}))[0] == 0
    # a program without the counter (this PR's parent): left out, no error
    value, note = layer_metrics.read(spec, _context({}, {}))
    assert value is None and COUNTER in note


@pytest.mark.parametrize("cell, seed", [
    # every event a full build: every node label's route built anew
    ("rehearsal_fabric.own_link_flaps", 2**31 + 361),
    # a delta build: a label route for each changed column
    ("rehearsal_fabric.metric_flaps", 2**31 + 362),
    # the spine's view: label routes as wide as the vantage
    ("rehearsal_fabric_ssw.own_link_flaps", 2**31 + 363),
])
def test_traced_rehearsal_reads_no_set_made(cell, seed, capsys, monkeypatch, tmp_path):
    monkeypatch.setattr(bench_run, "TRACE_DIR", str(tmp_path / "trace"))
    rc = bench_run.main(
        ["--workload", cell, "--seed", str(seed), "--seconds", "1.5",
         "--allow-cpu", "--trace", "1"]
    )
    out, _ = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert line["metrics"][NAME] == {"value": 0, "unit": "sets"}
    assert line["metrics"]["table_routes_per_event"]["value"] > 0
    assert line["metrics"]["generic_routes_per_event"]["value"] == 0
