"""`graph_recompiles_in_window` (ISSUE 27): its entry and its file agree,
and the traced CPU rehearsal reads it as 0 (a rehearsal: nothing here is
a device number)."""

import json
import os

from chipbench import layer_metrics
from chipbench import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REHEARSAL = "rehearsal_fabric.metric_flaps"
NAME = "graph_recompiles_in_window"
COUNTER = "decision.spf.graph_recompiles"


def _context(counters0, counters1):
    return layer_metrics.Context(
        hists={}, counters0=counters0, counters1=counters1, n_events=40,
        gauges={}, trace=None, config={}, device_kind="cpu",
    )


def test_entry_and_file_read_the_programs_counter_over_the_window():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry["source"] == "program_counter"
    assert entry["better"] == "lower" and entry["moves"] == "events_per_s"
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    spec = bench_run.load_json("metrics", NAME + ".json")
    assert spec["source"] == {"counter_delta": COUNTER, "per": "window"}
    assert layer_metrics.read(spec, _context({COUNTER: 1}, {COUNTER: 3}))[0] == 2
    # a program without the counter (this PR's parent): left out, no error
    value, note = layer_metrics.read(spec, _context({}, {}))
    assert value is None and COUNTER in note


def test_traced_rehearsal_holds_no_graph_recompile(capsys, monkeypatch, tmp_path):
    # a trace directory of its own: the other files' traced rehearsals share
    # the checkout's, and a worker may run one of them at the same time
    monkeypatch.setattr(bench_run, "TRACE_DIR", str(tmp_path / "trace"))
    rc = bench_run.main(
        ["--workload", REHEARSAL, "--seed", str(2**31 + 127), "--seconds", "1.5",
         "--allow-cpu", "--trace", "1"]
    )
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert line["metrics"][NAME] == {"value": 0, "unit": "count"}
    assert line["metrics"]["delta_route_build_share"]["value"] == 1
    assert "full route build" not in err
