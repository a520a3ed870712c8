"""`graph_links_patched_per_event` (ISSUE 34): its entry and its file agree,
and traced CPU rehearsals read it (rehearsals: nothing here is a device
number). A link that leaves or returns to the LSDB is a patch of the slots
the compiled graph has for it; the counter says how many a refresh absorbed
so, where `graph_recompiles_in_window` 0 alone could also mean that none
was sent."""

import json
import os

import pytest

from chipbench import layer_metrics
from chipbench import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "graph_links_patched_per_event"
COUNTER = "decision.spf.graph_links_patched"


def _context(counters0, counters1):
    return layer_metrics.Context(
        hists={}, counters0=counters0, counters1=counters1, n_events=8,
        gauges={}, trace=None, config={}, device_kind="cpu",
    )


def test_entry_and_file_read_the_programs_counter_per_event():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(NAME) == 45  # appended as the 46th, nothing moved
    entry = bench["per_layer"][45]
    assert (entry["source"], entry["layer"]) == (
        "program_counter", "supervised solve",
    )
    assert (entry["better"], entry["moves"]) == ("higher", "events_per_s")
    # the list the metric came with, and whatever later cells appended
    assert entry["workloads"][:5] == [w["name"] for w in bench["workloads"]][:5]
    spec = bench_run.load_json("metrics", NAME + ".json")
    assert {k: spec[k] for k in ("name", "layer", "unit", "moves")} == {
        k: entry[k] for k in ("name", "layer", "unit", "moves")
    }
    assert spec["source"] == {"counter_delta": COUNTER, "per": "event"}
    assert layer_metrics.read(spec, _context({COUNTER: 8}, {COUNTER: 24}))[0] == 2
    # there from the first sync: a window without such an event reads 0
    assert layer_metrics.read(spec, _context({COUNTER: 8}, {COUNTER: 8}))[0] == 0
    # a program without the counter (this PR's parent): left out, no error
    value, note = layer_metrics.read(spec, _context({}, {}))
    assert value is None and COUNTER in note


@pytest.mark.parametrize("cell, seed, patched, recompiles", [
    # every event: one of the vantage's up-links returns, another leaves
    ("rehearsal_fabric.own_link_flaps", 2**31 + 341, 2, 0),
    # metric changes alone: the mechanism is never asked
    ("rehearsal_fabric.metric_flaps", 2**31 + 342, 0, 0),
])
def test_traced_rehearsal_reads_it(
    cell, seed, patched, recompiles, capsys, monkeypatch, tmp_path
):
    monkeypatch.setattr(bench_run, "TRACE_DIR", str(tmp_path / "trace"))
    rc = bench_run.main(
        ["--workload", cell, "--seed", str(seed), "--seconds", "1.5",
         "--allow-cpu", "--trace", "1"]
    )
    out, _ = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert line["metrics"][NAME] == {"value": patched, "unit": "links"}
    assert line["metrics"]["graph_recompiles_in_window"]["value"] == recompiles
