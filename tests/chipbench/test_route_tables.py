"""`table_routes_per_event` and `generic_routes_per_event` (ISSUE 32): their
entries and files agree, and traced CPU rehearsals read both (rehearsals:
nothing here is a device number). A route's next hops come from the
resident solve's next-hop table where the input allows it and from the
generic next-hop stack otherwise; the two counters say how often each."""

import json
import os

import pytest

from chipbench import layer_metrics
from chipbench import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
COUNTERS = {
    "table_routes_per_event": "decision.route_build_table_routes",
    "generic_routes_per_event": "decision.route_build_generic_routes",
}


def _context(counters0=None, counters1=None):
    return layer_metrics.Context(
        hists={}, counters0=counters0 or {}, counters1=counters1 or {},
        n_events=8, gauges={}, trace=None, config={}, device_kind="cpu",
    )


@pytest.mark.parametrize("name, better", [
    ("table_routes_per_event", "higher"),
    ("generic_routes_per_event", "lower"),
])
def test_entry_and_file_read_the_programs_counter(name, better):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert (entry["source"], entry["layer"]) == ("program_counter", "route build")
    assert (entry["better"], entry["moves"]) == (better, "event_to_fib_ms.p50")
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    spec = bench_run.load_json("metrics", name + ".json")
    assert {k: spec[k] for k in ("name", "layer", "unit", "moves")} == {
        k: entry[k] for k in ("name", "layer", "unit", "moves")
    }
    counter = COUNTERS[name]
    assert spec["source"] == {"counter_delta": counter, "per": "event"}
    ctx = _context(counters0={counter: 40}, counters1={counter: 200})
    assert layer_metrics.read(spec, ctx)[0] == 20
    # the counters exist from the solver's start: a window whose routes
    # all came from the other side reads 0, not nothing
    ctx = _context(counters0={counter: 40}, counters1={counter: 40})
    assert layer_metrics.read(spec, ctx)[0] == 0
    # a program without the counter (this PR's parent): left out, no error
    value, note = layer_metrics.read(spec, _context())
    assert value is None and counter in note


@pytest.mark.parametrize("cell, seed", [
    # every event a full build: each prefix's and each node label's route
    ("rehearsal_fabric.own_link_flaps", 2**31 + 321),
    # a delta build: a unicast and a label route for each changed column
    ("rehearsal_fabric.metric_flaps", 2**31 + 322),
])
def test_traced_rehearsal_reads_both(cell, seed, capsys, monkeypatch, tmp_path):
    monkeypatch.setattr(bench_run, "TRACE_DIR", str(tmp_path / "trace"))
    rc = bench_run.main(
        ["--workload", cell, "--seed", str(seed), "--seconds", "1.5",
         "--allow-cpu", "--trace", "1"]
    )
    out, _ = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    metrics = line["metrics"]
    assert metrics["generic_routes_per_event"] == {"value": 0, "unit": "routes"}
    assert metrics["table_routes_per_event"]["value"] > 0
    assert metrics["table_routes_per_event"]["unit"] == "routes"
