"""`counter_syncs_per_event` and `full_build_ms.avg` (ISSUE 30): their
entries and files agree, and traced CPU rehearsals read both (rehearsals:
nothing here is a device number). A route build folds the solver's
statistics into its counters a few times an event, never once a distance
read; the full build's host work has a stage of its own."""

import json
import os

import pytest

from chipbench import layer_metrics
from chipbench import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SYNCS = "counter_syncs_per_event"
FULL_BUILD = "full_build_ms.avg"
COUNTER = "decision.spf.counter_syncs"
HISTOGRAM = "decision.full_build_ms"


def _context(hists=None, counters0=None, counters1=None):
    return layer_metrics.Context(
        hists=hists or {}, counters0=counters0 or {}, counters1=counters1 or {},
        n_events=8, gauges={}, trace=None, config={}, device_kind="cpu",
    )


def test_entries_and_files_read_the_programs_counter_and_histogram():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    syncs, full_build = by_name[SYNCS], by_name[FULL_BUILD]
    assert (syncs["source"], syncs["layer"]) == ("program_counter", "supervised solve")
    # the lists the metrics came with, and whatever later cells appended
    cells = [w["name"] for w in bench["workloads"]]
    assert syncs["workloads"][:4] == cells[:4]
    assert (full_build["source"], full_build["layer"]) == ("program_span", "route build")
    assert full_build["workloads"][0] == "fabric9976.own_link_flaps"
    for entry in (syncs, full_build):
        assert entry["better"] == "lower" and entry["moves"] == "event_to_fib_ms.p50"
        spec = bench_run.load_json("metrics", entry["name"] + ".json")
        assert {k: spec[k] for k in ("name", "layer", "unit", "moves")} == {
            k: entry[k] for k in ("name", "layer", "unit", "moves")
        }
    spec = bench_run.load_json("metrics", SYNCS + ".json")
    assert spec["source"] == {"counter_delta": COUNTER, "per": "event"}
    ctx = _context(counters0={COUNTER: 10}, counters1={COUNTER: 34})
    assert layer_metrics.read(spec, ctx)[0] == 3
    spec_fb = bench_run.load_json("metrics", FULL_BUILD + ".json")
    assert spec_fb["source"] == {"histogram": HISTOGRAM, "stat": "avg"}
    ctx = _context(hists={HISTOGRAM: {"count": 8, "avg": 1500.0}})
    assert layer_metrics.read(spec_fb, ctx)[0] == 1500.0
    # a program without the counter and the stage (this PR's parent): both
    # left out, no error
    value, note = layer_metrics.read(spec, _context())
    assert value is None and COUNTER in note
    value, note = layer_metrics.read(spec_fb, _context())
    assert value is None and HISTOGRAM in note


@pytest.mark.parametrize("cell, seed, syncs, full", [
    # the solve, the poll's end, build_route_db's end, the build's end
    ("rehearsal_fabric.own_link_flaps", 2**31 + 301, 4, True),
    # the solve, the poll's end, the delta build's end
    ("rehearsal_fabric.metric_flaps", 2**31 + 302, 3, False),
])
def test_traced_rehearsal_reads_both(cell, seed, syncs, full, capsys, monkeypatch, tmp_path):
    monkeypatch.setattr(bench_run, "TRACE_DIR", str(tmp_path / "trace"))
    rc = bench_run.main(
        ["--workload", cell, "--seed", str(seed), "--seconds", "1.5",
         "--allow-cpu", "--trace", "1"]
    )
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    metrics = line["metrics"]
    assert metrics[SYNCS] == {"value": syncs, "unit": "syncs"}
    if full:
        assert metrics["full_solves_per_event"]["value"] == 1
        assert metrics["device_syncs_per_event"]["value"] == 2
        assert 0 < metrics[FULL_BUILD]["value"] < metrics["route_build_ms.avg"]["value"]
        # the umbrella is the resolved solve and the host's build
        inside = sum(
            metrics[name]["value"]
            for name in (FULL_BUILD, "solve_refresh_ms.avg", "solve_cold_ms.avg",
                         "solve_d2h_ms.avg")
        )
        route_build = metrics["route_build_ms.avg"]["value"]
        assert 0.8 * route_build <= inside <= route_build
    else:
        assert FULL_BUILD not in metrics and "full route build" not in err
        assert metrics["device_syncs_per_event"]["value"] == 6
