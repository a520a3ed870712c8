"""ISSUE 33: the configuration `fabric9976_ssw` (the Clos as its spine
switch `ssw0_0` sees it: 173 neighbours, 174 solve rows padded to 256) and
its cell `fabric9976_ssw.metric_flaps` send and report what their files
say, and the five per-layer metrics that came with them read the
program's gauges and counters. Rehearsals on the CPU: nothing here is a
device number."""

import json
import os

import pytest

from chipbench import compare, layer_metrics, reference, work
from chipbench import run as bench_run
from chipbench.lsdb import Lsdb
from chipbench.topologies import build_edges
from chipbench.traffic_kinds import link_metric_swap

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = "fabric9976_ssw"
CELL = "fabric9976_ssw.metric_flaps"
FLAPS = ["fabric9976.metric_flaps", "grid10000.metric_flaps"]
OWN_LINKS = "fabric9976.own_link_flaps"
# name -> (layer, unit, the file's source, the older cells that report it)
NEW_METRICS = {
    "solve_rows": (
        "device solve", "rows",
        {"gauge_mean": "decision.spf.rows_last"}, FLAPS + [OWN_LINKS],
    ),
    "solve_rows_padded": (
        "device solve", "rows",
        {"gauge_mean": "decision.spf.rows_padded_last"}, FLAPS + [OWN_LINKS],
    ),
    "invalidation_rounds_per_event": (
        "device solve", "rounds",
        {"gauge_mean": "decision.spf.invalidation_rounds_last"}, FLAPS,
    ),
    "solve_h2d_bytes_per_event": (
        "supervised solve", "bytes",
        {"counter_delta": "decision.spf.host_to_device_bytes", "per": "event"},
        FLAPS + [OWN_LINKS],
    ),
    "solve_d2h_bytes_per_event": (
        "supervised solve", "bytes",
        {"counter_delta": "decision.spf.device_to_host_bytes", "per": "event"},
        FLAPS + [OWN_LINKS],
    ),
}
COLD_PATH = {
    "solve_cold_ms.avg", "solve_d2h_ms.avg", "full_solves_per_event",
    "route_build_ms.avg", "full_build_ms.avg", "compiles_in_window.cold",
}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _links(params):
    return [l for g in params["groups"] for l in link_metric_swap.expand(g)]


def test_the_configurations_counts_are_the_generated_topologys():
    config = bench_run.load_json("configs", f"{CONFIG}.json")
    rack = bench_run.load_json("configs", "fabric9976.json")
    lsdb = Lsdb(build_edges(config["topology"]))
    assert len(lsdb.nodes) == config["nodes"] == 9976
    assert lsdb.n_links == config["links"] == 116256
    assert 2 * lsdb.n_links == config["directed_edges"] == 232512
    assert config["vantage"] == "ssw0_0"
    assert len(lsdb.metric["ssw0_0"]) == config["vantage_up_neighbours"] == 173
    assert 1 + len(lsdb.metric["ssw0_0"]) == work.solve_rows(config) == 174
    assert work.sweep_bytes(config) == 232512 * 8 + 2 * 174 * 9976 * 4 == 15746688
    # the same fabric as the rack switch's configuration, width for width,
    # the same daemon and the same guarantees: only the vantage differs
    for key in ("topology", "nodes", "links", "directed_edges", "prefixes",
                "daemon", "guarantees", "reduced_notes"):
        assert config[key] == rack[key], key
    assert set(config["assumed"]) == {"planes", "vantage", "prefix_plan"}
    entry = next(c for c in _bench()["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["reduced"] == ["pods"] == list(config["reduced_notes"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert "DecisionBenchmark.cpp:51-56,814-823" in entry["source"]
    assert "[256, 16,384]" in config["on_device"]


def test_the_cell_sends_the_1536_rack_links_of_the_vantages_plane():
    cell = bench_run.resolve_cell(CELL)
    params = cell["params"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "metric_flaps", 1)
    assert len(cell["why"]) <= 200 and "1,536" in cell["why"]
    # the cell's file holds its links and how many events are verified;
    # the rest is the mix's own, untouched
    assert set(bench_run.load_json("cells", f"{CELL}.json")) == {"groups", "verify_events"}
    mix = bench_run.load_json("traffic", "metric_flaps.json")
    assert params["verify_events"] == 40 and mix["verify_events"] == 250
    for key in ("kind", "low", "high", "warmup_events", "event_timeout_s"):
        assert params[key] == mix[key], key
    assert params["kind"] == "link_metric_swap" and params["low"] == [1]
    links = _links(params)
    assert len(links) == len(set(links)) == 32 * 48 == 1536
    assert links[0] == ("fsw0_0", "rsw0_0") and links[-1] == ("fsw31_0", "rsw31_47")
    lsdb = Lsdb(build_edges(cell["config_data"]["topology"]))
    for a, b in links:
        assert b in lsdb.metric[a]  # a link of the fabric
        assert a in lsdb.metric["ssw0_0"]  # below the vantage's own plane
        assert "ssw0_0" not in (a, b)  # and not the vantage's own


def test_no_two_of_1056_events_leave_the_lsdb_in_the_same_state():
    params = bench_run.resolve_cell(CELL)["params"]
    gen = link_metric_swap.generate(params, 2**31 + 33)
    states = [(e.raised, e.metric) for e in (next(gen) for _ in range(1056))]
    assert len(set(states)) == len(states)


def test_every_event_moves_two_routes_by_metric_alone_from_a_spine_vantage():
    """The cell in small, by the reference: a fabric of 2 planes x 2 ssw
    over 4 pods of 2 fsw and 3 rsw from `ssw0_0`. While a rack link of the
    vantage's plane is high, the route toward that rack carries a higher
    metric over the one first hop it had (the way round inside the pod
    leaves by the same link); nothing else moves."""
    topology = {"generator": "fabric", "args": {
        "pods": 4, "ssw_per_plane": 2, "fsw_per_pod": 2, "rsw_per_pod": 3}}
    groups = [{"a": "fsw{p}_0", "b": "rsw{p}_{r}", "ranges": {"p": [0, 3], "r": [0, 2]}}]
    params = dict(bench_run.load_json("traffic", "metric_flaps.json"), groups=groups)
    lsdb = Lsdb(build_edges(topology))
    ref = reference.Reference(lsdb, "ssw0_0")
    before = ref.table()
    gen = link_metric_swap.generate(params, 2**31 + 34)
    for _ in range(40):
        event = next(gen)
        keys = event.apply(lsdb)
        ref.refresh(key.split(":", 1)[1] for key in keys)
        after = ref.table()
        far_sides = {event.raised[1]} | ({event.restore[1]} if event.restore else set())
        changed = compare.table_mismatches(before, after)
        assert set(changed) == {lsdb.prefix_of[n] for n in far_sides}, event
        for prefix in changed:
            (was,), (now,) = before[prefix], after[prefix]  # one first hop
            assert was[:2] == now[:2] and was[2] != now[2]
        raised = after[lsdb.prefix_of[event.raised[1]]]
        assert {metric for _, _, metric in raised} == {4}
        before = after


def test_the_cell_reports_the_warm_paths_metrics_and_the_five_new_ones():
    bench = _bench()
    cell = bench_run.resolve_cell(CELL)
    reported = {m["name"] for m in cell["per_layer"]}
    rack = {m["name"] for m in bench_run.resolve_cell(FLAPS[0])["per_layer"]}
    assert set(NEW_METRICS) <= reported
    assert {"solve_warm_ms.avg", "solve_device_ms", "delta_extract_device_ms",
            "relax_rounds_per_event", "relax_roofline", "route_build_delta_ms.avg",
            "delta_route_build_share", "solve_delta_extract_ms.avg",
            "solve_mirror_patch_ms.avg", "delta_build_ms.avg", "compiles_in_window",
            "graph_recompiles_in_window", "device_idle_pct"} <= reported
    assert not COLD_PATH & reported
    # nothing that the rack switch's cell does not report as well
    assert reported <= rack
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert {"event_to_fib_ms.p50", "events_per_s", "setup_s"} <= e2e
    # the cell stands behind every older cell wherever an older metric
    # gained it, and only cells appended after it stand behind it: an entry
    # put first or in the middle reads as a change to what was there
    order = [w["name"] for w in bench["workloads"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads", [])
        if CELL in cells and m["name"] not in NEW_METRICS:
            at = cells.index(CELL)
            assert all(order.index(c) < order.index(CELL) for c in cells[:at]), m["name"]
            assert all(order.index(c) > order.index(CELL) for c in cells[at + 1:]), m["name"]


def test_every_new_metric_file_is_named_by_benchmark_json():
    named = {m["name"] for m in _bench()["per_layer"]}
    files = {f[: -len(".json")] for f in os.listdir(os.path.join(bench_run.HERE, "metrics"))}
    assert set(NEW_METRICS) <= files & named


def _context(counters0=None, counters1=None, gauges=None):
    return layer_metrics.Context(
        hists={}, counters0=counters0 or {}, counters1=counters1 or {},
        n_events=8, gauges=gauges or {}, trace=None, config={}, device_kind="cpu",
    )


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_entry_and_file_read_the_programs_gauge_or_counter(name):
    layer, unit, source, older_cells = NEW_METRICS[name]
    entry = dict(next(m for m in _bench()["per_layer"] if m["name"] == name))
    # the list the metric came with, and whatever later cells appended
    assert entry.pop("workloads")[: 1 + len(older_cells)] == [CELL] + older_cells
    assert entry == {
        "name": name, "unit": unit, "better": "lower", "source": "program_counter",
        "layer": layer, "moves": "event_to_fib_ms.p50",
    }
    spec = bench_run.load_json("metrics", name + ".json")
    assert spec == {"name": name, "layer": layer, "unit": unit,
                    "moves": "event_to_fib_ms.p50", "source": source}
    if "gauge_mean" in source:
        gauge = source["gauge_mean"]
        assert layer_metrics.gauges_wanted([{"spec": spec}]) == [gauge]
        ctx = _context(gauges={gauge: [174, 174, 174]})
        assert layer_metrics.read(spec, ctx)[0] == 174
        missing = gauge
    else:
        counter = source["counter_delta"]
        ctx = _context(counters0={counter: 1000}, counters1={counter: 81000})
        assert layer_metrics.read(spec, ctx)[0] == 10000
        missing = counter
    # a program without it (this PR's parent lacks the two row gauges):
    # the metric is left out, and nothing raises
    value, note = layer_metrics.read(spec, _context())
    assert value is None and missing in note


def test_traced_rehearsal_from_a_spine_reads_the_five(capsys, monkeypatch, tmp_path):
    """The toy hub (70 neighbours, 71 rows padded to 128) through the
    whole served path, traced: every row is solved for on every event."""
    monkeypatch.setattr(bench_run, "TRACE_DIR", str(tmp_path / "trace"))
    rc = bench_run.main(
        ["--workload", "rehearsal_fabric_ssw.metric_flaps", "--seed", str(2**31 + 335),
         "--seconds", "1.5", "--allow-cpu", "--trace", "1"]
    )
    out, _ = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    assert metrics["solve_rows"] == {"value": 71, "unit": "rows"}
    assert metrics["solve_rows_padded"] == {"value": 128, "unit": "rows"}
    assert metrics["invalidation_rounds_per_event"]["value"] >= 1
    assert metrics["relax_rounds_per_event"]["value"] >= 1
    # per event: the 128 source rows and a few patched slots up; two
    # changed columns of distances and first-hop marks down, not the
    # [128, n_pad] mirror
    assert 0 < metrics["solve_h2d_bytes_per_event"]["value"] < 64 << 10
    assert 0 < metrics["solve_d2h_bytes_per_event"]["value"] < 64 << 10
    assert metrics["delta_route_build_share"]["value"] == 1
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["graph_recompiles_in_window"]["value"] == 0
