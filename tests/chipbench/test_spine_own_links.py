"""ISSUE 35, PERF.md §7's row 4b: the cell `fabric9976_ssw.own_link_flaps`,
the spine switch's local failure. It sends the vantage's own 173 links, one
down at any time, and the reference says what an event has to re-program on
the real Clos. Beside `test_wide_vantage.py`, which keeps the rack switch's
cell. Rehearsals on the CPU: nothing here is a device number."""

import collections
import json
import os

import pytest

from chipbench import compare, control, reference
from chipbench import run as bench_run
from chipbench.lsdb import Lsdb, if_name
from chipbench.topologies import build_edges
from chipbench.traffic_kinds import link_down_swap, link_metric_swap

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "fabric9976_ssw.own_link_flaps"
TOY_CELL = "rehearsal_fabric_ssw.own_link_flaps"
RACK_CELL = "fabric9976.own_link_flaps"
ME = "ssw0_0"


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_the_cell_sends_the_vantages_own_173_links_and_no_state_twice():
    cell = bench_run.resolve_cell(CELL)
    params, config = cell["params"], cell["config_data"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "fabric9976_ssw", "own_link_flaps", 1)
    assert len(cell["why"]) <= 200 and "173" in cell["why"] and "no p95" in cell["why"]
    # the mix's file unchanged: the cell's file holds its links, how many
    # events are verified, and says that this is no replayed set
    assert set(bench_run.load_json("cells", f"{CELL}.json")) == {
        "groups", "verify_events", "what"}
    mix = bench_run.load_json("traffic", "own_link_flaps.json")
    assert params["kind"] == mix["kind"] == "link_down_swap"
    assert (params["warmup_events"], params["event_timeout_s"]) == (4, 120)
    assert params["verify_events"] == 24 and mix["verify_events"] == 250
    assert "NOT a replayed set" in params["what"] and "a handful" in mix["what"]
    links = [l for g in params["groups"] for l in link_metric_swap.expand(g)]
    assert links == [(ME, f"fsw{p}_0") for p in range(173)]
    assert config["vantage"] == ME and config["vantage_up_neighbours"] == len(links)
    lsdb = Lsdb(build_edges(config["topology"]))
    assert sorted(lsdb.metric[ME]) == sorted(b for _, b in links)
    # blocks of the 173 shuffled from the seed: within a block no down link,
    # so no LSDB state, comes twice, and a window holds well under one
    gen = link_down_swap.generate(params, 2**31 + 35)
    block = [next(gen) for _ in range(173)]
    assert len({e.down for e in block}) == 173
    assert all(b.up == a.down for a, b in zip(block, block[1:]))
    other = link_down_swap.generate(params, 2**31 + 36)
    assert [next(other).down for _ in range(8)] != [e.down for e in block[:8]]
    # exactly one is down after every event: 172 up neighbours, 173 rows
    for event in block[:6]:
        keys = event.apply(lsdb)
        assert keys[0] == f"adj:{ME}" and 2 <= len(keys) <= 3
        assert len(lsdb.up_peers(ME)) == 172


def test_the_cell_joins_the_rack_switchs_lists_but_not_gc_pause_nor_the_p95():
    bench = _bench()
    for m in bench["per_layer"]:
        cells = m["workloads"]
        if m["name"] in ("solve_nodes_padded", "sell_classes", "sell_slots"):
            assert CELL in cells
            continue
        expected = RACK_CELL in cells and m["name"] != "gc_pause_ms.max"
        assert (CELL in cells) == expected, m["name"]
    cell = bench_run.resolve_cell(CELL)
    # tens of events a window have no 95th percentile
    assert {m["name"] for m in cell["end_to_end"]} == {
        "event_to_fib_ms.p50", "events_per_s", "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"solve_cold_ms.avg", "route_build_ms.avg", "solve_d2h_ms.avg",
            "full_solves_per_event", "full_build_ms.avg", "solve_rows",
            "solve_rows_padded", "solve_d2h_bytes_per_event", "relax_roofline",
            "graph_recompiles_in_window", "graph_links_patched_per_event",
            "compiles_in_window.cold"} <= reported
    assert not {"solve_warm_ms.avg", "route_build_delta_ms.avg", "delta_build_ms.avg",
                "delta_extract_device_ms", "solve_delta_extract_ms.avg",
                "solve_mirror_patch_ms.avg", "delta_columns_per_event",
                "delta_route_build_share", "gc_pause_ms.max"} & reported
    rack = {m["name"] for m in bench_run.resolve_cell(RACK_CELL)["per_layer"]}
    assert reported == rack - {"gc_pause_ms.max"}


def test_an_own_link_event_moves_399_routes_of_the_clos_from_its_spine():
    """What one event of the cell has to re-program, by the reference: the
    287 routes to the other spine switches lose one of 173 first hops and
    regain another, the 56 of the pod whose link went down are reached
    round over the 172 that stay, and the 56 of the pod whose link came
    back over their own one again."""
    cell = bench_run.resolve_cell(CELL)
    config = cell["config_data"]
    lsdb = Lsdb(build_edges(config["topology"]))
    ref = reference.Reference(lsdb, ME)
    gen = link_down_swap.generate(cell["params"], 2**31 + 37)
    first = next(gen)
    ref.refresh(key.split(":", 1)[1] for key in first.apply(lsdb))
    before = ref.table()
    for _ in range(2):
        event = next(gen)
        keys = event.apply(lsdb)
        assert len(keys) == 3 and keys[0] == f"adj:{ME}"
        ref.refresh(key.split(":", 1)[1] for key in keys)
        after = ref.table()
        changed = compare.table_mismatches(before, after)
        assert len(changed) == 287 + 56 + 56 == 399
        assert collections.Counter(len(after[p]) for p in changed) == {
            172: 287 + 56, 1: 56}
        assert collections.Counter(len(nhs) for nhs in after.values()) == {
            172: 287 + 56, 1: 9975 - 287 - 56}
        # some 59,000 NextHops through Fib for one event
        assert sum(len(after[p]) for p in changed) == 343 * 172 + 56 == 59052
        went = int(event.down[1][3:].split("_")[0])
        back = int(event.up[1][3:].split("_")[0])
        gone = if_name(ME, event.down[1])
        for r in (0, 47):
            round_about = after[lsdb.prefix_of[f"rsw{went}_{r}"]]
            assert len(round_about) == 172
            assert gone not in {iface for _, iface, _ in round_about}
            # down to any other pod, up to another spine of the plane, down
            assert {metric for _, _, metric in round_about} == {4}
            (one,) = after[lsdb.prefix_of[f"rsw{back}_{r}"]]
            assert one[1:] == (if_name(ME, event.up[1]), 2)
        before = after


@pytest.mark.parametrize("breakage", control.BREAKAGES)
def test_control_breaks_a_guarantee_on_the_toy_hubs_own_links(breakage):
    cell = bench_run.resolve_cell(TOY_CELL)
    got, compared, _ = control.control_run(cell, seed=2**31 + 38, n_events=30, breakage=breakage)
    assert got is False
    assert any(v["value"] > 0 for v in compared.values())


def test_traced_rehearsal_of_the_toy_hubs_own_links_builds_in_full(capsys, monkeypatch, tmp_path):
    """The toy hub (70 own links, 69 up after every event: 70 rows padded to
    128) through the whole served path, traced: a cold solve, the whole
    mirror back and a full route build on every event, no graph recompile."""
    monkeypatch.setattr(bench_run, "TRACE_DIR", str(tmp_path / "trace"))
    rc = bench_run.main(
        ["--workload", TOY_CELL, "--seed", str(2**31 + 39), "--seconds", "1.5",
         "--allow-cpu", "--trace", "1"]
    )
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert all(v["value"] == 0 for v in line["compared"].values())
    assert line["attempted"] >= 8 and "DeltaPath:" not in err
    metrics = line["metrics"]
    assert metrics["full_solves_per_event"] == {"value": 1, "unit": "solves"}
    assert metrics["solve_rows"] == {"value": 70, "unit": "rows"}
    assert metrics["solve_rows_padded"] == {"value": 128, "unit": "rows"}
    # the whole [128, n_pad = 256] int32 mirror back on every event
    assert metrics["solve_d2h_bytes_per_event"]["value"] == 128 * 256 * 4
    assert metrics["solve_nodes_padded"] == {"value": 256, "unit": "nodes"}
    assert metrics["sell_classes"]["value"] >= 2
    assert metrics["sell_slots"]["value"] >= 560
    assert metrics["full_build_ms.avg"]["value"] > 0
    assert metrics["graph_recompiles_in_window"]["value"] == 0
    assert metrics["graph_links_patched_per_event"]["value"] == 2
    assert metrics["compiles_in_window.cold"]["value"] == 0
    assert not {"delta_route_build_share", "solve_warm_ms.avg",
                "delta_columns_per_event"} & set(metrics)
