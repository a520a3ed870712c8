"""`BENCHMARK.json` grows by appending: a new cell goes to the end of
`workloads` and of every list that reports it, a new metric to the end of
`per_layer`. What eight tests of PRs 30, 33 and 34 pinned letter for letter
is kept here, as those tests now keep it too, in the form that stays true
when a later PR appends again: every list begins with what the PR that wrote it
left, in its order, and each metric's file reads what its entry says. PR 35's
own four metrics are held the same way."""

import json
import os

import pytest

from chipbench import layer_metrics
from chipbench import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# `workloads` in the order the PRs appended them
CELLS = [
    "fabric9976.metric_flaps", "grid10000.metric_flaps", "fabric9976.prefix_churn",
    "fabric9976.own_link_flaps",  # PR 29
    "fabric9976_ssw.metric_flaps",  # PR 33
    "fabric9976_ssw.own_link_flaps", "wan65536.listed_metric_flaps",  # PR 35
]
FLAPS = ["fabric9976.metric_flaps", "grid10000.metric_flaps"]
SSW, OWN = "fabric9976_ssw.metric_flaps", "fabric9976.own_link_flaps"
# name -> (layer, unit, better, moves, BENCHMARK.json's source, the file's
# source, the list as the PR that added the metric left it)
PINNED = {
    "solve_rows": (
        "device solve", "rows", "lower", "event_to_fib_ms.p50", "program_counter",
        {"gauge_mean": "decision.spf.rows_last"}, [SSW] + FLAPS + [OWN]),
    "solve_rows_padded": (
        "device solve", "rows", "lower", "event_to_fib_ms.p50", "program_counter",
        {"gauge_mean": "decision.spf.rows_padded_last"}, [SSW] + FLAPS + [OWN]),
    "invalidation_rounds_per_event": (
        "device solve", "rounds", "lower", "event_to_fib_ms.p50", "program_counter",
        {"gauge_mean": "decision.spf.invalidation_rounds_last"}, [SSW] + FLAPS),
    "solve_h2d_bytes_per_event": (
        "supervised solve", "bytes", "lower", "event_to_fib_ms.p50", "program_counter",
        {"counter_delta": "decision.spf.host_to_device_bytes", "per": "event"},
        [SSW] + FLAPS + [OWN]),
    "solve_d2h_bytes_per_event": (
        "supervised solve", "bytes", "lower", "event_to_fib_ms.p50", "program_counter",
        {"counter_delta": "decision.spf.device_to_host_bytes", "per": "event"},
        [SSW] + FLAPS + [OWN]),
    "full_build_ms.avg": (
        "route build", "ms", "lower", "event_to_fib_ms.p50", "program_span",
        {"histogram": "decision.full_build_ms", "stat": "avg"}, [OWN]),
    "counter_syncs_per_event": (
        "supervised solve", "syncs", "lower", "event_to_fib_ms.p50", "program_counter",
        {"counter_delta": "decision.spf.counter_syncs", "per": "event"}, CELLS[:5]),
    "graph_links_patched_per_event": (
        "supervised solve", "links", "higher", "events_per_s", "program_counter",
        {"counter_delta": "decision.spf.graph_links_patched", "per": "event"}, CELLS[:5]),
    # PR 35's four: the cells that list `solve_rows` and the two new ones;
    # those that list `solve_delta_extract_ms.avg` and the WAN's
    "solve_nodes_padded": (
        "device solve", "nodes", "lower", "event_to_fib_ms.p50", "program_counter",
        {"gauge_mean": "decision.spf.nodes_padded_last"}, FLAPS + CELLS[3:]),
    "sell_classes": (
        "device solve", "classes", "lower", "event_to_fib_ms.p50", "program_counter",
        {"gauge_mean": "decision.spf.sell_classes_last"}, FLAPS + CELLS[3:]),
    "sell_slots": (
        "device solve", "slots", "lower", "event_to_fib_ms.p50", "program_counter",
        {"gauge_mean": "decision.spf.sell_slots_last"}, FLAPS + CELLS[3:]),
    "delta_columns_per_event": (
        "device solve", "columns", "lower", "event_to_fib_ms.p50", "program_counter",
        {"counter_delta": "decision.spf.delta_columns", "per": "event"},
        FLAPS + [SSW, CELLS[6]]),
}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _context(**kw):
    blank = dict(hists={}, counters0={}, counters1={}, n_events=8, gauges={},
                 trace=None, config={}, device_kind="cpu")
    return layer_metrics.Context(**{**blank, **kw})


def test_workloads_begin_with_the_cells_in_the_order_they_were_appended():
    assert [w["name"] for w in _bench()["workloads"]][: len(CELLS)] == CELLS


def test_every_list_keeps_the_order_in_which_the_cells_were_appended():
    """A cell joins a list at its end, so wherever two cells share an older
    metric's list they stand in the order of `workloads`. The five metrics
    that came with `fabric9976_ssw.metric_flaps` name that cell first, as
    PR 33 wrote them; what joined since stands behind PR 33's list."""
    bench = _bench()
    order = {name: i for i, name in enumerate(w["name"] for w in bench["workloads"])}
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads", [])
        if m["name"] in PINNED and PINNED[m["name"]][-1][0] == SSW:
            cells = cells[1:]
        assert cells == sorted(cells, key=order.__getitem__), m["name"]
        assert len(set(cells)) == len(cells)


def test_per_layer_begins_with_the_46_entries_that_pr_34_left():
    names = [m["name"] for m in _bench()["per_layer"]]
    assert names.index("graph_links_patched_per_event") == 45
    assert names[46:50] == [
        "solve_nodes_padded", "sell_classes", "sell_slots", "delta_columns_per_event",
    ]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_entry_begins_with_its_prs_list_and_its_file_reads_the_program(name):
    layer, unit, better, moves, kind, source, first_cells = PINNED[name]
    entry = next(m for m in _bench()["per_layer"] if m["name"] == name)
    cells = entry.pop("workloads")
    assert entry == {"name": name, "unit": unit, "better": better, "source": kind,
                     "layer": layer, "moves": moves}
    assert cells[: len(first_cells)] == first_cells
    spec = bench_run.load_json("metrics", name + ".json")
    assert spec == {"name": name, "layer": layer, "unit": unit, "moves": moves,
                    "source": source}
    # the reading, and a program without the gauge, counter or histogram
    # (the parent of the PR that added it): left out, nothing raises
    if "gauge_mean" in source:
        missing = source["gauge_mean"]
        assert layer_metrics.gauges_wanted([{"spec": spec}]) == [missing]
        ctx = _context(gauges={missing: [174, 174, 174]})
        assert layer_metrics.read(spec, ctx)[0] == 174
    elif "counter_delta" in source:
        missing = source["counter_delta"]
        ctx = _context(counters0={missing: 1000}, counters1={missing: 81000})
        assert layer_metrics.read(spec, ctx)[0] == 10000
        same = _context(counters0={missing: 8}, counters1={missing: 8})
        assert layer_metrics.read(spec, same)[0] == 0  # there, and did not move
    else:
        missing = source["histogram"]
        ctx = _context(hists={missing: {"count": 8, "avg": 1500.0}})
        assert layer_metrics.read(spec, ctx)[0] == 1500.0
    value, note = layer_metrics.read(spec, _context())
    assert value is None and missing in note


def test_the_spines_metric_flaps_cell_reports_the_warm_path_and_not_the_cold():
    """What PR 33's test held of the cell besides its place in the lists."""
    cell = bench_run.resolve_cell(SSW)
    reported = {m["name"] for m in cell["per_layer"]}
    rack = {m["name"] for m in bench_run.resolve_cell(FLAPS[0])["per_layer"]}
    assert {"solve_rows", "solve_rows_padded", "invalidation_rounds_per_event",
            "solve_h2d_bytes_per_event", "solve_d2h_bytes_per_event",
            "solve_warm_ms.avg", "solve_device_ms", "delta_extract_device_ms",
            "relax_rounds_per_event", "relax_roofline", "route_build_delta_ms.avg",
            "delta_route_build_share", "solve_delta_extract_ms.avg",
            "solve_mirror_patch_ms.avg", "delta_build_ms.avg", "compiles_in_window",
            "graph_recompiles_in_window", "device_idle_pct"} <= reported
    assert not {"solve_cold_ms.avg", "solve_d2h_ms.avg", "full_solves_per_event",
                "route_build_ms.avg", "full_build_ms.avg",
                "compiles_in_window.cold"} & reported
    assert reported <= rack
    assert {"event_to_fib_ms.p50", "events_per_s", "setup_s"} <= {
        m["name"] for m in cell["end_to_end"]
    }
