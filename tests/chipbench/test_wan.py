"""ISSUE 35: the configuration `wan65536` (BASELINE.json config 3's WAN at
the size the benchmark's address plan holds, from its node of highest
degree), the kind `listed_link_metric_swap`, the mix `listed_metric_flaps`
and the cell `wan65536.listed_metric_flaps` send and report what their files
say, and the four per-layer metrics that came with them read the program's
gauges and counter. Rehearsals on the CPU: nothing here is a device
number."""

import collections
import json
import os

import numpy as np
import pytest
from scipy.sparse.csgraph import dijkstra

from chipbench import compare, control, reference, work
from chipbench import run as bench_run
from chipbench.lsdb import Lsdb
from chipbench.topologies import build_edges, wan
from chipbench.traffic_kinds import link_metric_swap, listed_link_metric_swap
from openr_tpu.topology import wan_edges

from test_benchmark_lists import CELLS

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG, TOY = "wan65536", "rehearsal_wan"
CELL, TOY_CELL = "wan65536.listed_metric_flaps", "rehearsal_wan.listed_metric_flaps"
SPINE_CELL = "fabric9976_ssw.own_link_flaps"
BIG = 2**31 + 35  # the driver's seeds do not fit 32 signed bits
NEW_METRICS = ["solve_nodes_padded", "sell_classes", "sell_slots", "delta_columns_per_event"]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def full():
    """The cell, its generated edge list and the LSDB, made once."""
    cell = bench_run.resolve_cell(CELL)
    edges = build_edges(cell["config_data"]["topology"])
    return cell, edges, Lsdb(edges)


def _tree_candidates(edges, lsdb, vantage, lo, hi):
    """The cell's rule: links of configured metric 1 on the predecessor
    tree of scipy's Dijkstra from the vantage (over the reference's own
    graph), neither end the vantage, `lo` to `hi` destinations below
    them, in the order of the edge list; with how many lie below each."""
    ref = reference.Reference(lsdb, vantage)
    me = ref.number[vantage]
    dist, parent = dijkstra(
        ref.graph, directed=True, indices=me, return_predecessors=True
    )
    below = np.ones(len(dist), dtype=int)
    for node in np.argsort(dist)[::-1]:  # every metric is positive
        if parent[node] >= 0:
            below[parent[node]] += below[node]
    links, sizes = [], []
    for a, b, metric in edges:
        ia, ib = ref.number[a], ref.number[b]
        child = ib if parent[ib] == ia else ia if parent[ia] == ib else None
        if (metric == 1 and vantage not in (a, b) and child is not None
                and lo <= below[child] <= hi):
            links.append([a, b])
            sizes.append(int(below[child]))
    return links, sizes, dist


@pytest.mark.parametrize("name, nodes", [(CONFIG, 65536), (TOY, 2048)])
def test_the_configurations_counts_and_vantage_are_the_generated_topologys(name, nodes):
    config = bench_run.load_json("configs", f"{name}.json")
    assert config["topology"] == {
        "generator": "wan", "args": {"n": nodes, "degree": 4, "seed": 3}
    }
    lsdb = Lsdb(build_edges(config["topology"]))
    assert len(lsdb.nodes) == config["nodes"] == nodes
    assert lsdb.n_links == config["links"] == 2 * nodes
    assert 2 * lsdb.n_links == config["directed_edges"]
    # the vantage by the stated rule: highest degree, lowest index on a tie
    degree = {node: len(peers) for node, peers in lsdb.metric.items()}
    vantage = min(degree, key=lambda node: (-degree[node], int(node[1:])))
    assert config["vantage"] == vantage
    assert config["vantage_up_neighbours"] == degree[vantage] == max(degree.values())
    assert work.solve_rows(config) == 1 + degree[vantage]
    assert work.sweep_bytes(config) == (
        config["directed_edges"] * 8 + 2 * (1 + degree[vantage]) * nodes * 4
    )
    # skewed degrees and metrics 1-100: what the Clos and the grid lack
    assert len(set(degree.values())) >= 9 and min(degree.values()) == 2
    metrics = {m for peers in lsdb.metric.values() for m in peers.values()}
    assert metrics == set(range(1, 101))


def test_the_configurations_file_states_source_cut_assumptions_and_guarantees():
    config = bench_run.load_json("configs", f"{CONFIG}.json")
    rack = bench_run.load_json("configs", "fabric9976.json")
    assert config["nodes"] == 256 * 256  # every /24 of 10.0.0.0/8's plan
    assert work.sweep_bytes(config) == 9437184
    for key in ("daemon", "daemon_notes", "guarantees"):
        assert config[key] == rack[key], key
    assert set(config["assumed"]) >= {"shape", "size", "vantage", "prefix_plan"}
    assert list(config["reduced_notes"]) == ["nodes"]
    assert "100,000" in config["reduced_notes"]["nodes"]
    assert "lsdb.py" in config["reduced_notes"]["nodes"]
    assert "348,288" in config["on_device"] and "[16, 65,536]" in config["on_device"]
    entry = next(c for c in _bench()["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["reduced"] == ["nodes"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert "BASELINE.json config 3" in entry["source"]
    assert "openr_tpu/topology.py:wan_edges" in entry["source"]
    assert len(entry["why"]) <= 200
    # the last prefix of the plan is the last node's
    lsdb = Lsdb(wan.edges(512, 4, 3))
    assert lsdb.prefix_of[lsdb.nodes[-1]] == "10.1.255.0/24"


@pytest.mark.parametrize("n", [512, 65536])
def test_the_generator_is_a_copy_of_the_programs_wan_edges(n):
    edges = wan.edges(n, 4, 3)
    assert edges == wan_edges(n, degree=4, seed=3)
    assert len(edges) == 2 * n and edges[0][:2] == ("w0", "w1")
    pairs = {frozenset(e[:2]) for e in edges}
    assert len(pairs) == len(edges) and all(len(p) == 2 for p in pairs)
    assert wan.edges(n, 4, 4) != edges  # another seed, another graph
    Lsdb(edges)  # refuses a parallel link


def test_every_candidate_is_a_metric_1_tree_link_with_32_to_128_below(full):
    cell, edges, lsdb = full
    vantage = cell["config_data"]["vantage"]
    want, sizes, dist = _tree_candidates(edges, lsdb, vantage, 32, 128)
    links = cell["params"]["links"]
    assert links == want and len(links) == len({tuple(l) for l in links}) == 66
    assert min(sizes) >= 32 and max(sizes) <= 128 and np.median(sizes) == 48
    # ISSUE 35's first band, which the chip's p95 spread too widely on
    wide, wide_sizes, _ = _tree_candidates(edges, lsdb, vantage, 16, 256)
    assert len(wide) == 145 and np.median(wide_sizes) == 33
    assert [l for l in wide if l in links] == links
    for a, b in links:
        assert lsdb.metric[a][b] == lsdb.metric[b][a] == 1
        assert vantage not in (a, b)
    # the tree the events move: deep, and over many distinct distances
    assert dist.max() == 487 and len(np.unique(dist)) == 417
    toy = bench_run.resolve_cell(TOY_CELL)
    toy_edges = build_edges(toy["config_data"]["topology"])
    toy_links, _, _ = _tree_candidates(
        toy_edges, Lsdb(toy_edges), toy["config_data"]["vantage"], 2, 64
    )
    assert toy["params"]["links"] == toy_links and len(toy_links) == 24


def test_the_cell_is_metric_flaps_numbers_over_a_list():
    cell = bench_run.resolve_cell(CELL)
    params = cell["params"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "listed_metric_flaps", 1)
    assert len(cell["why"]) <= 200 and "66" in cell["why"] and "set-up" in cell["why"]
    assert set(bench_run.load_json("cells", f"{CELL}.json")) == {"links", "verify_events", "what"}
    mix = bench_run.load_json("traffic", "listed_metric_flaps.json")
    flaps = bench_run.load_json("traffic", "metric_flaps.json")
    assert mix["kind"] == params["kind"] == "listed_link_metric_swap"
    assert mix["links"] is None and "groups" not in mix
    for key in ("low", "high", "warmup_events", "event_timeout_s", "verify_events"):
        assert mix[key] == flaps[key], key
    assert (params["low"], params["high"]) == ([1], list(range(3, 19)))
    assert (params["warmup_events"], params["event_timeout_s"]) == (24, 60)
    assert params["verify_events"] == 40 and mix["verify_events"] == 250


def test_the_cell_joins_the_grids_lists_and_not_gc_pause():
    """Held as a beginning: a cell appended after the WAN's joins these lists
    behind it, and nothing here needs an edit for it."""
    bench = _bench()
    assert [w["name"] for w in bench["workloads"]][: len(CELLS)] == CELLS
    older = CELLS[: CELLS.index(CELL)]
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            continue
        cells = m["workloads"]
        # gc_pause_ms.max lists neither: a window may hold no full collection
        expected = "grid10000.metric_flaps" in cells
        assert (CELL in cells) == expected, m["name"]
        if CELL in cells:
            assert all(cells.index(c) < cells.index(CELL) for c in cells if c in older), m["name"]
    p95 = next(m for m in bench["end_to_end"] if m["name"] == "event_to_fib_ms.p95")
    cells = p95["workloads"]
    assert CELL in cells and SPINE_CELL not in cells
    assert all(cells.index(c) < cells.index(CELL) for c in cells if c in older)
    cell = bench_run.resolve_cell(CELL)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "event_to_fib_ms.p50", "event_to_fib_ms.p95", "events_per_s", "setup_s"
    }
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) | {"relax_roofline", "solve_rows", "solve_warm_ms.avg",
                               "delta_route_build_share", "compiles_in_window"} <= reported
    grid = {m["name"] for m in bench_run.resolve_cell("grid10000.metric_flaps")["per_layer"]}
    assert reported == grid and "gc_pause_ms.max" not in reported
    # the four configurations and seven cells this file knows, each on one chip
    assert {"fabric9976", "grid10000", "fabric9976_ssw", CONFIG} <= {
        c["name"] for c in bench["configs"]
    }
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}
    assert all(chips[name] == 1 for name in CELLS)


def _take(params, seed, n):
    gen = listed_link_metric_swap.generate(params, seed)
    return [next(gen) for _ in range(n)]


def test_the_kind_is_seeded_and_a_block_holds_every_link_once():
    params = bench_run.resolve_cell(CELL)["params"]
    links = [tuple(l) for l in params["links"]]

    def stream(seed, n=len(links)):
        return [(e.restore, e.raised, e.metric) for e in _take(params, seed, n)]

    assert stream(BIG) == stream(BIG)
    assert stream(BIG) != stream(BIG + 1)
    seq = stream(BIG)
    assert seq[0][0] is None  # nothing is high before the first event
    assert sorted(r for _, r, _ in seq) == sorted(links)  # a block: each once
    assert sorted(r for _, r, _ in stream(BIG + 1)) == sorted(links)
    assert all(m in params["high"] for _, _, m in seq)
    # each event restores the link that the one before it raised, and never
    # raises the link that is high; no two of 1,056 leave the same state
    long = stream(BIG, 1056)
    assert all(b[0] == a[1] and b[1] != a[1] for a, b in zip(long, long[1:]))
    states = [(raised, metric) for _, raised, metric in long]
    assert len(set(states)) == len(states)
    # the events are link_metric_swap's own, and one deck deals them
    assert isinstance(_take(params, 1, 1)[0], link_metric_swap.Swap)
    with pytest.raises(ValueError, match="two candidate links"):
        next(listed_link_metric_swap.generate(dict(params, links=links[:1]), 1))


def test_every_event_of_1000_moves_a_distance_at_the_vantage(full):
    """At the full size, by the vantage's own row of the reference's graph:
    an event that moves a destination's distance changes its route."""
    cell, edges, _ = full
    lsdb = Lsdb(edges)  # this test's own: the events mutate it
    ref = reference.Reference(lsdb, cell["config_data"]["vantage"])
    me = ref.number[ref.vantage]
    before = dijkstra(ref.graph, directed=True, indices=me)
    moved = []
    for event in _take(cell["params"], BIG + 2, 1000):
        keys = event.apply(lsdb)
        assert 2 <= len(keys) <= 4 and len(set(keys)) == len(keys)
        ref.refresh(key.split(":", 1)[1] for key in keys)
        after = dijkstra(ref.graph, directed=True, indices=me)
        moved.append(int((after != before).sum()))
        before = after
    # two subtrees of 32-128 an event; where one lies inside the other, or
    # a way round costs no more, fewer distances move than lie below
    assert min(moved) >= 1
    assert 64 <= np.median(moved) <= 256 and max(moved) <= 256


def test_every_event_of_300_changes_a_route_in_the_toys_reference():
    cell = bench_run.resolve_cell(TOY_CELL)
    lsdb = Lsdb(build_edges(cell["config_data"]["topology"]))
    ref = reference.Reference(lsdb, cell["config_data"]["vantage"])
    before = ref.table()
    assert len(before) == 2047
    widths = collections.Counter(len(nhs) for nhs in before.values())
    assert widths[1] > 1900 and max(widths) >= 2  # a tree, nearly
    for event in _take(cell["params"], BIG + 3, 300):
        keys = event.apply(lsdb)
        ref.refresh(key.split(":", 1)[1] for key in keys)
        after = ref.table()
        assert len(compare.table_mismatches(before, after)) >= 2, event
        before = after


@pytest.mark.parametrize("index, name", list(enumerate(NEW_METRICS, start=46)))
def test_every_new_metric_file_is_named_by_benchmark_json(index, name):
    """Each by its name, at the index at which it was appended: nothing
    moved, and what is appended later stands behind it."""
    named = [m["name"] for m in _bench()["per_layer"]]
    files = {f[: -len(".json")] for f in os.listdir(os.path.join(bench_run.HERE, "metrics"))}
    assert name in files and named.index(name) == index


@pytest.mark.parametrize("breakage", control.BREAKAGES)
def test_control_breaks_a_guarantee_on_the_toy_wan_and_is_not_correct(breakage):
    cell = bench_run.resolve_cell(TOY_CELL)
    got, compared, _ = control.control_run(cell, seed=BIG + 4, n_events=40, breakage=breakage)
    assert got is False
    assert all(v["limit"] == 0 for v in compared.values())
    assert any(v["value"] > 0 for v in compared.values())


def test_traced_rehearsal_of_the_toy_wan_reads_the_four(capsys, monkeypatch, tmp_path):
    """2,048 nodes through the whole served path, traced: three merged
    degree classes, warm solves, DeltaPath on every event."""
    monkeypatch.setattr(bench_run, "TRACE_DIR", str(tmp_path / "trace"))
    rc = bench_run.main(
        ["--workload", TOY_CELL, "--seed", str(BIG + 5), "--seconds", "1.5",
         "--allow-cpu", "--trace", "1"]
    )
    out, _ = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert all(v["value"] == 0 for v in line["compared"].values())
    metrics = line["metrics"]
    assert metrics["solve_nodes_padded"] == {"value": 2048, "unit": "nodes"}
    assert metrics["sell_classes"] == {"value": 3, "unit": "classes"}
    assert metrics["sell_slots"] == {"value": 10875, "unit": "slots"}
    assert metrics["sell_slots"]["value"] > 8192  # more slots than edges
    assert metrics["delta_columns_per_event"]["value"] > 2
    assert metrics["solve_rows"] == {"value": 12, "unit": "rows"}
    assert metrics["solve_rows_padded"] == {"value": 16, "unit": "rows"}
    assert metrics["relax_rounds_per_event"]["value"] > 3
    assert metrics["delta_route_build_share"]["value"] == 1
    # events of many sizes: an extraction bucket first met inside the
    # window is a compile there, and the metric counts it since PR 35
    assert metrics["compiles_in_window"]["value"] >= 0
    assert metrics["graph_recompiles_in_window"]["value"] == 0
    assert "relax_roofline" not in metrics  # no device plane on the CPU
