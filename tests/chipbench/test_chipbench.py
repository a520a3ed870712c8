"""The benchmark's own tests: its arithmetic, its data files, its traffic,
its reference, and one whole run on the CPU (a rehearsal: nothing here is
a device number, and no test touches libtpu)."""

import json
import os
import re

import pytest

from chipbench import compare, control, reference, trace_reduce, work
from chipbench import run as bench_run
from chipbench.lsdb import Lsdb
from chipbench.topologies import build_edges
from chipbench.traffic_kinds import link_metric_swap as flaps

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REHEARSAL = "rehearsal_fabric.metric_flaps"


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_interval_union_and_idle_share():
    busy = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert trace_reduce.merge(busy) == [(0.0, 2.0), (3.0, 4.0)]
    assert trace_reduce.union_seconds(busy) == pytest.approx(3.0)
    assert trace_reduce.idle_share(busy, 10.0) == pytest.approx(0.7)
    with pytest.raises(ValueError):
        trace_reduce.idle_share(busy, 0.0)


def test_idle_gaps_are_labelled_by_the_host_span_that_covers_them():
    busy = [(1.0, 2.0), (5.0, 6.0)]
    found = trace_reduce.gaps(busy, 0.0, 6.5)
    assert found == [(2.0, 5.0), (0.0, 1.0), (6.0, 6.5)]  # longest first
    host = [("loop", 0.0, 10.0), ("route_build", 2.0, 5.0), ("debounce", 0.2, 0.9)]
    # two spans cover the gap whole: the shorter says more
    assert trace_reduce.label_gap((2.0, 5.0), host) == "route_build"
    assert trace_reduce.label_gap((0.0, 1.0), host) == "loop"
    assert trace_reduce.label_gap((0.0, 1.0), []) == trace_reduce.NO_SPAN
    # a short span at the gap's edge does not name the gap
    assert trace_reduce.label_gap((2.0, 5.0), [("sync", 1.9, 2.3)]) == trace_reduce.NO_SPAN
    assert trace_reduce.totals_by_name(
        [("a", 0, 1), ("b", 0, 3), ("a", 2, 3)]
    ) == [("b", 3), ("a", 2)]
    # an op's event is named by its whole HLO line
    assert trace_reduce.short_name(
        "%while.31 = (pred[16384,16]{0,1:T(8,128)(4,1)S(1)}, s32[]{:T(128)}) "
        "while((pred[16384,16]{0,1:T(8,128)} %tuple.224), condition=%c, body=%b"
    ) == "%while.31 while"
    assert trace_reduce.short_name("%fusion.2 = s32[8]{0:T(128)S(1)} fusion(s32[16384]{0} %b)") == "%fusion.2 fusion"
    assert trace_reduce.short_name("jit_solve(123)") == "jit_solve(123)"


@pytest.mark.parametrize(
    "name, nodes, directed_edges, rows",
    [("fabric9976", 9976, 232512, 9), ("grid10000", 10000, 39600, 3)],
)
def test_sweep_bytes_follow_the_configuration(name, nodes, directed_edges, rows):
    config = bench_run.load_json("configs", f"{name}.json")
    lsdb = Lsdb(build_edges(config["topology"]))
    # the file's counts are the generated topology's
    assert len(lsdb.nodes) == config["nodes"] == nodes
    assert 2 * lsdb.n_links == config["directed_edges"] == directed_edges
    assert 1 + len(lsdb.metric[config["vantage"]]) == work.solve_rows(config) == rows
    assert work.sweep_bytes(config) == directed_edges * 8 + 2 * rows * nodes * 4
    assert work.sweep_floor_s(config, "TPU v5 lite") == pytest.approx(
        work.sweep_bytes(config) / 819e9
    )


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks for device_kind"):
        work.peaks("TPU v9 imaginary")


def test_benchmark_json_names_units_and_files():
    bench = _bench()
    name_re = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit_re = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert name_re.match(m["name"]) and unit_re.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", [])) <= cells
    configs = {c["name"] for c in bench["configs"]}
    assert configs == {w["config"] for w in bench["workloads"]}  # each is used
    # at most half of the cells, rounded down, on four chips; one always may
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    for w in bench["workloads"]:
        assert name_re.match(w["name"]) and w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert w["config"] in configs
        cell = bench_run.resolve_cell(w["name"])  # finds every file it names
        assert cell["params"]["kind"] and cell["per_layer"] and len(cell["end_to_end"]) >= 2
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["file"].split("/")[0] in bench["paths"]
    for m in bench["per_layer"]:
        spec = bench_run.load_json("metrics", m["name"] + ".json")
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
        assert spec["unit"] == m["unit"] and "workloads" not in spec
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
        # every cell that reports the metric reports what it should move
        moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells)), m["name"]
        if "trace" in spec["source"]:
            assert os.path.exists(
                os.path.join(ROOT, "chipbench", "reducers", spec["source"]["trace"] + ".py")
            )


def _events(params, seed, n):
    gen = flaps.generate(params, seed)
    return [next(gen) for _ in range(n)]


def test_metric_flaps_is_deterministic_and_every_seed_sends_the_same_links():
    params = bench_run.resolve_cell(REHEARSAL)["params"]
    links = flaps.expand(params["groups"][0])
    assert len(links) == 60 and ("fsw0_0", "rsw0_1") in links
    assert not any("rsw0_0" in link for link in links)  # none is the vantage's own

    def take(seed, n=len(links)):
        return [(e.restore, e.raised, e.metric) for e in _events(params, seed, n)]

    big = 2**31 + 12345  # the driver's seeds do not fit 32 signed bits
    assert take(big) == take(big)
    assert take(big) != take(big + 1)
    seq = take(big)
    assert seq[0][0] is None  # nothing is high before the first event
    # each event restores the link that the one before it raised
    assert all(b[0] == a[1] and b[1] != a[1] for a, b in zip(seq, seq[1:]))
    assert all(m in params["high"] for _, _, m in seq)
    # a block is every link once: every seed sends the same links, reordered
    assert sorted(r for _, r, _ in take(1)) == sorted(r for _, r, _ in take(2)) == sorted(links)
    assert flaps.expand({"a": "g0_{k}", "b": "g0_{k1}", "ranges": {"k": [1, 3]}}) == [
        ("g0_1", "g0_2"), ("g0_2", "g0_3"), ("g0_3", "g0_4"),
    ]


@pytest.mark.parametrize("cell_name", ["fabric9976.metric_flaps", "grid10000.metric_flaps"])
def test_no_two_events_of_a_run_leave_the_lsdb_in_the_same_state(cell_name):
    params = bench_run.resolve_cell(cell_name)["params"]
    # about as many events as the fastest run of 40 s has sent
    n = 1056
    states = [(e.raised, e.metric) for e in _events(params, 2**31 + 5, n)]
    assert len(set(states)) == len(states)


@pytest.mark.parametrize("topology, vantage, groups", [
    ({"generator": "fabric", "args": {"pods": 3, "ssw_per_plane": 2, "fsw_per_pod": 4, "rsw_per_pod": 6}},
     "rsw0_0",
     [{"a": "fsw{p}_{f}", "b": "rsw{p}_{r}", "ranges": {"p": [0, 2], "f": [0, 3], "r": [1, 5]}}]),
    ({"generator": "grid", "args": {"n": 12}},
     "g0_0",
     [{"a": "g0_{k}", "b": "g0_{k1}", "ranges": {"k": [1, 4]}},
      {"a": "g{k}_0", "b": "g{k1}_0", "ranges": {"k": [1, 4]}}]),
])
def test_every_event_of_the_mix_changes_a_route_at_the_vantage(topology, vantage, groups):
    """The cells' candidate links in small: the reference says that each
    event moves the routes toward both links' far sides."""
    params = dict(bench_run.load_json("traffic", "metric_flaps.json"), groups=groups)
    lsdb = Lsdb(build_edges(topology))
    ref = reference.Reference(lsdb, vantage)
    before = ref.table()
    for event in _events(params, 77, 40):
        keys = event.apply(lsdb)
        assert 2 <= len(keys) <= 4 and len(set(keys)) == len(keys)
        ref.refresh(key.split(":", 1)[1] for key in keys)
        after = ref.table()
        changed = compare.table_mismatches(before, after)
        same_far_side = event.restore is None or event.restore[1] == event.raised[1]
        assert len(changed) >= (1 if same_far_side else 2), event
        before = after


def test_reference_ecmp_on_a_grid_known_by_hand():
    lsdb = Lsdb(build_edges({"generator": "grid", "args": {"n": 3}}))
    table = reference.route_table(lsdb, "g0_0")

    def hops(node):
        return {(iface, metric) for _, iface, metric in table[lsdb.prefix_of[node]]}

    assert hops("g0_2") == {("if-g0_0-g0_1", 2)}
    assert hops("g1_1") == {("if-g0_0-g0_1", 2), ("if-g0_0-g1_0", 2)}
    assert hops("g2_2") == {("if-g0_0-g0_1", 4), ("if-g0_0-g1_0", 4)}
    lsdb.set_metric("g0_0", "g0_1", 5)  # everything now leaves through g1_0
    table = reference.route_table(lsdb, "g0_0")
    assert hops("g0_1") == {("if-g0_0-g1_0", 3)}
    assert len(table) == 8


@pytest.mark.parametrize("cell_name", [
    REHEARSAL, "rehearsal_fabric.prefix_churn", "rehearsal_fabric.own_link_flaps",
])
@pytest.mark.parametrize("breakage", control.BREAKAGES)
def test_control_breaks_a_guarantee_and_comes_out_not_correct(breakage, cell_name):
    cell = bench_run.resolve_cell(cell_name)
    got, compared, _ = control.control_run(cell, seed=2**31 + 7, n_events=40, breakage=breakage)
    assert got is False
    assert all(v["limit"] == 0 for v in compared.values())
    assert any(v["value"] > 0 for v in compared.values())


def test_comparison_refuses_split_events_and_calls_no_configuration_allows():
    tables = ({"10.0.0.0/24": frozenset({("a", "if", 1, ())})}, {})
    ok, compared, _ = compare.compare(
        final=tables, agent_events=[[], []], tables=lambda i: tables,
        verify=[0, 1], updates_per_event=[1, 2], counter_moves={},
    )
    assert not ok and compared["events_not_one_update"]["value"] == 1
    # with segment routing off, as every configuration of a cell has it
    assert "segment routing off does not allow" in compare.event_is_wrong(
        [("add_mpls_routes", [])], ([], []), tables
    )
    assert "no configuration allows" in compare.event_is_wrong(
        [("add_static_routes", [])], ([], []), tables
    )
    for call in ("sync_fib", "sync_mpls_fib"):
        for on in (False, True):
            assert "full sync" in compare.event_is_wrong([(call, [])], ([], []), tables, on)
    # a sample keeps the last event and is drawn from the seed
    assert compare.choose_events(5, 10, 1) == [0, 1, 2, 3, 4]
    sample = compare.choose_events(500, 50, 2**31 + 3)
    assert len(sample) == 50 and sample[-1] == 499
    assert sample == compare.choose_events(500, 50, 2**31 + 3)


def test_topology_generators_are_found_by_name_and_keep_upstreams_shape():
    edges = build_edges({"generator": "fabric", "args": {"pods": 1}})
    lsdb = Lsdb(edges)
    # upstream's smallest fabric: 8 x 36 spines and one pod of 8 + 48
    assert len(lsdb.nodes) == 344 and lsdb.n_links == 8 * (48 + 36)
    assert len(lsdb.metric["fsw0_3"]) == 84 and len(lsdb.metric["rsw0_0"]) == 8
    assert set(lsdb.metric["ssw3_0"]) == {"fsw0_3"}  # plane 3 meets fsw 3 only
    with pytest.raises(ValueError, match="unknown topology generator"):
        build_edges({"generator": "moebius"})
    with pytest.raises(ValueError, match="bad topology generator name"):
        build_edges({"generator": "../x"})


def test_a_renamed_device_program_is_an_error_not_a_silent_metric():
    summary = trace_reduce.TraceSummary(
        window_s=1.0, busy_s=0.5, ops=[], host=[], events=[],
        programs=[("jit_solve(1)", 0.0, 0.2), ("jit_solve(1)", 0.5, 0.6)],
    )
    assert summary.program_seconds("jit_solve") == (pytest.approx(0.3), 2)
    with pytest.raises(LookupError, match="jit_solve"):
        summary.program_seconds("jit_relax")
    # no device plane (a rehearsal): nothing to read, and no error
    summary.programs = []
    assert summary.program_seconds("jit_solve") == (0, 0)


def _run(capsys, *extra):
    rc = bench_run.main(
        ["--workload", REHEARSAL, "--seed", str(2**31 + 99), "--seconds", "1.5",
         "--allow-cpu", *extra]
    )
    out, err = capsys.readouterr()
    return rc, json.loads(out.strip().splitlines()[-1]), err


def test_whole_run_on_the_cpu_prints_the_contracts_line(capsys):
    rc, line, err = _run(capsys, "--trace", "0")
    assert rc == 0
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 10
    assert set(line["metrics"]) == {m["name"] for m in _bench()["end_to_end"]}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"  # a rehearsal, and it says so
    assert "CPU REHEARSAL" in err
    # the numbers compared, each beside its limit, end standard error
    assert err.strip().splitlines()[-1].startswith("compared ")


def test_traced_run_reports_per_layer_metrics_and_leaves_out_what_it_cannot_read(capsys):
    rc, line, _ = _run(capsys, "--trace", "1")
    assert rc == 0 and line["correct"] is True
    assert {"busy_s", "window_s"} <= set(line["device"])
    got = set(line["metrics"])
    assert {"debounce_ms.avg", "solve_warm_ms.avg", "delta_route_build_share",
            "relax_rounds_per_event", "compiles_in_window"} <= got
    assert line["metrics"]["delta_route_build_share"]["value"] == 1
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    # no device plane on the CPU: a roofline or idle share is left out,
    # never reported as 0
    assert not {"relax_roofline", "device_idle_pct", "solve_device_ms"} & got


def test_an_answer_altered_where_it_is_produced_is_not_correct(capsys, monkeypatch):
    """The timed path broken underneath the harness: Fib drops a member of
    every equal-cost set before it programs the agent."""
    import openr_tpu.fib.fib as fib

    real = fib.get_best_nexthops_unicast
    monkeypatch.setattr(
        fib, "get_best_nexthops_unicast", lambda nhs: real(nhs)[:1]
    )
    rc, line, _ = _run(capsys, "--trace", "0")
    assert rc == 0 and line["correct"] is False
    assert line["compared"]["table_mismatches"]["value"] > 0
