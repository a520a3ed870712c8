"""ISSUE 29: a vantage with more neighbours than a machine word has bits.
The reference keeps every first hop of every set (until PR 29 it packed a
set into an `np.int64`, and from the 64th neighbour on gave routes a part
of their set or none), and the cell `fabric9976.own_link_flaps` sends what
its files say. A rehearsal: nothing here is a device number."""

import collections
import json
import os

import pytest

from chipbench import compare, reference
from chipbench import run as bench_run
from chipbench.lsdb import Lsdb, if_name, nexthop_v4
from chipbench.topologies import build_edges
from chipbench.traffic_kinds import link_down_swap, link_metric_swap

HUB = "rehearsal_fabric_ssw"
OWN_LINKS = "fabric9976.own_link_flaps"


def _lsdb(config_name):
    config = bench_run.load_json("configs", f"{config_name}.json")
    return config, Lsdb(build_edges(config["topology"]))


def test_reference_names_all_70_first_hops_of_a_hub_known_by_hand():
    """One plane of two spines over 70 pods of one fsw and two rsw, from
    `ssw0_0`: every fsw is a neighbour and the one first hop toward itself
    and its two racks; the other spine lies behind all 70."""
    config, lsdb = _lsdb(HUB)
    assert config["vantage"] == "ssw0_0" and config["vantage_up_neighbours"] == 70
    assert len(lsdb.nodes) == config["nodes"] == 212
    assert 2 * lsdb.n_links == config["directed_edges"] == 560
    table = reference.route_table(lsdb, "ssw0_0")
    assert len(table) == 211

    def hop(p, metric):
        fsw = f"fsw{p}_0"
        return (nexthop_v4("ssw0_0", fsw), if_name("ssw0_0", fsw), metric)

    assert table[lsdb.prefix_of["ssw0_1"]] == {hop(p, 2) for p in range(70)}
    assert len(table[lsdb.prefix_of["ssw0_1"]]) == 70
    for p in range(70):
        assert table[lsdb.prefix_of[f"fsw{p}_0"]] == {hop(p, 1)}
        for r in range(2):
            assert table[lsdb.prefix_of[f"rsw{p}_{r}"]] == {hop(p, 2)}
    # a neighbour's link down: it leaves the one wide set and is reached
    # round through the other spine, over the 69 that stay
    ref = reference.Reference(lsdb, "ssw0_0")
    ref.refresh(lsdb.set_link_up("ssw0_0", "fsw64_0", False))
    table = ref.table()
    rest = {hop(p, 2) for p in range(70) if p != 64}
    assert table[lsdb.prefix_of["ssw0_1"]] == rest
    assert table[lsdb.prefix_of["fsw64_0"]] == {(a, i, 3) for a, i, _ in rest}
    assert table[lsdb.prefix_of["rsw64_1"]] == {(a, i, 4) for a, i, _ in rest}


@pytest.mark.parametrize("vantage, sets", [
    # a rack switch: 8 fsw of its pod; its 8 fsw and the 8 x 36 + 172 x 8
    # switches straight above one of them lie behind that one alone
    ("rsw0_0", {8: 8303, 1: 8 + 288 + 1376}),
    # a fabric switch: its 84 neighbours; the other pods' racks and fsw of
    # its plane over its 36 ssw; its own pod's other fsw and their planes
    # over its 48 rsw; the other pods' other fsw over all 84
    ("fsw0_0", {1: 84, 36: 172 * 48 + 172, 48: 7 + 7 * 36, 84: 172 * 7}),
    # a spine switch: 173 fsw, one a pod, and each pod's 8 fsw and 48 rsw
    # behind its own; the other 35 ssw of the plane and the 7 x 36 of the
    # others behind all 173
    ("ssw0_0", {1: 173 * 56, 173: 35 + 7 * 36}),
], ids=["rsw0_0", "fsw0_0", "ssw0_0"])
def test_reference_on_the_real_clos_from_each_tier(vantage, sets):
    """`fabric9976` as its three tiers see it: how many routes have how many
    first hops, counted by hand. Only the rack switch has under 64
    neighbours."""
    _, lsdb = _lsdb("fabric9976")
    table = reference.route_table(lsdb, vantage)
    assert len(table) == 9975
    assert collections.Counter(len(nhs) for nhs in table.values()) == sets
    if vantage == "ssw0_0":
        assert sets == {1: 9688, 173: 287}
        wide = table[lsdb.prefix_of["ssw7_35"]]
        assert {iface for _, iface, _ in wide} == {
            if_name("ssw0_0", f"fsw{p}_0") for p in range(173)
        }
        assert {metric for _, _, metric in wide} == {4}
    # equal sets are one object: a table holds a few hundred of them
    assert len({id(nhs) for nhs in table.values()}) < 600


def test_a_reference_that_finds_no_first_hop_for_a_reachable_node_raises():
    lsdb = Lsdb(build_edges({"generator": "grid", "args": {"n": 3}}))
    ref = reference.Reference(lsdb, "g0_0")
    # the LSDB moves and nobody tells the reference: its graph still holds
    # the old metric, so no neighbour's distance adds up to the vantage's
    lsdb.set_metric("g0_0", "g0_1", 7)
    lsdb.set_metric("g0_0", "g1_0", 7)
    with pytest.raises(ValueError, match="over no first hop"):
        ref.table()
    ref.refresh(["g0_0", "g0_1", "g1_0"])
    assert len(ref.table()) == 8


def test_own_link_flaps_on_the_clos_is_a_replayed_set_of_8_states():
    """The cell as its files and `BENCHMARK.json` give it: the vantage's 8
    up-links, one down at any time, every event verified."""
    cell = bench_run.resolve_cell(OWN_LINKS)
    params, config = cell["params"], cell["config_data"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("fabric9976", "own_link_flaps", 1)
    assert "replayed set" in cell["why"] and "8 events" in cell["why"]
    assert params["kind"] == "link_down_swap"
    assert (params["warmup_events"], params["event_timeout_s"], params["verify_events"]) == (4, 120, 250)
    links = [l for g in params["groups"] for l in link_metric_swap.expand(g)]
    assert links == [("rsw0_0", f"fsw0_{f}") for f in range(8)]
    assert config["vantage"] == "rsw0_0" and config["vantage_up_neighbours"] == len(links)
    gen = link_down_swap.generate(params, 2**31 + 29)
    stream = [next(gen) for _ in range(200)]
    assert {e.down for e in stream} == set(links)  # 8 LSDB states
    assert len({(e.up, e.down) for e in stream[1:]}) > 40  # of 56 transitions
    # what the cell reports: the cold path's metrics, not the warm path's
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"solve_cold_ms.avg", "route_build_ms.avg", "solve_d2h_ms.avg",
            "full_solves_per_event", "graph_recompiles_in_window",
            "compiles_in_window.cold"} <= reported
    assert not {"solve_warm_ms.avg", "route_build_delta_ms.avg", "delta_build_ms.avg",
                "delta_extract_device_ms", "solve_delta_extract_ms.avg",
                "solve_mirror_patch_ms.avg"} & reported
    # a window of 8 events has no 95th percentile: the metric lists the
    # cells that send hundreds, and this one is not among them
    assert {m["name"] for m in cell["end_to_end"]} == {
        "event_to_fib_ms.p50", "events_per_s", "setup_s"
    }


def test_every_metric_file_is_named_by_benchmark_json():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as fh:
        named = {m["name"] for m in json.load(fh)["per_layer"]}
    files = {
        f[: -len(".json")]
        for f in os.listdir(os.path.join(bench_run.HERE, "metrics"))
    }
    assert files == named


def test_an_own_link_event_moves_8721_routes_of_the_clos():
    """What one event of the cell has to re-program, by the reference: the
    8,303 rack routes swap one of 7 first hops, and the 1 + 36 + 172
    switches of each of the two links' planes change their metric."""
    config, lsdb = _lsdb("fabric9976")
    params = bench_run.resolve_cell(OWN_LINKS)["params"]
    ref = reference.Reference(lsdb, config["vantage"])
    gen = link_down_swap.generate(params, 2**31 + 30)
    keys = next(gen).apply(lsdb)
    ref.refresh(key.split(":", 1)[1] for key in keys)
    before = ref.table()
    for _ in range(3):
        keys = next(gen).apply(lsdb)
        assert len(keys) == 3 and keys[0] == "adj:rsw0_0"
        ref.refresh(key.split(":", 1)[1] for key in keys)
        after = ref.table()
        assert len(compare.table_mismatches(before, after)) == 8303 + 2 * 209
        # the down link's plane is reached round over the 7 links that are up
        assert collections.Counter(len(nhs) for nhs in after.values()) == {
            7: 8303 + 209, 1: 9975 - 8303 - 209
        }
        before = after
