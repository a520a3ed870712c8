"""Label routes in the yardstick: a configuration names its reference
(`"reference"`) and its prefixes' forwarding (`"prefix_forwarding"`), and
the comparison reads push stacks and the MPLS table.

`reference_ksp2` (KSP2_ED_ECMP over SR-MPLS, written from the LSDB as plain
data) against the CPU oracle, `SpfSolver.build_route_db` on the program's
LinkState and PrefixState fed the encoder's own bytes, with Fib's choice of
best next hops applied as Fib applies it before the agent: next hop, metric
and push stack of every unicast route, label, action and next hop of every
label route, on grids, a ring with chords and a Clos, with equal-cost ties,
before and after metric changes and links down. The four older
configurations compare as they did; an mpls call with segment routing off
still fails a run; the label rehearsal runs whole on the CPU and its
control and a fault read above 0. CPU runs at toy sizes: nothing here is a
device number."""

import base64
import dataclasses
import json
import random
import subprocess
import sys

import pytest

from chipbench import compare, control, reference, reference_ksp2
from chipbench import run as bench_run
from chipbench.lsdb import Lsdb, WireEncoder, nexthop_v6
from chipbench.topologies import build_edges
from openr_tpu.fib import get_best_nexthops_mpls, get_best_nexthops_unicast
from openr_tpu.lsdb import LinkState, PrefixState
from openr_tpu.solver import SpfSolver
from openr_tpu.types import (
    IpPrefix,
    MplsAction,
    MplsActionCode,
    MplsRoute,
    NextHop,
    UnicastRoute,
)
from openr_tpu.utils import serializer

KSP2 = {"prefix_forwarding": {"type": "SR_MPLS", "algorithm": "KSP2_ED_ECMP"}}
REHEARSAL = "rehearsal_grid_ksp2.metric_flaps"
BIG = 2**31 + 4000  # the driver's seeds do not fit 32 signed bits


def _redrawn(edges, seed, high):
    """The same links with every metric drawn from 1..high: many ties."""
    rng = random.Random(seed)
    return [(a, b, rng.randint(1, high)) for a, b, _ in edges]


def _widest(edges):
    degree = {}
    for a, b, _ in edges:
        degree[a] = degree.get(a, 0) + 1
        degree[b] = degree.get(b, 0) + 1
    return max(sorted(degree), key=degree.get)


CLOS = {"generator": "fabric",
        "args": {"pods": 2, "ssw_per_plane": 2, "fsw_per_pod": 2, "rsw_per_pod": 3}}
GRAPHS = {
    # name -> (edges, vantage)
    "grid": lambda seed: (build_edges({"generator": "grid", "args": {"n": 6}}), "g2_2"),
    "grid_metrics": lambda seed: (
        _redrawn(build_edges({"generator": "grid", "args": {"n": 5}}), seed, 2), "g0_0"),
    "ring_chords": lambda seed: (
        _redrawn(build_edges({"generator": "wan", "args": {"n": 40, "degree": 4, "seed": seed}}),
                 seed, 3), None),
    "clos": lambda seed: (build_edges(CLOS), "rsw0_0"),
    "clos_fabric_switch": lambda seed: (build_edges(CLOS), "fsw1_1"),
}


class Twin:
    """One LSDB twice: plain, for the reference, and as the program's
    LinkState and PrefixState, fed the bytes the benchmark's encoder sends."""

    def __init__(self, edges, vantage, config):
        self.lsdb = Lsdb(edges)
        self.vantage = vantage
        self.wire = WireEncoder(self.lsdb, config["prefix_forwarding"])
        self.link_state, self.prefix_state = LinkState("0"), PrefixState()
        for key in self.wire.all_keys():
            self._publish(key)
        self.reference = reference_ksp2.Reference(self.lsdb, vantage, config)

    def _publish(self, key):
        value = self.wire.key_vals([key])[key]["value"]
        db = serializer.loads(base64.b64decode(value))
        if key.startswith("adj:"):
            self.link_state.update_adjacency_database(db)
        else:
            self.prefix_state.update_prefix_database(db)

    def moved(self, nodes):
        for node in nodes:
            self._publish(f"adj:{node}")
        self.reference.refresh(nodes)

    def oracle(self):
        """What Fib hands the agent from the CPU oracle's route db."""
        me = self.vantage
        db = SpfSolver(me).build_route_db(me, {"0": self.link_state}, self.prefix_state)
        unicast = [
            UnicastRoute(r.dest, tuple(get_best_nexthops_unicast(list(r.nexthops))))
            for r in (e.to_unicast_route() for e in db.unicast_entries.values())
        ]
        mpls = [
            MplsRoute(r.top_label, tuple(get_best_nexthops_mpls(list(r.nexthops))))
            for r in (e.to_mpls_route() for e in db.mpls_entries.values())
        ]
        return compare.routes_as_table(unicast), compare.mpls_routes_as_table(mpls)


def _assert_equal(twin, what):
    got, want = twin.reference.tables(), twin.oracle()
    for side in (0, 1):
        wrong = compare.table_mismatches(got[side], want[side])
        assert not wrong, (what, side, [(k, got[side].get(k), want[side].get(k)) for k in wrong[:2]])
    return got


@pytest.mark.parametrize("seed", [BIG + 1, 3300040002])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_reference_ksp2_equals_the_cpu_oracle(graph, seed):
    edges, vantage = GRAPHS[graph](seed)
    twin = Twin(edges, vantage or _widest(edges), KSP2)
    lsdb, me = twin.lsdb, twin.vantage
    rng = random.Random(seed)
    links = sorted((a, b) for a in lsdb.metric for b in lsdb.metric[a] if a < b)
    own = sorted(lsdb.metric[me])
    seen = _assert_equal(twin, "first table")
    pushed = second = php = swap = 0
    steps = [
        ("metric", lambda: rng.choice(links), lambda a, b: lsdb.set_metric(a, b, rng.randint(2, 4))),
        ("down", lambda: rng.choice(links), lambda a, b: lsdb.set_link_up(a, b, False)),
        ("own metric", lambda: (me, rng.choice(own)), lambda a, b: lsdb.set_metric(a, b, 3)),
        ("own down", lambda: (me, rng.choice(own)), lambda a, b: lsdb.set_link_up(a, b, False)),
        ("metric", lambda: rng.choice(links), lambda a, b: lsdb.set_metric(a, b, 1)),
        ("all up", lambda: None, None),
    ]
    for what, pick, move in steps:
        for unicast, mpls in [seen]:
            pushed += sum(1 for nhs in unicast.values() for nh in nhs if nh[3])
            second += sum(1 for nhs in unicast.values() if len({nh[2] for nh in nhs}) > 1)
            actions = [nh[2] for nhs in mpls.values() for nh in nhs]
            php, swap = php + actions.count("PHP"), swap + actions.count("SWAP")
        if move is None:
            nodes = set()
            for a, b in sorted(lsdb.down):
                nodes |= set(lsdb.set_link_up(a, b, True))
        else:
            nodes = set(move(*pick()))
        twin.moved(sorted(nodes))
        seen = _assert_equal(twin, what)
    # the comparison had stacks, second paths of another cost, PHP and SWAP
    assert pushed and php and swap
    if graph != "clos":  # a rack switch's paths all cost the same
        assert second


def test_the_second_path_set_and_the_tie_order_are_upstreams():
    """A 3 x 3 grid from its corner, by hand. Toward the far corner six
    shortest paths tie; the trace takes, at each node, the path link whose
    far end comes first by (distance, name), so its two edge-disjoint paths
    push the labels of g0_2, g1_2 and of g1_1, g2_1 (the reverse order would
    push g2_0, g2_1 and g1_1, g1_2), and the second set, with the corner's
    two links ignored, is empty. Toward a node of row 0 the first set is one
    path and the second goes round through row 1."""
    twin = Twin(build_edges({"generator": "grid", "args": {"n": 3}}), "g0_0", KSP2)
    unicast, mpls = _assert_equal(twin, "by hand")
    label, prefix = twin.lsdb.label_of, twin.lsdb.prefix_of

    def hops(node):
        return sorted((nh[1], nh[2], nh[3]) for nh in unicast[prefix[node]])

    assert hops("g2_2") == [
        ("if-g0_0-g0_1", 4, (label["g2_2"], label["g1_2"], label["g0_2"])),
        ("if-g0_0-g1_0", 4, (label["g2_2"], label["g2_1"], label["g1_1"])),
    ]
    assert hops("g0_2") == [
        ("if-g0_0-g0_1", 2, (label["g0_2"],)),
        ("if-g0_0-g1_0", 4, (label["g0_2"], label["g1_2"], label["g1_1"])),
    ]
    assert hops("g0_1") == [
        ("if-g0_0-g0_1", 1, ()),
        ("if-g0_0-g1_0", 3, (label["g0_1"], label["g1_1"])),
    ]
    # label routes: PHP toward a neighbour, SWAP over both first hops beyond
    assert mpls[label["g0_1"]] == {(nexthop_v6("g0_1"), "if-g0_0-g0_1", "PHP", ())}
    assert mpls[label["g1_1"]] == {
        (nexthop_v6(peer), f"if-g0_0-{peer}", "SWAP", (label["g1_1"],))
        for peer in ("g0_1", "g1_0")
    }
    assert mpls[label["g0_0"]] == {("::", None, "POP_AND_LOOKUP", ())}


def test_the_reference_imports_nothing_of_the_program():
    code = (
        "import sys, chipbench.reference_ksp2, chipbench.compare, chipbench.control; "
        "print(sorted(m for m in sys.modules if m.split('.')[0] == 'openr_tpu'))"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "[]"


# the control on the older rehearsals, seed 2**31 + 40, 40 events, as it
# read before label routes: (table, event, unprogrammed) for stale, first_hop
BEFORE = {
    "rehearsal_fabric.metric_flaps": ((2, 39, 1), (17, 16, 24)),
    "rehearsal_fabric.prefix_churn": ((2, 39, 1), (16, 20, 2)),
    "rehearsal_fabric.own_link_flaps": ((27, 39, 1), (22, 40, 0)),
    "rehearsal_fabric_ssw.metric_flaps": ((2, 39, 1), (1, 0, 0)),
    "rehearsal_fabric_ssw.own_link_flaps": ((7, 39, 1), (4, 40, 0)),
    "rehearsal_wan.listed_metric_flaps": ((37, 39, 1), (18, 11, 0)),
}


@pytest.mark.parametrize("cell_name", sorted(BEFORE))
def test_the_older_configurations_compare_exactly_as_before(cell_name):
    cell = bench_run.resolve_cell(cell_name)
    config = cell["config_data"]
    # they name neither key, and segment routing is off
    assert "reference" not in config and "prefix_forwarding" not in config
    assert not compare.segment_routing(config)
    assert control.breakages(config) == control.BREAKAGES
    for breakage, want in zip(control.BREAKAGES, BEFORE[cell_name]):
        ok, compared, _ = control.control_run(cell, 2**31 + 40, 40, breakage)
        got = tuple(compared[k]["value"] for k in
                    ("table_mismatches", "event_mismatches", "events_unprogrammed"))
        assert not ok and got == want, breakage
        assert compared["mpls_table_mismatches"]["value"] == 0


@pytest.mark.parametrize("name", ["rehearsal_fabric", "rehearsal_fabric_ssw", "rehearsal_wan", "grid10000"])
def test_the_default_reference_gives_the_plain_table_with_empty_stacks(name):
    config = bench_run.load_json("configs", f"{name}.json")
    lsdb = Lsdb(build_edges(config["topology"]))
    ref = reference.Reference(lsdb, config["vantage"], config)
    table = ref.table()
    unicast, mpls = ref.tables()
    assert mpls == {}
    assert unicast == {p: frozenset((*nh, ()) for nh in nhs) for p, nhs in table.items()}
    # the program's routes with no MPLS action read back the same
    routes = [
        UnicastRoute(IpPrefix(p), tuple(NextHop(address=a, iface=i, metric=m) for a, i, m in nhs))
        for p, nhs in table.items()
    ]
    assert compare.routes_as_table(routes) == unicast
    # and the encoder writes IP / SP_ECMP as it did before the key existed
    node = config["vantage"]
    plain, stated = WireEncoder(lsdb), WireEncoder(lsdb, {"type": "IP", "algorithm": "SP_ECMP"})
    key = f"prefix:{node}"
    assert plain.key_vals([key])[key]["value"] == stated.key_vals([key])[key]["value"]


def test_an_mpls_call_with_segment_routing_off_fails_the_comparison():
    pop = MplsRoute(150, (NextHop("::", mpls_action=MplsAction(MplsActionCode.POP_AND_LOOKUP)),))
    unicast = {"10.0.0.0/24": frozenset({("a", "if", 1, ())})}
    held = (unicast, compare.mpls_routes_as_table([pop]))
    events = [[("add_mpls_routes", [pop])]]

    def run(want, on):  # the agent ends holding the label route
        return compare.compare(
            final=held, agent_events=events, tables=lambda i: ((unicast, {}), want)[i],
            verify=[0], updates_per_event=[1], counter_moves={}, segment_routing=on,
        )

    ok, compared, notes = run(held, False)
    assert not ok and compared["event_mismatches"]["value"] == 1
    assert "segment routing off" in notes[0]
    ok, compared, _ = run(held, True)
    assert ok and all(v["value"] == 0 for v in compared.values())
    # on, a label route that the reference does not hold is counted
    ok, compared, _ = run((unicast, {}), True)
    assert not ok and compared["mpls_table_mismatches"]["value"] == 1
    assert compared["event_mismatches"]["value"] == 1


def _run(capsys, cell, seed, *extra):
    rc = bench_run.main(
        ["--workload", cell, "--seed", str(seed), "--seconds", "2", "--allow-cpu", *extra]
    )
    out, err = capsys.readouterr()
    return rc, json.loads(out.strip().splitlines()[-1]), err


def test_a_run_whose_fib_programs_labels_under_a_configuration_without_them_is_not_correct(
    capsys, monkeypatch
):
    """rehearsal_fabric states segment routing off; a Fib that programs
    label routes all the same fails the run."""
    import openr_tpu.openr as daemon

    real = daemon.Fib
    monkeypatch.setattr(
        daemon, "Fib",
        lambda config, *a, **kw: real(dataclasses.replace(config, enable_segment_routing=True), *a, **kw),
    )
    rc, line, _ = _run(capsys, "rehearsal_fabric.metric_flaps", BIG + 5, "--trace", "0")
    assert rc == 0 and line["correct"] is False
    assert line["compared"]["mpls_table_mismatches"]["value"] > 0
    assert line["compared"]["event_mismatches"]["value"] > 0


def test_label_rehearsal_runs_whole_on_the_cpu_and_is_correct(capsys):
    rc, line, err = _run(capsys, REHEARSAL, BIG + 6, "--trace", "0")
    assert rc == 0 and line["correct"] is True and line["attempted"] > 5
    assert list(line["compared"]) == list(compare.LIMITS)
    assert all(v == {"value": 0, "limit": 0} for v in line["compared"].values())
    assert "label routes" in err and "CPU REHEARSAL" in err


def test_a_label_routes_action_altered_in_fib_is_not_correct(capsys, monkeypatch):
    """The timed path broken underneath the harness: Fib hands the agent
    the far corner's label route with PHP where the route swaps."""
    import openr_tpu.fib.fib as fib

    config = bench_run.load_json("configs", "rehearsal_grid_ksp2.json")
    corner = Lsdb(build_edges(config["topology"])).label_of["g7_7"]
    real = fib.get_best_nexthops_mpls

    def altered(nexthops):
        best = real(nexthops)
        if any(nh.mpls_action.swap_label == corner for nh in best):
            return [dataclasses.replace(nh, mpls_action=MplsAction(MplsActionCode.PHP)) for nh in best]
        return best

    monkeypatch.setattr(fib, "get_best_nexthops_mpls", altered)
    rc, line, _ = _run(capsys, REHEARSAL, BIG + 7, "--trace", "0")
    assert rc == 0 and line["correct"] is False
    assert line["compared"]["mpls_table_mismatches"]["value"] > 0


@pytest.mark.parametrize("breakage", control.BREAKAGES + control.LABEL_BREAKAGES)
def test_control_on_the_label_rehearsal_comes_out_not_correct(breakage):
    cell = bench_run.resolve_cell(REHEARSAL)
    assert control.breakages(cell["config_data"]) == control.BREAKAGES + control.LABEL_BREAKAGES
    ok, compared, _ = control.control_run(cell, BIG + 8, 40, breakage)
    assert ok is False and all(v["limit"] == 0 for v in compared.values())
    if breakage == "push_stack":
        # every route of the toy pushes labels on some next hop
        assert compared["table_mismatches"]["value"] > 0
        assert compared["mpls_table_mismatches"]["value"] == 0
