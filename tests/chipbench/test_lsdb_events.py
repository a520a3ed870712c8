"""ISSUE 28: events that are not a metric change. The LSDB's two further
mutations, what the wire encoder and the reference make of them, the kinds
`prefix_swap` and `link_down_swap`, and a whole CPU run of each (a
rehearsal: nothing here is a device number, and no test touches libtpu)."""

import base64
import hashlib
import json
import os

import pytest

from chipbench import compare, reference
from chipbench import run as bench_run
from chipbench.lsdb import Lsdb, WireEncoder
from chipbench.topologies import build_edges
from chipbench.traffic_kinds import link_down_swap, link_metric_swap, prefix_swap

FABRIC = {"generator": "fabric", "args": {"pods": 3, "ssw_per_plane": 2, "fsw_per_pod": 4, "rsw_per_pod": 6}}
GRID = {"generator": "grid", "args": {"n": 12}}
FABRIC_LINKS = [{"a": "fsw{p}_{f}", "b": "rsw{p}_{r}", "ranges": {"p": [0, 2], "f": [0, 3], "r": [1, 5]}}]
GRID_LINKS = [
    {"a": "g0_{k}", "b": "g0_{k1}", "ranges": {"k": [1, 4]}},
    {"a": "g{k}_0", "b": "g{k1}_0", "ranges": {"k": [1, 4]}},
]
BIG = 2**31 + 28  # the driver's seeds do not fit 32 signed bits


# -- (a) what only `set_metric` has touched is the parent's, byte for byte --

@pytest.mark.parametrize("topology, vantage, groups, on_wire, in_table", [
    (FABRIC, "rsw0_0", FABRIC_LINKS,
     "a6c81e5466dc09dd0de4fe24acb1636f9521698ed5fc2fbb257736bc62208085",
     "a51d2a9ce83700edcb22f3f353e644f783faccd23929270ac3c5b8203204a488"),
    (GRID, "g0_0", GRID_LINKS,
     "75858570de99c707bfbe0c76785f8bff9d0bf5b52c0ba3ef2635cda26dc6d092",
     "f4e61469553cdf2df3d76fca9c715b87d6b349c93c5877f01b6f15b55d031551"),
], ids=["rehearsal_fabric", "grid12"])
def test_metric_flaps_send_the_bytes_and_get_the_tables_of_the_parent(
    topology, vantage, groups, on_wire, in_table
):
    """The digests were taken at commit bc97e35 (PR 27), whose `Lsdb` knew
    metrics only: every key's encoded value and the reference's table after
    40 `link_metric_swap` events. They hold the program's serializer too."""
    params = dict(bench_run.load_json("traffic", "metric_flaps.json"), groups=groups)
    lsdb = Lsdb(build_edges(topology))
    wire = WireEncoder(lsdb)
    ref = reference.Reference(lsdb, vantage)
    events = link_metric_swap.generate(params, BIG)
    for _ in range(40):
        keys = next(events).apply(lsdb)
        ref.refresh(key.split(":", 1)[1] for key in keys)
    sent = wire.key_vals(wire.all_keys())
    assert len(sent) == 2 * len(lsdb.nodes)
    assert hashlib.sha256(
        "".join(f"{k}={sent[k]['value']};" for k in sorted(sent)).encode()
    ).hexdigest() == on_wire
    assert hashlib.sha256(
        repr(sorted((p, sorted(nhs)) for p, nhs in ref.table().items())).encode()
    ).hexdigest() == in_table


# -- the two mutations, and what goes on the wire for them ------------------

def _decoded(wire, key):
    from openr_tpu.utils import serializer

    return serializer.loads(base64.b64decode(wire.key_vals([key])[key]["value"]))


def test_a_down_link_is_in_neither_database_and_a_withdrawn_prefix_is_no_entry():
    lsdb = Lsdb(build_edges({"generator": "grid", "args": {"n": 3}}))
    wire = WireEncoder(lsdb)
    up = wire.key_vals(["adj:g0_0", "prefix:g1_1"])
    assert lsdb.set_link_up("g0_0", "g0_1", False) == ("g0_0", "g0_1")
    assert lsdb.set_link_up("g0_1", "g0_0", False) == ()  # nothing changed
    assert lsdb.up_peers("g0_0") == {"g1_0": 1} and "g0_0" not in lsdb.up_peers("g0_1")
    assert lsdb.metric["g0_0"]["g0_1"] == 1  # the plan keeps the link and its metric
    assert [a.other_node_name for a in _decoded(wire, "adj:g0_0").adjacencies] == ["g1_0"]
    assert sorted(a.other_node_name for a in _decoded(wire, "adj:g0_1").adjacencies) == ["g0_2", "g1_1"]
    assert lsdb.set_announced("g1_1", False) == ("g1_1",)
    assert lsdb.set_announced("g1_1", False) == ()
    gone = _decoded(wire, "prefix:g1_1")
    assert gone.this_node_name == "g1_1" and gone.prefix_entries == []
    # back up and announced: the bytes of before, under a higher version
    assert lsdb.set_link_up("g0_0", "g0_1", True) == ("g0_0", "g0_1")
    assert lsdb.set_announced("g1_1", True) == ("g1_1",)
    again = wire.key_vals(["adj:g0_0", "prefix:g1_1"])
    for key in up:
        assert again[key]["value"] == up[key]["value"]
        assert again[key]["version"] > up[key]["version"]
    with pytest.raises(KeyError):
        lsdb.set_link_up("g0_0", "g2_2", False)
    with pytest.raises(KeyError):
        lsdb.set_announced("nobody", False)


# -- (c) the reference, by hand ----------------------------------------------

def test_reference_with_a_link_down_and_a_prefix_withdrawn_known_by_hand():
    lsdb = Lsdb(build_edges({"generator": "grid", "args": {"n": 3}}))
    ref = reference.Reference(lsdb, "g0_0")

    def hops(node):
        return {(iface, metric) for _, iface, metric in ref.table()[lsdb.prefix_of[node]]}

    def move(nodes):
        ref.refresh(nodes)
        return ref.table()

    whole = ref.table()
    # a far link down: what lay behind it goes round, the rest stays
    table = move(lsdb.set_link_up("g0_1", "g0_2", False))
    assert hops("g0_2") == {("if-g0_0-g0_1", 4), ("if-g0_0-g1_0", 4)}  # via g1_2
    assert compare.table_mismatches(whole, table) == [lsdb.prefix_of["g0_2"]]
    move(lsdb.set_link_up("g0_2", "g0_1", True))
    assert ref.table() == whole
    # the vantage's own link down: that first hop leaves every set
    table = move(lsdb.set_link_up("g0_0", "g0_1", False))
    assert len(table) == 8
    assert all(iface == "if-g0_0-g1_0" for nhs in table.values() for _, iface, _ in nhs)
    assert hops("g0_1") == {("if-g0_0-g1_0", 3)} and hops("g2_2") == {("if-g0_0-g1_0", 4)}
    # both down: the vantage reaches nobody
    assert move(lsdb.set_link_up("g0_0", "g1_0", False)) == {}
    move(lsdb.set_link_up("g0_0", "g0_1", True) + lsdb.set_link_up("g0_0", "g1_0", True))
    assert ref.table() == whole
    # a /24 withdrawn: no route to it, the others untouched; no refresh is
    # needed, the table is the LSDB's as it stands when asked
    lsdb.set_announced("g1_1", False)
    table = ref.table()
    assert lsdb.prefix_of["g1_1"] not in table
    assert compare.table_mismatches(whole, table) == [lsdb.prefix_of["g1_1"]]
    lsdb.set_announced("g1_1", True)
    assert ref.table() == whole


# -- (b) the two kinds ---------------------------------------------------------

def _cell_params(name, **over):
    return dict(bench_run.resolve_cell(name)["params"], **over)


KINDS = {
    # kind -> (module, rehearsal cell, candidates of its parameters, what an
    # event takes out of service, what it gives back)
    "prefix_swap": (
        prefix_swap, "rehearsal_fabric.prefix_churn",
        lambda p: [n for g in p["nodes"] for n in prefix_swap.expand(g)],
        lambda e: e.withdraw, lambda e: e.announce,
    ),
    "link_down_swap": (
        link_down_swap, "rehearsal_fabric.own_link_flaps",
        lambda p: [l for g in p["groups"] for l in link_metric_swap.expand(g)],
        lambda e: e.down, lambda e: e.up,
    ),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_new_kinds_are_deterministic_and_every_seed_sends_the_same_candidates(kind):
    module, cell, candidates_of, taken, given = KINDS[kind]
    # the prefix events alone: the sparse link events have their own test
    params = _cell_params(cell, link_event_every=0)
    candidates = candidates_of(params)
    assert len(candidates) == {"prefix_swap": 37, "link_down_swap": 4}[kind]
    assert len(set(candidates)) == len(candidates) and "rsw0_0" not in candidates

    def take(seed, n=len(candidates)):
        gen = module.generate(params, seed)
        return [(given(e), taken(e)) for e in (next(gen) for _ in range(n))]

    seq = take(BIG, 5 * len(candidates))
    assert seq == take(BIG, len(seq)) and seq != take(BIG + 1, len(seq))
    assert seq[0][0] is None  # nothing is out of service before the first event
    # each event gives back what the one before it took, and takes another
    assert all(b[0] == a[1] and b[1] != a[1] for a, b in zip(seq, seq[1:]))
    # a block is every candidate once: every seed sends the same, reordered
    assert sorted(t for _, t in take(1)) == sorted(t for _, t in take(2)) == sorted(candidates)
    # exactly one candidate is out of service after every event
    lsdb = Lsdb(build_edges(FABRIC))
    gen = module.generate(params, BIG)
    for _ in range(3 * len(candidates)):
        event = next(gen)
        event.apply(lsdb)
        if kind == "prefix_swap":
            assert lsdb.withdrawn == {event.withdraw} and not lsdb.down
        else:
            a, b = event.down
            assert lsdb.down == {(a, b), (b, a)} and not lsdb.withdrawn
            assert len(lsdb.up_peers("rsw0_0")) == 3


def test_prefix_churn_puts_a_link_event_in_every_40th_place_and_warms_three_up():
    params = _cell_params("fabric9976.prefix_churn")
    assert params["kind"] == "prefix_swap" and params["link_event_every"] == 40
    gen = prefix_swap.generate(params, BIG)
    stream = [next(gen) for _ in range(3000)]
    at = [i for i, e in enumerate(stream) if isinstance(e, link_metric_swap.Swap)]
    assert at == list(range(0, 3000, 40))
    assert all(isinstance(e, prefix_swap.PrefixSwap) for i, e in enumerate(stream) if i % 40)
    warm = int(params["warmup_events"])
    assert sum(i < warm for i in at) == 3
    # the window's 3rd and 43rd events: both inside the traced second
    assert [i - warm for i in at if i >= warm][:2] == [2, 42]
    # the link events are the Clos's own metric flaps, none on the vantage's links
    links = {e.raised for e in stream if isinstance(e, link_metric_swap.Swap)}
    metric_flaps = _cell_params("fabric9976.metric_flaps")
    assert params["groups"] == metric_flaps["groups"]
    assert (params["low"], params["high"]) == (metric_flaps["low"], metric_flaps["high"])
    assert not any("rsw0_0" in link for link in links)


def test_fabric9976_prefix_churn_leaves_no_lsdb_state_twice_in_3000_events():
    params = _cell_params("fabric9976.prefix_churn")
    candidates = [n for g in params["nodes"] for n in prefix_swap.expand(g)]
    assert len(candidates) == len(set(candidates)) == 8303
    assert "rsw0_0" not in candidates and {"rsw0_1", "rsw172_47"} <= set(candidates)
    gen = prefix_swap.generate(params, 2**31 + 5)
    states, withdrawn, degraded, written = [], None, None, {}
    for _ in range(3000):
        event = next(gen)
        if isinstance(event, prefix_swap.PrefixSwap):
            withdrawn = event.withdraw
            for node in (event.announce, event.withdraw):
                written[node] = written.get(node, 0) + 1
        else:
            degraded = (event.raised, event.metric)
        states.append((withdrawn, degraded))
    assert len(set(states)) == len(states)
    # KvStore's flood damping holds a key down after 8 updates within seconds
    del written[None]
    assert max(written.values()) == 2


def _cell_case(kind, config_name, cell_name):
    """A cell of BENCHMARK.json as a case of the test below, by its files."""
    config = bench_run.load_json("configs", f"{config_name}.json")
    groups = bench_run.load_json("cells", f"{cell_name}.json")["groups"]
    return kind, config["topology"], config["vantage"], {"groups": groups}


@pytest.mark.parametrize("kind, topology, vantage, over", [
    ("prefix_swap", FABRIC, "rsw0_0",
     {"nodes": [{"node": "rsw{p}_{r}", "ranges": {"p": [1, 2], "r": [0, 5]}}]}),
    ("prefix_swap", GRID, "g0_0",
     {"nodes": [{"node": "g{r}_{c}", "ranges": {"r": [1, 11], "c": [0, 11]}}]}),
    ("link_down_swap", FABRIC, "rsw0_0",
     {"groups": [{"a": "rsw0_0", "b": "fsw0_{f}", "ranges": {"f": [0, 3]}}]}),
    ("link_down_swap", GRID, "g0_0",
     {"groups": [{"a": "g0_0", "b": "g0_1"}, {"a": "g0_0", "b": "g1_0"}]}),
    # the cell itself, on the real Clos: its configuration's topology and
    # vantage, its own file's candidates
    _cell_case("link_down_swap", "fabric9976", "fabric9976.own_link_flaps"),
], ids=["prefix_swap-fabric", "prefix_swap-grid", "link_down_swap-own-fabric", "link_down_swap-own-grid",
        "fabric9976.own_link_flaps"])
def test_every_event_of_the_new_mixes_changes_routes_at_the_vantage(kind, topology, vantage, over):
    """In small, what the cells send: the reference says that a prefix event
    takes exactly one route away and brings exactly one, by exactly those
    two nodes' prefix keys, and that an event on the vantage's own links
    after a stream's first changes two routes or more and cuts nobody off."""
    module = KINDS[kind][0]
    params = dict(over, link_event_every=0)
    lsdb = Lsdb(build_edges(topology))
    ref = reference.Reference(lsdb, vantage)
    before = ref.table()
    gen = module.generate(params, 77)
    for i in range(40):
        event = next(gen)
        keys = event.apply(lsdb)
        assert len(set(keys)) == len(keys)
        ref.refresh(key.split(":", 1)[1] for key in keys)
        after = ref.table()
        changed = compare.table_mismatches(before, after)
        if kind == "prefix_swap":
            gone = [p for p in changed if p not in after]
            new = [p for p in changed if p not in before]
            assert gone == [lsdb.prefix_of[event.withdraw]]
            if i:
                assert new == [lsdb.prefix_of[event.announce]] and len(changed) == 2
                assert keys == [f"prefix:{event.announce}", f"prefix:{event.withdraw}"]
        else:
            assert all(key.startswith("adj:") for key in keys) and 2 <= len(keys) <= 3
            assert len(changed) >= (2 if i else 1), event
            assert len(after) == len(before)  # nobody is cut off
            # one of the vantage's links is down, the others are up
            assert len(lsdb.up_peers(vantage)) == len(lsdb.metric[vantage]) - 1
        before = after


# -- (d) one whole run of each on the CPU ---------------------------------------

@pytest.mark.parametrize("cell", [
    "rehearsal_fabric.prefix_churn", "rehearsal_fabric.own_link_flaps",
    # a vantage of 70 neighbours, 71 solve rows: its route toward the other
    # spine has 70 first hops, more than a machine word has bits
    "rehearsal_fabric_ssw.metric_flaps",
])
def test_whole_cpu_run_of_the_new_mixes_is_correct(cell, capsys):
    """Each needs only its `cells/` file: `resolve_cell` takes a
    configuration.traffic pair that no `workloads` entry names. One second:
    the toy's prefix keys are few, and KvStore's flood damping holds one
    down once it has taken 8 updates within seconds."""
    rc = bench_run.main(
        ["--workload", cell, "--seed", str(BIG + 1), "--seconds", "1",
         "--allow-cpu", "--trace", "0"]
    )
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 10
    assert all(v["value"] == 0 for v in line["compared"].values()), line["compared"]
    if cell.endswith("own_link_flaps"):
        # the vantage's own link: a cold solve and a full route build, every time
        assert "full route build" in err
    else:
        assert "full route build" not in err


def _ecmp_sets_lose_a_member(monkeypatch):
    import openr_tpu.fib.fib as fib

    real = fib.get_best_nexthops_unicast
    monkeypatch.setattr(fib, "get_best_nexthops_unicast", lambda nhs: real(nhs)[:1])


def _withdrawals_are_lost(monkeypatch):
    import openr_tpu.solver.delta as delta

    real = delta.DeltaRouteBuilder._build_delta

    def build(self, me, link_states, prefix_state, prev_db, *rest):
        out = real(self, me, link_states, prefix_state, prev_db, *rest)
        if out is not None:
            new_db, update = out
            for prefix in update.unicast_routes_to_delete:
                new_db.unicast_entries[prefix] = prev_db.unicast_entries[prefix]
            del update.unicast_routes_to_delete[:]
        return out

    monkeypatch.setattr(delta.DeltaRouteBuilder, "_build_delta", build)


def _full_builds_answer_one_event_late(monkeypatch):
    import openr_tpu.solver.delta as delta

    real = delta.DeltaRouteBuilder._build_full
    held = {}

    def build(self, me, link_states, prefix_state, prev_db, policy_fn):
        fresh, _, _ = real(self, me, link_states, prefix_state, prev_db, policy_fn)
        held["db"], late = fresh, held.get("db", fresh)
        return late, delta.get_route_delta(late, prev_db), False

    monkeypatch.setattr(delta.DeltaRouteBuilder, "_build_full", build)


@pytest.mark.parametrize("fault, number, cell", [
    (_ecmp_sets_lose_a_member, "table_mismatches", "rehearsal_fabric.prefix_churn"),
    (_withdrawals_are_lost, "event_mismatches", "rehearsal_fabric.prefix_churn"),
    (_ecmp_sets_lose_a_member, "table_mismatches", "rehearsal_fabric.own_link_flaps"),
    (_full_builds_answer_one_event_late, "event_mismatches", "rehearsal_fabric.own_link_flaps"),
], ids=["ecmp_cut_in_fib", "withdrawal_lost_in_delta_build", "own_link-ecmp_cut_in_fib",
        "own_link-stale_full_build"])
def test_a_prefix_event_answered_wrongly_is_not_correct(fault, number, cell, capsys, monkeypatch):
    """The timed path broken underneath the harness, on the prefix mix: an
    announced /24 programmed over one first hop of four, and a withdrawn /24
    that Decision never deletes; on the vantage's own links: the same cut,
    and a full route build that answers with the table of the event before."""
    fault(monkeypatch)
    rc = bench_run.main(
        ["--workload", cell, "--seed", str(BIG + 2),
         "--seconds", "1", "--allow-cpu", "--trace", "0"]
    )
    out, _ = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is False
    assert line["compared"][number]["value"] > 0, line["compared"]


def test_the_trace_directory_is_this_process_own():
    # two test workers that trace at once must not delete each other's trace
    assert os.path.basename(bench_run.TRACE_DIR) == f".chipbench_trace.{os.getpid()}"
    assert os.path.dirname(bench_run.TRACE_DIR) == bench_run.ROOT
