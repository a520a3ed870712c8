"""ISSUE 33: a vantage with more than 128 up-neighbours, so that the solve's
source batch and the next-hop extraction's link arrays both cross the
256 bucket (`s_pad` 256, `l_pad` 256): a hub in the fabric's shape, one
plane of two spines over 140 pods of two fsw and two rsw, solved from
`ssw0_0` (140 adjacencies, 141 rows), every link's metric drawn from a
seed in 1-5 so that first-hop sets of many widths arise. The TPU backend's
full build and a run of warm solves with DeltaPath give, route for route,
the next-hop sets and metrics of the benchmark's plain reference
(`chipbench.reference`, scipy's Dijkstra on plain data) and of the CPU
solver. Integers throughout, so every comparison is exact. A CPU run at a
small size: nothing here is a device number."""

import base64
import collections
import random

import pytest

from chipbench import reference
from chipbench.lsdb import Lsdb, WireEncoder
from chipbench.topologies import build_edges
from openr_tpu.lsdb import LinkState
from openr_tpu.solver import DeltaRouteBuilder, SpfSolver, TpuSpfSolver
from openr_tpu.utils import serializer
from test_route_delta import assert_route_db_equal, make_prefix_state

ME = "ssw0_0"
PODS = 140
HUB = {
    "generator": "fabric",
    "args": {"pods": PODS, "ssw_per_plane": 2, "fsw_per_pod": 2, "rsw_per_pod": 2},
}
SEEDS = [2**31 + 33, 3300033141]  # the driver's seeds do not fit 32 signed bits


class Hub:
    """One LSDB twice: plain, for the reference, and as the program's
    LinkState; `set_metric` moves both."""

    def __init__(self, seed):
        rng = random.Random(seed)
        self.lsdb = Lsdb(
            [(a, b, rng.randint(1, 5)) for a, b, _ in build_edges(HUB)]
        )
        self.wire = WireEncoder(self.lsdb)
        self.link_state = LinkState("0")
        for node in self.lsdb.nodes:
            self._publish(node)
        self.als = {"0": self.link_state}
        self.ps = make_prefix_state(
            {n: [p] for n, p in self.lsdb.prefix_of.items()}
        )
        self.reference = reference.Reference(self.lsdb, ME)

    def _publish(self, node):
        # the adjacency database as the benchmark's encoder sends it, so
        # that the reference's table and the program's routes name the
        # same interfaces and addresses
        key = f"adj:{node}"
        value = self.wire.key_vals([key])[key]["value"]
        self.link_state.update_adjacency_database(
            serializer.loads(base64.b64decode(value))
        )

    def set_metric(self, a, b, metric):
        changed = self.lsdb.set_metric(a, b, metric)
        for node in changed:
            self._publish(node)
        self.reference.refresh(changed)


def as_table(db):
    """A route db's unicast entries in the reference's plain form."""
    return {
        str(prefix): frozenset(
            (nh.address, nh.iface, nh.metric) for nh in entry.nexthops
        )
        for prefix, entry in db.unicast_entries.items()
    }


def assert_equals_reference_and_oracle(db, hub):
    want = hub.reference.table()
    got = as_table(db)
    assert set(got) == set(want)
    wrong = [p for p in want if got[p] != want[p]]
    assert not wrong, (len(wrong), wrong[:3])
    assert_route_db_equal(
        SpfSolver(ME).build_route_db(ME, hub.als, hub.ps), db
    )


def other_links(lsdb):
    """Every link that is not the vantage's own, in a fixed order."""
    return sorted(
        (a, b) for a in lsdb.metric for b in lsdb.metric[a]
        if a < b and ME not in (a, b)
    )


def set_moves_and_distance_stays(hub):
    """(a, b, metric, width): raising link a<->b to `metric` takes members
    out of a's first-hop set of `width` and leaves a's distance as it was.
    Found by trying, on the plain LSDB alone: the destinations by the width
    of their sets, and each one's links in turn."""
    table = hub.reference.table()
    node_of = {p: n for n, p in hub.lsdb.prefix_of.items()}
    by_width = sorted(table.items(), key=lambda kv: (-len(kv[1]), kv[0]))
    for prefix, nhs in by_width[:20]:
        dest = node_of[prefix]
        for peer, metric in sorted(hub.lsdb.metric[dest].items()):
            if ME in (dest, peer):
                continue
            hub.reference.refresh(hub.lsdb.set_metric(dest, peer, metric + 1))
            after = hub.reference.table()[prefix]
            hub.reference.refresh(hub.lsdb.set_metric(dest, peer, metric))
            same_distance = {m for _, _, m in after} == {m for _, _, m in nhs}
            if same_distance and 0 < len(after) < len(nhs):
                return dest, peer, metric + 1, len(nhs)
    raise AssertionError("no link moves a set and leaves its distance")


@pytest.mark.parametrize("seed", SEEDS)
def test_full_build_and_warm_solves_from_a_vantage_of_140_neighbours(seed):
    hub = Hub(seed)
    assert len(hub.lsdb.metric[ME]) == PODS
    solver = TpuSpfSolver(ME)
    builder = DeltaRouteBuilder(solver)
    db, _, used = builder.build(ME, hub.als, hub.ps, None, force_full=True)
    assert not used
    assert_equals_reference_and_oracle(db, hub)
    # both 256 buckets are crossed: 141 solve rows, 140 first-hop links
    assert solver.counters["decision.spf.rows_last"] == 1 + PODS == 141
    assert solver.counters["decision.spf.rows_padded_last"] == 256
    solve = solver._solves[("0", ME)][1]
    assert solve.nh_mask()[1].shape[0] >= PODS
    widths = collections.Counter(
        len(nhs) for nhs in hub.reference.table().values()
    )
    assert len(widths) >= 4 and max(widths) >= 4, widths

    rng = random.Random(seed + 1)
    links = other_links(hub.lsdb)
    raised = None
    moved = 0
    for k in range(12):
        if k == 6:
            # a member leaves a wide set and the distance stays as it was
            before = hub.reference.table()
            dest, peer, metric, width = set_moves_and_distance_stays(hub)
            hub.set_metric(dest, peer, metric)
            after = hub.reference.table()
            prefix = hub.lsdb.prefix_of[dest]
            assert 0 < len(after[prefix]) < width
            assert {m for _, _, m in after[prefix]} == {
                m for _, _, m in before[prefix]
            }
        elif raised is not None and k % 2:
            a, b, metric = raised  # the restore of the raise before it
            hub.set_metric(a, b, metric)
            raised = None
        else:
            a, b = links[rng.randrange(len(links))]
            raised = (a, b, hub.lsdb.metric[a][b])
            hub.set_metric(a, b, rng.randint(6, 18))
        db, update, used = builder.build(ME, hub.als, hub.ps, db)
        assert used, k  # a metric change off the vantage's links stays warm
        moved += len(update.unicast_routes_to_update)
        assert_equals_reference_and_oracle(db, hub)
    assert moved > 0
    assert solver.counters["decision.spf.incremental_solves"] == 12
    assert solver.counters["decision.spf.rows_last"] == 141
    assert solver.counters["decision.spf.rows_padded_last"] == 256
    for name in (
        "decision.spf.fallback_solves", "decision.route_build_generic_routes"
    ):
        assert solver.counters.get(name, 0) == 0, name
