"""The plain reference: what the vantage's routing table has to hold.

Shortest paths with equal-cost multipath (upstream's SP_ECMP): for every
other node's prefix, the set of the vantage's adjacencies that lie on some
shortest path to that node, each with the path's metric. Distances come
from scipy's Dijkstra, run from the vantage and from each of its
neighbours: neighbour `u` is a first hop toward `d` exactly where
metric(vantage, u) + dist_u(d) = dist_vantage(d). The sets are the columns
of a boolean [neighbours, nodes] matrix, so a vantage may have any number
of neighbours (a spine switch of the Clos has 173). A link that is down is
no edge, either way, and no first hop; a node that does not announce its
/24 has no route. It imports nothing of the program and reads only
`chipbench.lsdb.Lsdb`.

A table is `{prefix: frozenset((address, interface, metric), ...)}`.
`tables()` gives what `compare.py` reads, the form of every reference that
a configuration may name (its `"reference"`, this module by default): the
unicast table with each next hop's push stack, `()` here, since SP_ECMP
over IP pushes no label, and the MPLS table, empty, since segment routing
is off.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, Iterable, List, Optional, Tuple

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra

from chipbench.lsdb import Lsdb, if_name, nexthop_v4

NextHops = FrozenSet[Tuple[str, str, int]]
Table = Dict[str, NextHops]
# compare.py's forms: a unicast next hop (address, interface, metric,
# push stack); a label route's (address, interface, action, labels)
MplsTable = Dict[int, FrozenSet[tuple]]
Tables = Tuple[Table, MplsTable]


class Reference:
    """The vantage's table on `lsdb` as it stands; `refresh` after the
    LSDB moved."""

    def __init__(
        self, lsdb: Lsdb, vantage: str, config: Optional[dict] = None
    ) -> None:
        # `config` is every reference's third argument; this one reads none
        self.lsdb, self.vantage = lsdb, vantage
        self.number = {node: i for i, node in enumerate(lsdb.nodes)}
        # CSR by hand, a row per node in the order of `lsdb.nodes`: `slot`
        # is where each directed link's weight sits in `graph.data`
        self.slot: Dict[Tuple[str, str], int] = {}
        indices, indptr = [], [0]
        for node in lsdb.nodes:
            for peer in lsdb.metric[node]:
                self.slot[node, peer] = len(indices)
                indices.append(self.number[peer])
            indptr.append(len(indices))
        n = len(lsdb.nodes)
        self.graph = csr_matrix(
            (np.ones(len(indices)), np.array(indices), np.array(indptr)),
            shape=(n, n),
        )
        self.refresh(lsdb.nodes)
        self.hop_of = {
            peer: (nexthop_v4(vantage, peer), if_name(vantage, peer))
            for peer in lsdb.metric[vantage]
        }
        self.prefixes = [lsdb.prefix_of[node] for node in lsdb.nodes]

    def refresh(self, nodes: Iterable[str]) -> None:
        """Re-reads the metric and the up-state of the links out of
        `nodes`: a down link weighs infinity, which Dijkstra never takes."""
        down = self.lsdb.down
        for node in nodes:
            for peer, metric in self.lsdb.metric[node].items():
                self.graph.data[self.slot[node, peer]] = (
                    np.inf if (node, peer) in down else metric
                )

    def table(self) -> Table:
        """The prefix of every node that is reachable and announces it, on
        the LSDB as it stands -> its ECMP next-hop set."""
        return self._table(())

    def tables(self) -> Tables:
        """`table()` in compare.py's form, and no label route."""
        return self._table(((),)), {}

    def first_hops(self) -> Tuple[List[str], np.ndarray, np.ndarray]:
        """(the vantage's up neighbours, its distance to every node by
        number, member): member[i, d] says that neighbour i is a first hop
        toward node d, on some shortest path."""
        neighbours = self.lsdb.up_peers(self.vantage)
        sources = [self.number[self.vantage]] + [self.number[peer] for peer in neighbours]
        dist = dijkstra(self.graph, directed=True, indices=sources)
        through = np.fromiter(neighbours.values(), float, len(neighbours))[:, None] + dist[1:]
        return list(neighbours), dist[0], through == dist[0]

    def _table(self, tail: tuple) -> Table:
        """`table()`, each next hop's tuple followed by `tail`."""
        me = self.number[self.vantage]
        neighbours, dist, member = self.first_hops()
        hops = [self.hop_of[peer] for peer in neighbours]
        silent = {self.number[node] for node in self.lsdb.withdrawn}
        # a destination's set is its column of `member`, of any height;
        # equal columns share one frozenset, keyed by the column's packed
        # bytes and the distance
        columns = np.ascontiguousarray(np.packbits(member, axis=0).T)
        sets: Dict[Tuple[bytes, int], NextHops] = {}
        table: Table = {}
        for node in np.flatnonzero(np.isfinite(dist)).tolist():
            if node == me or node in silent:
                continue
            key = (columns[node].tobytes(), int(dist[node]))
            if key not in sets:
                sets[key] = frozenset(
                    (*hops[i], key[1], *tail)
                    for i in np.flatnonzero(member[:, node])
                )
                if not sets[key]:
                    raise ValueError(
                        f"{self.lsdb.nodes[node]} is reachable from "
                        f"{self.vantage} over no first hop"
                    )
            table[self.prefixes[node]] = sets[key]
        return table


def route_table(lsdb: Lsdb, vantage: str) -> Table:
    return Reference(lsdb, vantage).table()
