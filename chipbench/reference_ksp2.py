"""The plain reference of a configuration whose prefixes are forwarded over
segment routing (SR-MPLS) with upstream's KSP2_ED_ECMP, or SP_ECMP, and
whose daemon programs label routes: what the platform agent has to hold.

It follows upstream's published logic as the program's docstrings cite it,
written again from the LSDB as plain data (it imports nothing of the
program; the graph, its refresh and the ECMP first hops are those of
`chipbench.reference`, which reads only `chipbench.lsdb`):

  shortest paths   Dijkstra from the vantage, each node with its path links
                   (LinkState.cpp getSpfResult). Distances come from scipy;
                   the path links of a node are the links from every peer `p`
                   with dist(p) + metric(p, node) = dist(node).
  path tracing     traceOnePath's greedy edge-disjoint back-trace
                   (LinkState.cpp:398-419): from the destination back to the
                   vantage over path links not yet visited, every link tried
                   marked visited, repeated until it finds no path.
  second paths     the second path set is the same solve and trace with
                   every link of the first set ignored (LinkState.cpp:760-789).
  route selection  (Decision.cpp:909-1066) a prefix's next hops are the first
                   links of both sets; a second path that contains a first
                   path is dropped; a next hop's metric is its path's cost;
                   its push stack holds the node labels along the path from
                   the bottom up, the destination's first, the first hop's
                   own label dropped (PHP), where the path has more than one
                   link; a path of one link pushes nothing.
  label routes     (Decision.cpp:415-501) POP_AND_LOOKUP for the vantage's own
                   label; for every other reachable node its ECMP first
                   hops, PHP toward the node itself and SWAP to its label
                   otherwise, over the neighbour's v6 address; of those the
                   agent holds what Fib programs (upstream's Fib,
                   getBestNextHopsMpls, Util.cpp:497-535): at the least cost,
                   the PHP next hop alone where there is one.

Tie order: upstream keeps a node's path links in the order in which its
Dijkstra relaxed them, which its containers decide. Here that order is
stated: a node's path links in the order of their far ends by (distance
from the vantage, node name). That is the order in which a Dijkstra that
takes nodes out of its queue by (distance, name) relaxes them, and the one
the CPU oracle (`openr_tpu/lsdb/link_state.py` run_spf) follows;
`tests/chipbench/test_label_routes.py` holds the two equal on grids, a ring
with chords and a Clos, under metric changes and links down.

Departures from upstream, none of which a configuration here can reach: one
area; one announcer per prefix, the node itself, with no prepend label and
no minimum of next hops; no BGP, no drained node, no adjacency label (the
encoder writes none); IPv4 prefixes only. Every link metric is at least 1.

Cost of one `tables()` on one core of an Intel Xeon host (a host time, not a
device number), KSP2 from the grid's corner `g0_0`: 0.22 s on a 32 x 32
grid (n = 1,024), 2.0 s on a 64 x 64 grid (n = 4,096), 9.1 s on the
100 x 100 grid of `grid10000`. The second path sets cost a Dijkstra and a
trace per destination, so the cost grows as n times the graph; a cell's
`verify_events` has to be sized from it (two tables a verified event).

A table is compare.py's form: `{prefix: frozenset((address, interface,
metric, push stack))}` and `{label: frozenset((address, interface, action,
labels))}`.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Set, Tuple

import numpy as np
from scipy.sparse.csgraph import dijkstra

from chipbench import reference
from chipbench.lsdb import PREFIX_FORWARDING, Lsdb, if_name, nexthop_v4, nexthop_v6
from chipbench.reference import MplsTable, Table, Tables

Link = Tuple[str, str]  # (a, b) with a < b: one link, either direction


def _link(a: str, b: str) -> Link:
    return (a, b) if a < b else (b, a)


def _links(path: List[str]) -> List[Link]:
    """A path's links, from the vantage (path[0]) outward."""
    return [_link(a, b) for a, b in zip(path, path[1:])]


def _contains(inner: List[Link], outer: List[Link]) -> bool:
    """`inner` lies contiguously inside `outer` (LinkState.h:395)."""
    n = len(inner)
    return any(outer[i:i + n] == inner for i in range(len(outer) - n + 1))


class Reference(reference.Reference):
    """The vantage's unicast and label tables on `lsdb` as it stands;
    `refresh` after the LSDB moved."""

    def __init__(self, lsdb: Lsdb, vantage: str, config: Optional[dict] = None) -> None:
        forwarding = dict(PREFIX_FORWARDING, **(config or {}).get("prefix_forwarding", {}))
        if forwarding["type"] != "SR_MPLS":
            raise ValueError(
                f"reference_ksp2 answers for SR_MPLS prefixes, not {forwarding['type']}"
            )
        self.k = {"SP_ECMP": 1, "KSP2_ED_ECMP": 2}[forwarding["algorithm"]]
        super().__init__(lsdb, vantage, config)

    # -- shortest paths and their trace ------------------------------------

    def _dist(self, ignored: Set[Link]) -> List[float]:
        """Distances from the vantage with the links of `ignored` left out,
        by node number; infinity where a node is out of reach."""
        data = self.graph.data
        saved = []
        for a, b in ignored:
            for slot in (self.slot[a, b], self.slot[b, a]):
                saved.append((slot, data[slot]))
                data[slot] = np.inf
        try:
            row = dijkstra(self.graph, directed=True, indices=self.number[self.vantage])
        finally:
            for slot, weight in saved:
                data[slot] = weight
        return row.tolist()

    def _path_links(
        self, node: str, dist: List[float], ignored: Set[Link],
        cache: Dict[str, List[str]],
    ) -> List[str]:
        """The far ends of `node`'s path links, in the stated tie order."""
        found = cache.get(node)
        if found is None:
            number = self.number
            here = dist[number[node]]
            found = sorted(
                (p for p, m in self.lsdb.up_peers(node).items()
                 if dist[number[p]] + m == here and _link(p, node) not in ignored),
                key=lambda p: (dist[number[p]], p),
            )
            cache[node] = found
        return found

    def _trace_one(self, node, dist, ignored, cache, visited) -> Optional[List[str]]:
        """traceOnePath: a path from the vantage to `node` over path links
        not yet visited, as its nodes; None where there is none."""
        if node == self.vantage:
            return [node]
        for prev in self._path_links(node, dist, ignored, cache):
            link = _link(prev, node)
            if link not in visited:
                visited.add(link)
                sub = self._trace_one(prev, dist, ignored, cache, visited)
                if sub is not None:
                    sub.append(node)
                    return sub
        return None

    def _path_set(self, dest, dist, ignored, cache) -> List[List[str]]:
        """One set of edge-disjoint shortest paths to `dest`."""
        paths: List[List[str]] = []
        if dist[self.number[dest]] == np.inf:
            return paths
        visited: Set[Link] = set()
        while True:
            path = self._trace_one(dest, dist, ignored, cache, visited)
            if path is None:
                return paths
            paths.append(path)

    # -- the tables --------------------------------------------------------

    def _unicast_hop(self, path: List[str]) -> tuple:
        first = path[1]
        cost = sum(self.lsdb.metric[a][b] for a, b in zip(path, path[1:]))
        push = tuple(self.lsdb.label_of[node] for node in reversed(path[2:]))
        return (nexthop_v4(self.vantage, first), if_name(self.vantage, first), cost, push)

    def table(self) -> Table:
        """The prefix of every node that is reachable and announces it ->
        the next hops of its first (and second) edge-disjoint path set."""
        dist = self._dist(set())
        cache: Dict[str, List[str]] = {}
        table: Table = {}
        for dest, d in zip(self.lsdb.nodes, dist):
            if d == np.inf or dest == self.vantage or dest in self.lsdb.withdrawn:
                continue
            first = self._path_set(dest, dist, set(), cache)
            paths = list(first)
            if self.k == 2:
                ignored = {link for path in first for link in _links(path)}
                firsts = [_links(path) for path in first]
                second = self._path_set(dest, self._dist(ignored), ignored, {})
                paths += [
                    path for path in second
                    if not any(_contains(f, _links(path)) for f in firsts)
                ]
            table[self.lsdb.prefix_of[dest]] = frozenset(map(self._unicast_hop, paths))
        return table

    def mpls_table(self) -> MplsTable:
        """Every node label the vantage reaches -> the label route's next
        hops as the agent holds them."""
        me = self.vantage
        table: MplsTable = {
            self.lsdb.label_of[me]: frozenset({("::", None, "POP_AND_LOOKUP", ())})
        }
        names, dist, member = self.first_hops()
        for x in np.flatnonzero(np.isfinite(dist)).tolist():
            node = self.lsdb.nodes[x]
            if node == me:
                continue
            label = self.lsdb.label_of[node]
            hops = [names[i] for i in np.flatnonzero(member[:, x])]
            if node in hops:  # PHP beats SWAP at the same cost
                hops = [node]
            table[label] = frozenset(
                (nexthop_v6(u), if_name(me, u), "PHP", ()) if u == node
                else (nexthop_v6(u), if_name(me, u), "SWAP", (label,))
                for u in hops
            )
        return table

    def tables(self) -> Tables:
        return self.table(), self.mpls_table()

