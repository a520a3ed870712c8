"""RIB value types and route-db diffing.

Equivalents of openr/decision/RibEntry.h (RibUnicastEntry:37, RibMplsEntry:93),
openr/decision/RouteUpdate.h (DecisionRouteUpdate) and the getRouteDelta diff
in openr/decision/Decision.cpp:47-85.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, FrozenSet, List, Optional, Set, Tuple, Union

from openr_tpu.types import (
    IpPrefix,
    MplsAction,
    MplsActionCode,
    MplsRoute,
    NextHop,
    PrefixEntry,
    UnicastRoute,
)

_PHP = MplsAction(MplsActionCode.PHP)


@dataclass
class RibUnicastEntry:
    """A computed unicast route: prefix + ECMP nexthop set + best-path info."""

    prefix: IpPrefix
    nexthops: Set[NextHop] = field(default_factory=set)
    best_prefix_entry: Optional[PrefixEntry] = None
    best_area: Optional[str] = None
    do_not_install: bool = False
    best_nexthop: Optional[NextHop] = None  # for BGP route re-advertising

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, RibUnicastEntry)
            and self.prefix == other.prefix
            and self.nexthops == other.nexthops
            and self.best_prefix_entry == other.best_prefix_entry
            and self.best_nexthop == other.best_nexthop
            and self.do_not_install == other.do_not_install
        )

    def to_unicast_route(self) -> UnicastRoute:
        return UnicastRoute(self.prefix, tuple(sorted(
            self.nexthops, key=lambda nh: (nh.address, nh.iface or "")
        )))


@dataclass(eq=False, slots=True)
class LabelNextHops:
    """What determines the next hops of a node-label route whose first hops
    came from the TPU solver's next-hop table: the first-hop group's links
    as (neighbour, v4 address, v6 address, interface, area) — the tuple the
    table shares between every destination behind the group —, the
    distance, the address family, the label every SWAP carries, and those
    of the links' neighbours that are the destination themselves (PHP over
    their own link). A route holds this in place of its `NextHop`s until somebody
    reads them: with segment routing off nothing in an event does, and a
    Clos rack's full build makes 69,800 of them less. `made` is its
    solver's tally of the sets that were made after all."""

    links: Tuple[Tuple[str, str, str, str, str], ...]
    metric: int
    is_v4: bool
    swap_label: int
    php_neighbors: FrozenSet[str]
    made: List[int]

    def __eq__(self, other) -> bool:
        """Whether `make()` of both gives equal sets, with no `NextHop`
        made. By value: a cold solve builds the table's links anew."""
        return (
            isinstance(other, LabelNextHops)
            and self.metric == other.metric
            and self.swap_label == other.swap_label
            and self.is_v4 == other.is_v4
            and self.php_neighbors == other.php_neighbors
            and self.links == other.links
        )

    def make(self) -> Set[NextHop]:
        self.made[0] += 1
        swap = MplsAction(MplsActionCode.SWAP, swap_label=self.swap_label)
        return {
            NextHop(
                v4 if self.is_v4 else v6,
                iface,
                self.metric,
                _PHP if neighbor in self.php_neighbors else swap,
                False,
                area,
                0,
                neighbor,
            )
            for neighbor, v4, v6, iface, area in self.links
        }


class RibMplsEntry:
    """A computed MPLS label route: top label + nexthop set. Built with a
    `LabelNextHops` it makes the set on the first read of `nexthops` and
    keeps it; the set is then the form's, and a reader that wants another
    assigns `nexthops`."""

    __slots__ = ("label", "_nexthops", "_deferred")

    def __init__(
        self,
        label: int,
        nexthops: Union[Set[NextHop], LabelNextHops, None] = None,
    ) -> None:
        self.label = label
        if isinstance(nexthops, LabelNextHops):
            self._nexthops, self._deferred = None, nexthops
        else:
            self.nexthops = set() if nexthops is None else nexthops

    @property
    def nexthops(self) -> Set[NextHop]:
        nexthops = self._nexthops
        if nexthops is None:
            nexthops = self._nexthops = self._deferred.make()
        return nexthops

    @nexthops.setter
    def nexthops(self, nexthops: Set[NextHop]) -> None:
        self._nexthops, self._deferred = nexthops, None

    def __eq__(self, other) -> bool:
        """Label and next-hop set equal. Two entries that hold what
        determines their sets are compared on that, whether or not a read
        has made either's since: a full build's diff against a table that
        ctrl once read makes no set."""
        if not isinstance(other, RibMplsEntry) or self.label != other.label:
            return False
        mine, theirs = self._deferred, other._deferred
        if mine is not None and theirs is not None:
            return mine == theirs
        return self.nexthops == other.nexthops

    def __repr__(self) -> str:
        made = "not made" if self._nexthops is None else self._nexthops
        return f"RibMplsEntry(label={self.label!r}, nexthops={made!r})"

    def to_mpls_route(self) -> MplsRoute:
        return MplsRoute(self.label, tuple(sorted(
            self.nexthops, key=lambda nh: (nh.address, nh.iface or "")
        )))


@dataclass
class DecisionRouteDb:
    """Full computed RIB from one node's perspective."""

    unicast_entries: Dict[IpPrefix, RibUnicastEntry] = field(
        default_factory=dict
    )
    mpls_entries: Dict[int, RibMplsEntry] = field(default_factory=dict)


@dataclass
class DecisionRouteUpdate:
    """Incremental route delta published to Fib (RouteUpdate.h)."""

    unicast_routes_to_update: List[RibUnicastEntry] = field(
        default_factory=list
    )
    unicast_routes_to_delete: List[IpPrefix] = field(default_factory=list)
    mpls_routes_to_update: List[RibMplsEntry] = field(default_factory=list)
    mpls_routes_to_delete: List[int] = field(default_factory=list)
    perf_events: Optional[object] = None
    # monotonic stage trace riding the delta to Fib (monitor.spans.Span);
    # host-local only — never serialized, never compared
    span: Optional[object] = None

    def empty(self) -> bool:
        return not (
            self.unicast_routes_to_update
            or self.unicast_routes_to_delete
            or self.mpls_routes_to_update
            or self.mpls_routes_to_delete
        )


def apply_route_delta(
    old_db: DecisionRouteDb, delta: DecisionRouteUpdate
) -> DecisionRouteDb:
    """The diff's inverse: fold an update into a route db, returning a new
    db that shares unchanged entry objects with the old one. The DeltaPath
    route build uses this to keep Decision's full RouteDatabase current
    without rebuilding it — apply_route_delta(old, get_route_delta(new,
    old)) == new for any pair of dbs."""
    unicast = dict(old_db.unicast_entries)
    mpls = dict(old_db.mpls_entries)
    for prefix in delta.unicast_routes_to_delete:
        unicast.pop(prefix, None)
    for entry in delta.unicast_routes_to_update:
        unicast[entry.prefix] = entry
    for label in delta.mpls_routes_to_delete:
        mpls.pop(label, None)
    for entry in delta.mpls_routes_to_update:
        mpls[entry.label] = entry
    return DecisionRouteDb(unicast_entries=unicast, mpls_entries=mpls)


def get_route_delta(
    new_db: DecisionRouteDb, old_db: DecisionRouteDb
) -> DecisionRouteUpdate:
    """Diff two route dbs (Decision.cpp:47-85)."""
    delta = DecisionRouteUpdate()
    for prefix, entry in new_db.unicast_entries.items():
        old = old_db.unicast_entries.get(prefix)
        if old is not None and old == entry:
            continue
        delta.unicast_routes_to_update.append(entry)
    for prefix in old_db.unicast_entries:
        if prefix not in new_db.unicast_entries:
            delta.unicast_routes_to_delete.append(prefix)
    for label, entry in new_db.mpls_entries.items():
        old = old_db.mpls_entries.get(label)
        if old is not None and old == entry:
            continue
        delta.mpls_routes_to_update.append(entry)
    for label in old_db.mpls_entries:
        if label not in new_db.mpls_entries:
            delta.mpls_routes_to_delete.append(label)
    return delta
