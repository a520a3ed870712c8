"""DeltaPath route build: O(changes) per event instead of O(table).

The warm solver already repairs only the distance entries an LSDB event
touched (ops/spf.py:_sell_solver_warm) and, since the device-side delta
extraction landed, reports exactly WHICH destination columns moved
(`_AreaSolve.take_route_delta`). This module closes the remaining host-side
gap: instead of rebuilding the whole RouteDatabase and diffing it against
the previous one (`get_route_delta`, O(prefixes) per event even for a
single link flap), `DeltaRouteBuilder` recomputes only the prefixes and
node-label routes the device delta names and emits the
`DecisionRouteUpdate` directly — the DeltaPath end-to-end difference
propagation (PAPERS.md, arxiv 1808.06893) on the host side.

Soundness: a route entry from `my_node_name`'s perspective is a function of
(a) my own distance row and the first-hop mask at the columns of its
announcers / label targets (under LFA, every changed column: the
alternates read the neighbours' rows), (b) my own out-link attributes (the
nexthop triangle's weight column, link up/down, addresses), (c) the
transit/overload mask, (d) node labels, and (e) the prefix advertisements
themselves. The device delta covers (a) exactly: the mirror patch compares
my row and the mask, after the overload rule, before and after each solve
and names the columns where either moved, or every changed column where
the old values are not in hand (`_AreaSolve._finish_delta`); the
solver refuses to produce a delta for events touching (b) or (c)
(`_AreaSolve._finish_delta` qualification), Decision forces the full path
for (d) and batches that structurally change the LSDB, and Decision feeds
(e) in as explicit dirty prefixes. SR_MPLS-forwarding prefixes (KSP2 path
traces can move on edges no distance column reflects) are always dirty via
`PrefixState.mpls_forwarding_prefixes`. RFC 5286 LFA adds exactly one
input beyond the announcer columns — the ME column, read by every
alt-neighbor row's inequality threshold — so with an APSP-capable solver
(`lfa_delta_ready`, docs/Apsp.md) the delta path stays enabled under
`compute_lfa_paths`: the solver's poll answers None whenever the changed
set contains me (poisoning exactly the events whose LFA thresholds moved),
and every other LFA input is a changed-announcer column the dirty set
already covers. Solvers without a resident APSP state keep the historical
force-full behavior. Everything else is provably unchanged and is neither
recomputed nor diffed.

The correctness backstop is the SolverSupervisor's route-delta shadow audit
(`verify_route_delta`): every Nth delta-built db is compared against a full
rebuild, and mismatches self-heal exactly like warm-state audit hits.
"""

from __future__ import annotations

import logging
from typing import Callable, Dict, Optional, Set, Tuple

from openr_tpu.monitor.spans import stage
from openr_tpu.solver.routes import (
    DecisionRouteDb,
    DecisionRouteUpdate,
    apply_route_delta,
    get_route_delta,
)
from openr_tpu.types import is_mpls_label_valid

log = logging.getLogger(__name__)


class _ChangedRoutes:
    """Where the solver writes a partial rebuild's unicast entries. An
    entry is compared with the previous db's where it arrives, after the
    policy hook, and kept only if it differs: DeltaPath rebuilds the
    prefixes of every column in which my own distance or my first hops
    moved, and more where it cannot tell (under LFA, or where the mirrors
    were not in hand), so an unchanged route's entry dies at once instead
    of living to the build's end."""

    def __init__(self, prev_entries, policy_fn, changed: list) -> None:
        self._prev_entries = prev_entries
        self._policy_fn = policy_fn
        self._changed = changed
        self.routed: Set = set()  # prefixes that got an entry, changed or not

    def __setitem__(self, prefix, entry) -> None:
        self.routed.add(prefix)
        if self._policy_fn is not None:
            self._policy_fn(entry)
        old_entry = self._prev_entries.get(prefix)
        if old_entry is None or old_entry != entry:
            self._changed.append(entry)


class DeltaRouteBuilder:
    """Builds (new route db, update) per rebuild, taking the O(changes)
    partial path whenever the solver offers a device delta and the event
    class qualifies, else the classic full build + diff. Owned by Decision;
    drivable synchronously by tests without an event loop."""

    def __init__(self, solver, histograms: Optional[Dict] = None) -> None:
        self.solver = solver
        # the owner's histogram dict (decision.delta_build_ms,
        # decision.full_build_ms)
        self.histograms = histograms
        # label -> set of nodes advertising it (collision detection for the
        # partial node-label rebuild); rebuilt lazily after any full build,
        # so it can never span a structural change
        self._label_index: Optional[Dict[int, Set[str]]] = None
        self.last_error: Optional[BaseException] = None
        self.delta_builds = 0
        self.full_builds = 0

    # ------------------------------------------------------------------

    def build(
        self,
        my_node_name: str,
        area_link_states: Dict,
        prefix_state,
        prev_db: Optional[DecisionRouteDb],
        *,
        dirty_prefixes: Set = frozenset(),
        force_full: bool = False,
        policy_fn: Optional[Callable] = None,
        build: Optional[int] = None,
    ) -> Tuple[Optional[DecisionRouteDb], Optional[DecisionRouteUpdate], bool]:
        """Returns (new_db, update, used_delta). new_db is None when this
        node is in no area's graph (build_route_db contract). policy_fn, if
        given, is applied to every (re)computed unicast entry before
        diffing — the RibPolicy hook. `build` is Decision's number for
        this route build, the tag of its profiler stages."""
        self.last_error = None
        changed_nodes: Optional[Set[str]] = None
        try:
            # always drain the solver's accumulated delta, even when this
            # rebuild is forced full — a stale column set left pending
            # would otherwise ride into a later event's dirty set
            changed_nodes = self.solver.poll_device_delta(area_link_states)
        except Exception as exc:  # solve fault: the full path's supervised
            self.last_error = exc  # build_route_db owns retry/fallback
            log.warning("device delta poll failed: %s", exc)
        lfa_on = getattr(self.solver, "compute_lfa_paths", False)
        lfa_ready = getattr(self.solver, "lfa_delta_ready", None)
        result = None
        if (
            changed_nodes is not None
            and not force_full
            and prev_db is not None
            and (not lfa_on or (lfa_ready is not None and lfa_ready()))
        ):
            try:
                # the solver has returned: DeltaPath's route objects and
                # their comparison with the previous db
                with stage("decision.delta_build", self.histograms, build):
                    out = self._build_delta(
                        my_node_name,
                        area_link_states,
                        prefix_state,
                        prev_db,
                        changed_nodes,
                        set(dirty_prefixes),
                        policy_fn,
                    )
                if out is not None:
                    self.delta_builds += 1
                    result = out[0], out[1], True
            except Exception as exc:
                # a delta-path bug must degrade to the full build, never
                # wedge convergence
                self.last_error = exc
                log.exception("delta route build failed; falling back")
        if result is None:
            # the poll has resolved the areas (refresh, solve and, where
            # the delta was poisoned, the whole mirror's fetch, each under
            # its own phase): what is left is the host's per-prefix work
            with stage("decision.full_build", self.histograms, build):
                result = self._build_full(
                    my_node_name,
                    area_link_states,
                    prefix_state,
                    prev_db,
                    policy_fn,
                )
        # the build's distance reads fold nothing into the solver's
        # counters one by one: once, here, full or delta
        self.solver.sync_counters(area_link_states)
        return result

    # ------------------------------------------------------------------

    def _build_full(
        self, my_node_name, area_link_states, prefix_state, prev_db, policy_fn
    ):
        new_db = self.solver.build_route_db(
            my_node_name, area_link_states, prefix_state
        )
        self._label_index = None  # labels may have moved; rebuild lazily
        self.full_builds += 1
        if new_db is None:
            return None, None, False
        if policy_fn is not None:
            for entry in new_db.unicast_entries.values():
                policy_fn(entry)
        delta = get_route_delta(new_db, prev_db or DecisionRouteDb())
        return new_db, delta, False

    def _build_delta(
        self,
        my_node_name: str,
        area_link_states: Dict,
        prefix_state,
        prev_db: DecisionRouteDb,
        changed_nodes: Set[str],
        dirty_prefixes: Set,
        policy_fn: Optional[Callable],
    ) -> Optional[Tuple[DecisionRouteDb, DecisionRouteUpdate]]:
        """The partial rebuild; None bails to the full path (collision
        cases whose arbitration needs the whole table)."""
        dirty = dirty_prefixes
        dirty |= prefix_state.prefixes_for_nodes(changed_nodes)
        dirty |= set(prefix_state.mpls_forwarding_prefixes)

        update = DecisionRouteUpdate()
        ordered = sorted(dirty)
        advertised = prefix_state.prefixes
        rebuilt = _ChangedRoutes(
            prev_db.unicast_entries, policy_fn, update.unicast_routes_to_update
        )
        self.solver.build_unicast_routes(
            rebuilt,
            my_node_name,
            ((prefix, advertised.get(prefix)) for prefix in ordered),
            area_link_states,
            prefix_state,
        )
        update.unicast_routes_to_delete.extend(
            prefix
            for prefix in ordered
            if prefix not in rebuilt.routed
            and prefix in prev_db.unicast_entries
        )

        # node-label routes of the changed destinations (their distance /
        # nexthop set moved); adjacency-label routes depend only on my own
        # links, which never qualify for the delta path
        label_index = self._ensure_label_index(area_link_states)
        for area, link_state in sorted(area_link_states.items()):
            adj_dbs = link_state.get_adjacency_databases()
            for node in sorted(changed_nodes):
                adj_db = adj_dbs.get(node)
                if adj_db is None:
                    continue
                label = adj_db.node_label
                if label == 0 or not is_mpls_label_valid(label):
                    continue
                if len(label_index.get(label, ())) > 1:
                    # duplicate-label arbitration scans the whole table:
                    # leave it to the full path
                    return None
                entry = self.solver.build_node_label_route(
                    my_node_name, area, adj_db, area_link_states
                )
                old = prev_db.mpls_entries.get(label)
                if entry is None:
                    if old is not None:
                        update.mpls_routes_to_delete.append(label)
                elif old is None or old != entry:
                    update.mpls_routes_to_update.append(entry)

        return apply_route_delta(prev_db, update), update

    def _ensure_label_index(self, area_link_states) -> Dict[int, Set[str]]:
        """node-label -> advertising nodes, across areas. Built once per
        full build (labels only move in batches that force the full path),
        so delta events pay O(changes) lookups, not an O(n) scan."""
        if self._label_index is None:
            index: Dict[int, Set[str]] = {}
            for link_state in area_link_states.values():
                for adj_db in link_state.get_adjacency_databases().values():
                    if adj_db.node_label:
                        index.setdefault(adj_db.node_label, set()).add(
                            adj_db.this_node_name
                        )
            self._label_index = index
        return self._label_index
