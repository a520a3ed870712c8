"""TPU batched SPF solver backend.

Drop-in replacement for the CPU oracle: inherits the entire route-assembly
pipeline from SpfSolver and overrides the SPF access seam so that distances
and ECMP nexthop sets come from one batched min-plus solve on device
(openr_tpu.ops.spf) instead of per-source Dijkstra runs.

Per (area, topology-version, node) the solver compiles the LinkState to
padded arrays and solves for sources = {me} ∪ neighbors(me) in a single
device call — exactly the rows the route pipeline consumes:
  - reachability/metric from me (best-announcer selection, min-cost nodes)
  - dist(neighbor, t) for the triangle-condition ECMP nexthops and for the
    RFC 5286 LFA inequality
Nexthop sets are materialized lazily per queried destination via the triangle
condition w(me,n) + D[n,t] == D[me,t], which reproduces Dijkstra's
nexthop-union semantics (LinkState.cpp:855-871) without tracing paths.

KSP (k-edge-disjoint shortest paths) is fused on device as well: the
reference's per-destination penalized Dijkstra re-runs
(LinkState::getKthPaths link-ignore re-solve, LinkState.cpp:760-789) become
extra batch rows of one per-row-weights solve (ignored links ≙ INF weights),
so one device call covers every destination's k-th solve; only the cheap
greedy edge-disjoint back-trace (traceOnePath, LinkState.cpp:398-419) runs
host-side, reconstructed from the distance rows with exactly Dijkstra's
path-link ordering (settle order = (metric, name); links in per-node sorted
order).
"""

from __future__ import annotations

import time
from typing import (
    Dict,
    FrozenSet,
    Iterable,
    List,
    Optional,
    Set,
    Tuple,
    Union,
)

import numpy as np

from openr_tpu.lsdb.link_state import Link, LinkState, Path
from openr_tpu.ops.graph import (
    INF,
    CompiledGraph,
    _next_bucket,
    compile_graph,
    refresh_graph,
)
from openr_tpu.ops.spf import (
    batched_spf,
    batched_spf_vw,
    compile_cache_memory,
    compile_cache_stats,
    sell_fixpoint_masked,
)
from openr_tpu.monitor.memledger import get_ledger
from openr_tpu.solver.cpu import Metric, SpfSolver
from openr_tpu.solver.flight_recorder import (
    PhaseClock,
    SolveTrace,
    phase_stage,
)
from openr_tpu.lsdb.prefix_state import PrefixState
from openr_tpu.solver.routes import LabelNextHops, RibUnicastEntry
from openr_tpu.testing.faults import fault_point
from openr_tpu.types import (
    IpPrefix,
    NextHop,
    PrefixEntry,
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
    PrefixType,
)


class DeviceCapacityError(RuntimeError):
    """Predicted RESOURCE_EXHAUSTED: the memory ledger's capacity model
    says the chosen layout cannot fit current headroom. Raised BEFORE the
    device dispatch so the supervisor classifies it as `device_oom` and
    walks the degrade ladder (smaller mesh -> CPU oracle) instead of the
    allocator dying mid-solve."""


# fixed per-bucket patch width for the fused patch+solve executable; events
# changing more slots per bucket fall back to standalone scatters
_PATCH_SLOTS = 64

# DeltaPath extraction cutoff: when more than this fraction of the
# destination columns changed, the full [S, n_pad] mirror is the cheaper
# copy-back and the event is served as a full rebuild instead
_DELTA_MAX_FRAC = 0.5


# what became of a prefix in `TpuSpfSolver.build_unicast_routes`, where it is
# not the number of its next-hop set
_NO_ROUTE = -1
_ONE_BY_ONE = -2


def _run_starts(ids: np.ndarray) -> np.ndarray:
    """Where each run of equal values starts in `ids` (not empty)."""
    new = np.empty(len(ids), dtype=bool)
    new[0] = True
    np.not_equal(ids[1:], ids[:-1], out=new[1:])
    return np.flatnonzero(new)


def _nearest_announcers(
    item: np.ndarray, dist: np.ndarray, reach: np.ndarray, drained: np.ndarray
) -> np.ndarray:
    """Which of a batch's announcers its routes go toward. Announcer k
    belongs to prefix `item[k]` (ascending) and lies at `dist[k]`: of each
    prefix's reachable announcers the healthy ones, or all where all are
    drained, and of those the nearest."""
    toward = reach
    if drained.any():
        healthy = reach & ~drained
        some_healthy = np.zeros(item[-1] + 1, dtype=bool)
        some_healthy[item[healthy]] = True
        toward = np.where(some_healthy[item], healthy, reach)
    toward = np.flatnonzero(toward)
    if not len(toward):
        return toward
    starts = _run_starts(item[toward])
    nearest = np.minimum.reduceat(dist[toward], starts)
    lengths = np.diff(starts, append=len(toward))
    return toward[dist[toward] == np.repeat(nearest, lengths)]


class _NodeView:
    """NodeSpfResult-compatible view over the device distance matrix."""

    __slots__ = ("metric", "_result", "_dest")

    def __init__(self, metric: Metric, result: "_TpuSpfResult", dest: str):
        self.metric = metric
        self._result = result
        self._dest = dest

    @property
    def next_hops(self) -> Set[str]:
        return self._result.next_hops_of(self._dest)


class _TpuSpfResult:
    """SpfResult-compatible mapping dest -> _NodeView, backed by D rows."""

    def __init__(self, area: "_AreaSolve", source: str):
        self._area = area
        self._source = source
        self._src_row = area.row_map[source]
        self._nh_cache: Dict[str, Set[str]] = {}

    def __contains__(self, dest: str) -> bool:
        col = self._area.graph.node_index.get(dest)
        if col is None:
            return False
        return self._area.d[self._src_row, col] < INF

    def get(self, dest: str) -> Optional[_NodeView]:
        col = self._area.graph.node_index.get(dest)
        if col is None:
            return None
        metric = int(self._area.d[self._src_row, col])
        if metric >= INF:
            return None
        return _NodeView(metric, self, dest)

    def __getitem__(self, dest: str) -> _NodeView:
        view = self.get(dest)
        if view is None:
            raise KeyError(dest)
        return view

    def next_hops_of(self, dest: str) -> Set[str]:
        """ECMP nexthop node set for source -> dest via triangle condition.

        The batch only solves nexthop sets from the primary node's
        perspective (neighbor rows for other sources are not in it). For
        any other source the resident all-pairs matrix answers instead —
        the same triangle against APSP rows (docs/Apsp.md); without one,
        fail fast rather than serve a silent partial answer (the route
        pipeline only reads nexthop sets from my_node_name's perspective).
        """
        if self._source != self._area.sources[0]:
            cached = self._nh_cache.get(dest)
            if cached is not None:
                return cached
            if self._area.ensure_apsp():
                nhs = _ApspSpfResult(
                    self._area, self._source
                ).next_hops_of(dest)
                self._nh_cache[dest] = nhs
                return nhs
            raise RuntimeError(
                f"nexthop sets are only solved for {self._area.sources[0]}, "
                f"requested for {self._source}"
            )
        cached = self._nh_cache.get(dest)
        if cached is not None:
            return cached
        area = self._area
        nhs: Set[str] = set()
        if dest != self._source:
            col = area.graph.node_index.get(dest)
            if col is not None:
                names, mask = area.nh_mask()
                nhs = {n for n, hit in zip(names, mask[:, col]) if hit}
        self._nh_cache[dest] = nhs
        return nhs


class _ApspSpfResult:
    """SpfResult-compatible view for a source OUTSIDE the solved batch,
    backed by the area's resident all-pairs matrix (docs/Apsp.md).

    Metrics read the source's APSP row; nexthop sets fall out of the same
    triangle condition the batch path uses — w(s, n) + D[n, t] == D[s, t]
    over s's ordered up-links, with overloaded neighbors valid only as
    final destinations — but against ALT-NEIGHBOR ROWS of the one resident
    matrix instead of a per-source Dijkstra column solve (the CPU-oracle
    fallback this replaces)."""

    def __init__(self, area: "_AreaSolve", source: str):
        self._area = area
        self._source = source
        self._src_row = area.graph.node_index[source]
        self._nh_cache: Dict[str, Set[str]] = {}

    def __contains__(self, dest: str) -> bool:
        col = self._area.graph.node_index.get(dest)
        if col is None:
            return False
        return self._area.apsp.d[self._src_row, col] < INF

    def get(self, dest: str) -> Optional[_NodeView]:
        col = self._area.graph.node_index.get(dest)
        if col is None:
            return None
        metric = int(self._area.apsp.d[self._src_row, col])
        if metric >= INF:
            return None
        return _NodeView(metric, self, dest)

    def __getitem__(self, dest: str) -> _NodeView:
        view = self.get(dest)
        if view is None:
            raise KeyError(dest)
        return view

    def next_hops_of(self, dest: str) -> Set[str]:
        cached = self._nh_cache.get(dest)
        if cached is not None:
            return cached
        nhs: Set[str] = set()
        area = self._area
        idx = area.graph.node_index
        col = idx.get(dest)
        d = area.apsp.d
        if (
            dest != self._source
            and col is not None
            and d[self._src_row, col] < INF
        ):
            ls = area.link_state
            for link in ls.ordered_links_from_node(self._source):
                if not link.is_up():
                    continue
                n = link.other_node_name(self._source)
                ni = idx.get(n)
                if ni is None:
                    continue
                # an overloaded neighbor relays nothing: valid only when
                # it is itself the destination (nh_mask semantics)
                if ls.is_node_overloaded(n) and n != dest:
                    continue
                w = link.metric_from_node(self._source)
                if w + int(d[ni, col]) == int(d[self._src_row, col]):
                    nhs.add(n)
        self._nh_cache[dest] = nhs
        return nhs


class _NextHopTable:
    """Every destination's next hops, read off one resident solve.

    A destination's column of `nh_mask` names its first-hop links (its
    group), the distance row gives its metric, and the up-links'
    attributes are read once for all destinations. A group is keyed by
    what its column holds, not by which column it is: DeltaPath patches
    columns in place, and a content key needs no invalidation. The table
    goes where its mask goes (`_AreaSolve._drop_nh_mask`); the mask stays
    the one place that decides a first hop."""

    def __init__(self, solve: "_AreaSolve") -> None:
        me = solve.me
        self._solve = solve
        self._mask = solve.nh_mask()[1]
        self.attr_version = solve.link_state.link_attr_version
        # mask row i is my i-th up-link: (neighbour, v4 and v6 next-hop
        # address, interface, area)
        self._links = [
            (
                link.other_node_name(me),
                link.nh_v4_from_node(me),
                link.nh_v6_from_node(me),
                link.iface_from_node(me),
                link.area,
            )
            for link in solve.nh_up_links()
        ]
        assert len(self._links) == len(self._mask)
        # what a column holds -> (its links, their neighbours' names)
        self._groups: Dict[bytes, Tuple[Tuple[tuple, ...], FrozenSet[str]]] = {}
        # (group, metric, is_v4) -> the unicast next hops of every
        # destination behind that group at that distance
        self.unicast_sets: Dict[
            Tuple[bytes, int, bool], FrozenSet[NextHop]
        ] = {}

    def next_hops(
        self,
        dst_node_names: Set[str],
        is_v4: bool,
        swap_label: Optional[int],
        label_sets_made: List[int],
    ) -> Union[Set[NextHop], LabelNextHops, None]:
        """What `SpfSolver.next_hops_toward` gives without LFA and per-
        destination actions, or None where no destination is reachable. A
        label's next hops are its own by construction (its SWAP carries
        it), and with segment routing off nobody reads them: they are
        given as what determines them, and made where they are read
        (counted in `label_sets_made`)."""
        solve = self._solve
        node_index = solve.graph.node_index
        from_me = solve.d[0]
        if len(dst_node_names) == 1:
            (dst,) = dst_node_names
            col = node_index.get(dst)
            if col is None:
                return None
            metric = int(from_me[col])
            group_key = self._mask[:, col].tobytes()
        else:
            # the closest announcers (get_min_cost_nodes' rule): a link
            # is a first hop toward the set where it is toward one of them
            cols = [
                col
                for col in map(node_index.get, dst_node_names)
                if col is not None
            ]
            if not cols:
                return None
            dists = from_me[cols]
            metric = int(dists.min())
            nearest = [c for c, m in zip(cols, dists) if m == metric]
            group_key = self._mask[:, nearest].any(axis=1).tobytes()
        if metric >= INF:
            return None
        links, neighbors = self._group(group_key)
        if not links:
            return None  # toward myself
        if swap_label is not None:
            return LabelNextHops(
                links,
                metric,
                is_v4,
                swap_label,
                # PHP over the destination's own link; elsewhere the one
                # empty frozenset, not an object per route
                neighbors & dst_node_names or frozenset(),
                label_sets_made,
            )
        # a set of its own for every route: RibPolicy rewrites an entry's
        # nexthops, and no sibling's may change with it
        return set(self._shared_set(group_key, links, metric, is_v4))

    def read_unicast(
        self,
        cols: np.ndarray,
        starts: np.ndarray,
        metrics: np.ndarray,
        is_v4: np.ndarray,
    ) -> Tuple[np.ndarray, List[FrozenSet[NextHop]]]:
        """`next_hops` without a label, for all the routes of a build in
        one read. Route r goes toward the destinations whose columns are
        `cols[starts[r]:starts[r + 1]]`, all at distance `metrics[r]` (its
        nearest announcers). Returns, for each route, which of the returned
        sets holds its next hops (-1 where no link leads there: toward
        myself), and the sets: shared, so a route takes a copy."""
        routes = len(starts)
        if not routes or not self._links:
            return np.full(routes, -1, dtype=np.intp), []
        member = self._mask[:, cols]  # [links, destinations]
        if routes < len(cols):
            # a link is a first hop toward a set where it is toward one
            member = np.logical_or.reduceat(member, starts, axis=1)
        member = np.ascontiguousarray(member.T)
        # routes of one (group, metric, family) share a set: one sort of
        # the three laid side by side finds them
        keys = np.concatenate(
            (
                np.packbits(member, axis=1),
                metrics.astype("<i4")[:, None].view(np.uint8),
                is_v4.astype(np.uint8)[:, None],
            ),
            axis=1,
        )
        _, first, which = np.unique(
            keys.view(np.dtype((np.void, keys.shape[1]))).ravel(),
            return_index=True,
            return_inverse=True,
        )
        sets: List[FrozenSet[NextHop]] = []
        for route in first.tolist():
            group_key = member[route].tobytes()
            links, _ = self._group(group_key)
            sets.append(
                self._shared_set(
                    group_key, links, int(metrics[route]), bool(is_v4[route])
                )
                if links
                else frozenset()
            )
        has_links = np.fromiter(map(bool, sets), dtype=bool, count=len(sets))
        return np.where(has_links[which], which, -1), sets

    def _group(
        self, group_key: bytes
    ) -> Tuple[Tuple[tuple, ...], FrozenSet[str]]:
        """(links, their neighbours' names) of what a column holds."""
        group = self._groups.get(group_key)
        if group is None:
            member = np.frombuffer(group_key, dtype=np.bool_)
            links = tuple(self._links[i] for i in np.flatnonzero(member))
            group = self._groups[group_key] = (
                links, frozenset(link[0] for link in links)
            )
        return group

    def _shared_set(
        self, group_key: bytes, links, metric: int, is_v4: bool
    ) -> FrozenSet[NextHop]:
        """The unicast next hops of every destination behind a group at
        one distance in one family, made once."""
        key = (group_key, metric, is_v4)
        shared = self.unicast_sets.get(key)
        if shared is None:
            shared = self.unicast_sets[key] = frozenset(
                NextHop(
                    v4 if is_v4 else v6,
                    iface,
                    metric,
                    None,
                    False,
                    area,
                    0,
                    neighbor,
                )
                for neighbor, v4, v6, iface, area in links
            )
        return shared


class _AreaSolve:
    """One batched device solve: sources = [me] + up-neighbors(me).

    Incremental event path: on topology change, `refresh()` patches the
    compiled arrays via the LinkState changelog (weight-only changes keep
    shapes and jit executables) and re-runs the device solve. The source
    batch is bucket-padded so a changed neighbor count stays in the same
    executable too.

    The distance matrix stays DEVICE-RESIDENT between events: host readers
    go through the lazy `d` mirror, and a weight-patch event feeds the
    previous fixpoint back in as the warm initial state (decrease-only
    events directly; events with weight increases first invalidate the
    entries whose old shortest path witnesses a changed edge — see
    ops.spf._sell_solver_warm). A cold solve is forced by a structural
    rebuild, a source-batch change, an overload-mask change, or a
    _PATCH_SLOTS overflow."""

    def __init__(
        self,
        link_state: LinkState,
        me: str,
        mesh=None,
        warm_start: bool = True,
        apsp_max_nodes: int = 0,
        apsp_audit_interval: int = 0,
        apsp_dispatch=None,
        recorder=None,
        on_capacity_refusal=None,
    ) -> None:
        self.link_state = link_state
        self.me = me
        # device-memory ledger (monitor/memledger.py): every persistent
        # buffer this solve uploads registers under this area tag and is
        # released by close() — the exact-accounting observatory surface
        self._ledger = get_ledger()
        self._mem_area = f"{link_state.area}/{me}"
        self._mem: Dict[str, int] = {}
        self._on_capacity_refusal = on_capacity_refusal
        # flight recorder (solver/flight_recorder.py): every solve emits a
        # SolveTrace into the bounded per-area ring, with the host's time
        # split by a PhaseClock whose seams never wait for the device
        self._recorder = recorder
        self._pclock = PhaseClock()
        self._last_trace: Optional[SolveTrace] = None
        # jax.sharding.Mesh or None: when set, the source batch is sharded
        # over the mesh 'batch' axis and the persistent layout buffers are
        # replicated across devices — same executables, multi-chip spread
        self.mesh = mesh
        self.warm_start = warm_start
        self.graph: CompiledGraph = compile_graph(link_state)
        # resident all-pairs matrix (docs/Apsp.md): lazily closed on first
        # consumer read, warm-re-closed per weight event, poisoned with the
        # batch warm state; None when the apsp knob is off
        self.apsp = None
        if apsp_max_nodes > 0:
            from openr_tpu.apsp import ApspState

            self.apsp = ApspState(
                apsp_max_nodes,
                dispatch=apsp_dispatch,
                audit_interval=apsp_audit_interval,
                warm=warm_start,
                area=self._mem_area,
                on_refusal=self._note_capacity_refusal,
            )
        self.device_solves = 0
        self.ksp_device_batches = 0
        self.ksp_warm_batches = 0  # penalized batches seeded from the base
        # convergence observability (decision.spf.* counters)
        self.incremental_solves = 0  # warm-started weight-patch solves
        self.full_solves = 0  # cold solves (from D0 = INF)
        self.rounds_last: Optional[int] = None  # relax rounds of last solve
        # boolean invalidation-mark fixpoint rounds of the last WARM solve
        # (None until one runs; 0 for decrease-only events)
        self.invalidation_rounds_last: Optional[int] = None
        # profiling (decision.spf.* histograms/gauges): wall time of the
        # last solve dispatch + whether it rode the warm path, and the
        # host<->device traffic this solve has generated — the warm event
        # path's whole point is shrinking both, so they are measured in the
        # serving path, not offline
        self.solve_ms_last: Optional[float] = None
        self.last_solve_warm = False
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        # host reads that block on a device value (decision.spf.device_syncs)
        self.device_syncs = 0
        # warm refreshes that ended in compile_graph, whatever the reason
        # (decision.spf.graph_recompiles): each is a cold solve and a full
        # route build
        self.graph_recompiles = 0
        # links that arrived in or left the LSDB and that a warm refresh
        # took as patches of slots the snapshot had
        # (decision.spf.graph_links_patched): without it, no recompiles
        # cannot be told from no such event
        self.graph_links_patched = 0
        # halo-exchange accounting (the 2-D tiled layout's cross-chip
        # traffic): ring-rotation count of the last solve and cumulative
        # frontier bytes moved between chips — the destination-sharded
        # analog of the d2h/h2d counters (docs/Monitoring.md)
        self.halo_bytes = 0
        self.halo_exchanges_last: Optional[int] = None
        self._halo_synced = 0
        # DeltaPath (device-side route-delta extraction) accounting: the
        # changed-destination columns and copy-back bytes of extraction
        # dispatches — d2h_bytes grows by delta_bytes on the delta path and
        # by the full [S, n_pad] mirror on the cold/audit path, which is
        # how the two are told apart in tests and dashboards
        self.delta_extracts = 0
        self.delta_columns = 0
        # of those, the columns a route from here reads: where my own
        # distance row or my first-hop mask moved (decision.spf.
        # delta_route_columns)
        self.delta_route_columns = 0
        self.delta_bytes = 0
        self.delta_extract_ms_last: Optional[float] = None
        # changed destination columns accumulated for the route-delta
        # consumer (take_route_delta), every changed one and the route
        # columns among them; None = poisoned: some solve since the last
        # take had no device delta, the consumer must full-rebuild
        self._delta_pending: Optional[set] = set()
        self._route_pending: Optional[set] = set()
        self._last_solve_delta: Optional[np.ndarray] = None
        self._last_route_delta: Optional[np.ndarray] = None
        # _sync_spf_counters bookmarks (what is already folded into counters)
        self._inc_synced = 0
        self._full_synced = 0
        self._h2d_synced = 0
        self._d2h_synced = 0
        self._device_syncs_synced = 0
        self._graph_recompiles_synced = 0
        self._graph_links_patched_synced = 0
        self._delta_cols_synced = 0
        self._delta_route_cols_synced = 0
        self._delta_bytes_synced = 0
        self._delta_extracts_synced = 0
        self._ksp_warm_synced = 0
        # persistent device buffers (SURVEY.md §7: the <100ms convergence
        # budget leaves no room to re-upload the LSDB per event): sell
        # nbr/wg/overloaded live on device across events; weight patches
        # upload only the changed slots
        self._dev: Optional[dict] = None
        # device-resident distance matrix [s_pad, n_pad] + lazy host mirror
        self._d_dev = None
        self._d_host: Optional[np.ndarray] = None
        self._solve()

    @property
    def d(self) -> np.ndarray:
        """Host mirror of the device-resident distance matrix, fetched on
        first access after each solve — chained events that are never read
        host-side (or only read late) skip the [S, n_pad] copy-back.

        An OWNED copy, not np.asarray: on the CPU backend asarray can be a
        zero-copy view of the device buffer, and the warm solver donates
        that buffer to the next event — a view would alias reused memory."""
        if self._d_host is None:
            fetch = phase_stage("d2h", self._pclock.build)
            try:
                self._d_host = self._to_host(self._d_dev)
            finally:
                ms = fetch.stop()  # a failed read leaves no stage open
            self.d2h_bytes += self._d_host.nbytes
            trace = self._last_trace
            if trace is not None:
                # the lazy mirror fetch is this solve's d2h phase; it
                # lands after the trace was recorded, so attribute it
                # post-hoc (the ring holds the live object) and queue the
                # histogram sample for the next counter sync
                trace.phases["d2h"] = trace.phases.get("d2h", 0.0) + ms
                trace.d2h_bytes += self._d_host.nbytes
                self._recorder.observe_phase("d2h", ms)
            self._mem_register(
                "mirror", "host", arrays=(self._d_host,)
            )
        return self._d_host

    # -- blocking device reads (decision.spf.device_syncs) --------------

    def _to_int(self, value) -> int:
        """A device scalar read on the host: the host waits for the
        program that computes it."""
        self.device_syncs += 1
        return int(value)

    def _to_host(self, value) -> np.ndarray:
        """An owned host copy of a device array, waited for likewise."""
        self.device_syncs += 1
        return np.array(value)

    # -- device-memory ledger seams (monitor/memledger.py) -------------

    def _mem_register(
        self, structure: str, layout: str, arrays=(), nbytes=None
    ) -> None:
        """Ledger register seam: (re-)register one named resident
        structure under this area, releasing the previous generation
        first — a structural rebuild frees the old buffers when the new
        upload replaces them, so live_bytes tracks what is actually
        reachable on device."""
        self._ledger.release(self._mem.pop(structure, None))
        self._mem[structure] = self._ledger.register(
            self._mem_area,
            structure,
            layout=layout,
            arrays=arrays,
            nbytes=nbytes,
        )

    def _mem_release(self, structure: str) -> None:
        """Ledger release seam for one named structure."""
        self._ledger.release(self._mem.pop(structure, None))

    def close(self) -> None:
        """Area teardown: release every ledger-registered structure (the
        resident distance matrix, layout buffers, patch slots, mirrors)
        and the APSP state. Called when the owning TpuSpfSolver drops or
        replaces this solve (invalidation, mesh degradation, LinkState
        replacement)."""
        if self.apsp is not None:
            self.apsp.close()
        for structure in list(self._mem):
            self._mem_release(structure)

    def _note_capacity_refusal(self, verdict: Dict) -> None:
        """Record + propagate a headroom-gated admission refusal up to
        the owning solver (surfaced as SOLVER_CAPACITY_REFUSED)."""
        if self._on_capacity_refusal is not None:
            self._on_capacity_refusal(verdict)

    def _admit_layout(self, layout: str) -> None:
        """Predictive capacity admission: before the first dispatch of a
        layout, ask the ledger's forward model whether it fits current
        headroom. No capacity source (the CPU tier-1 backend) -> no
        verdict -> admit; a definite no-fit raises DeviceCapacityError so
        the supervisor degrades (device_oom ladder) BEFORE the allocator
        raises RESOURCE_EXHAUSTED mid-solve."""
        verdict = self._ledger.predict_fit(
            self.graph.n,
            layout,
            n_sources=len(getattr(self, "sources", ())) or 1,
            graph=self.graph,
            mesh_shape=(
                (self.mesh.shape["batch"], self.mesh.shape["graph"])
                if self.mesh is not None
                else None
            ),
        )
        if verdict["fits"] is False:
            self._ledger.record_refusal(verdict)
            self._note_capacity_refusal(verdict)
            raise DeviceCapacityError(
                f"predicted RESOURCE_EXHAUSTED: layout {layout} for area "
                f"{self._mem_area} needs {verdict['predicted_bytes']} bytes, "
                f"headroom {verdict['headroom_bytes']} "
                f"(capacity {verdict['capacity_bytes']}, "
                f"source {verdict['source']})"
            )

    def _batch_pad(self, n: int, minimum: int = 8) -> int:
        """Source-batch pad: power-of-two bucket, rounded up to a multiple
        of the mesh batch-axis size so GSPMD splits rows evenly."""
        s_pad = _next_bucket(n, minimum=minimum)
        if self.mesh is not None:
            b = self.mesh.shape["batch"]
            s_pad += (-s_pad) % b
        return s_pad

    def _replicated(self, x):
        """Device placement for a persistent layout buffer: plain asarray
        single-device, explicitly replicated under a mesh (committed, so
        every sharded solve reuses it without per-call resharding)."""
        import jax
        import jax.numpy as jnp

        if self.mesh is None:
            return jnp.asarray(x)
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(jnp.asarray(x), NamedSharding(self.mesh, P()))

    def _solve(self, refresh: bool = False) -> None:
        """One solve under a phase clock of its own; with `refresh`, the
        changelog is first replayed into the compiled graph."""
        pc = self._pclock = PhaseClock(getattr(self._recorder, "build", None))
        try:
            if refresh:
                pc.enter("refresh")
                old = self.graph
                self.graph = refresh_graph(old, self.link_state)
                # a patched snapshot shares its parent's link_edges
                if self.graph.link_edges is not old.link_edges:
                    self.graph_recompiles += 1
                elif self.graph is not old:
                    self.graph_links_patched += self.graph.links_patched
            self._solve_phases(pc)
        finally:
            pc.stop()  # a fault mid-phase still ends its profiler span

    def _solve_phases(self, pc: PhaseClock) -> None:
        # `solve_ms` and the phases inside it start together, so that the
        # phases add up to it (`refresh` lies before, `d2h` after)
        pc.enter("prepare")
        t0 = time.perf_counter()
        # named fault seam: the supervisor's error-classification/breaker
        # tests inject compile/runtime/device-loss faults here, exactly
        # where a real XLA dispatch would raise
        fault_point("solver.tpu.solve", self)
        me = self.me
        neighbors = sorted(
            {
                link.other_node_name(me)
                for link in self.link_state.links_from_node(me)
                if link.is_up()
            }
        )
        self.sources: List[str] = [me] + neighbors
        self.row_map: Dict[str, int] = {
            name: i for i, name in enumerate(self.sources)
        }
        rows = np.array(
            [self.graph.node_index[s] for s in self.sources], dtype=np.int32
        )
        s_pad = self._batch_pad(len(rows), minimum=8)
        rows = np.concatenate(
            [rows, np.full(s_pad - len(rows), rows[0], dtype=np.int32)]
        )
        # one device call for the whole batch; results stay device-resident
        # (the host mirror is fetched lazily through the `d` property).
        # Timing covers rows + patch build + dispatch; on the sliced-ELL
        # paths the scalar `rounds` output forces completion of the same
        # computation, so the measured wall time includes device execution
        # there.
        inc_before = self.incremental_solves
        self._last_solve_delta = None  # set by a qualifying resident solve
        rec = self._recorder
        h2d0, d2h0, halo0 = self.h2d_bytes, self.d2h_bytes, self.halo_bytes
        misses0 = compile_cache_stats()["misses"] if rec is not None else 0
        self.h2d_bytes += rows.nbytes
        if self._use_tiled():
            self._admit_layout("tile2d")
            self._d_dev, self.rounds_last = self._tile_solve_resident(rows)
        elif self.graph.sell is not None:
            self._admit_layout("sell")
            self._d_dev, self.rounds_last = self._sell_solve_resident(rows)
        elif self.mesh is not None:
            from openr_tpu.parallel import sharded_batched_spf

            self._admit_layout("replicated")
            pc.enter("h2d")
            self._d_dev = sharded_batched_spf(self.graph, rows, self.mesh)
            self.rounds_last = None  # edge-list form: rounds untracked
            self.full_solves += 1
        else:
            self._admit_layout("bf")
            self._d_dev, self.rounds_last = self._bf_solve_resident(rows)
        self._mem_register(
            "dist",
            (self._dev or {}).get("kind", "none"),
            arrays=(self._d_dev,),
        )
        pc.stop()
        self.solve_ms_last = (time.perf_counter() - t0) * 1e3
        self.last_solve_warm = self.incremental_solves > inc_before
        self.device_solves += 1
        if self._last_solve_delta is None:
            # cold or non-qualifying event: the host mirrors are stale and
            # the accumulated delta cannot describe the event — poison it
            # until the consumer takes it (and full-rebuilds)
            self._d_host = None
            self._mem_release("mirror")
            self._drop_nh_mask()
            self._delta_pending = self._route_pending = None
        elif self._delta_pending is not None:
            # qualifying event: mirrors were patched in place during
            # extraction, the changed columns accumulate for the consumer
            self._delta_pending.update(self._last_solve_delta.tolist())
            self._route_pending.update(self._last_route_delta.tolist())
        # KSP: (dest, k) -> traced edge-disjoint path set for src == me;
        # reset with the snapshot, so topology changes invalidate it for free
        self._ksp: Dict[Tuple[str, int], List[Path]] = {}
        # source -> its _TpuSpfResult, one for all the reads of this solve
        self._spf_results: Dict[str, _TpuSpfResult] = {}
        # APSP staleness guard (docs/Apsp.md): any event that poisons the
        # batch warm solve — cold start, patch overflow, structural
        # rebuild, overload change — also invalidates the resident
        # all-pairs matrix, so a consumer can never read distances the
        # event classes above moved out from under it. Warm events leave
        # it resident; its own ensure() re-closes the touched blocks.
        if self.apsp is not None and not self.last_solve_warm:
            self.apsp.invalidate("batch_warm_poisoned")
        if rec is not None:
            kind = (self._dev or {}).get("kind") or (
                "replicated" if self.mesh is not None else "none"
            )
            self._last_trace = SolveTrace(
                seq=rec.next_seq(),
                ts=time.time(),
                area=self.link_state.area,
                node=self.me,
                event="solve",
                layout=kind,
                warm=self.last_solve_warm,
                solve_ms=self.solve_ms_last,
                rounds=self.rounds_last,
                invalidation_rounds=(
                    self.invalidation_rounds_last
                    if self.last_solve_warm
                    else None
                ),
                halo_exchanges=(
                    self.halo_exchanges_last if kind == "tile2d" else None
                ),
                h2d_bytes=self.h2d_bytes - h2d0,
                d2h_bytes=self.d2h_bytes - d2h0,
                halo_bytes=self.halo_bytes - halo0,
                delta_columns=(
                    len(self._last_solve_delta)
                    if self._last_solve_delta is not None
                    else None
                ),
                compile_cache_misses=(
                    compile_cache_stats()["misses"] - misses0
                ),
                breaker_state=rec.breaker_state,
                phases=dict(pc.phases),
            )
            rec.record(self._last_trace, pc)
        # corruption seam (ctx = this solve): the warm-state audit tests
        # perturb the resident D here to prove divergence detection works
        fault_point("solver.tpu.warm_d", self)

    def ensure_apsp(self) -> bool:
        """Bring the resident all-pairs matrix current with this solve's
        graph snapshot; False when APSP is off or the area exceeds the
        node cap (consumers fall back to their column-solve paths)."""
        if self.apsp is None:
            return False
        return self.apsp.ensure(self.graph)

    def _use_tiled(self) -> bool:
        """The destination-tiled P('batch', 'graph') layout serves whenever
        the mesh has a real graph axis and it divides the padded node
        count (both are powers of two in practice). A graph axis of one
        has nothing to tile — the row-sharded replica layouts keep it."""
        return (
            self.mesh is not None
            and self.mesh.shape["graph"] > 1
            and self.graph.n_pad % self.mesh.shape["graph"] == 0
        )

    def _graph_sharded(self, x):
        """Device placement for a per-partition tiled buffer: leading dim
        split over the mesh 'graph' axis, replicated over 'batch'."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(
            jnp.asarray(x), NamedSharding(self.mesh, P("graph", None))
        )

    def _account_halo(self, exchanges: int) -> None:
        """Fold one tiled solve's ring traffic into the halo counters:
        `exchanges` ppermute rotations ran, and per rotation every device
        forwarded its compact frontier (ctr [S_l, h] int32) plus the
        slot->column map ([h] int32)."""
        tiling = self._dev["tiling"]
        b = self.mesh.shape["batch"]
        g = self.mesh.shape["graph"]
        s_l = max(len(self._dev["rows"]) // max(b, 1), 1)
        payload = (s_l * tiling.h + tiling.h) * 4
        self.halo_exchanges_last = exchanges
        self.halo_bytes += exchanges * b * g * payload

    def _tile_solve_resident(self, rows: np.ndarray):
        """Destination-tiled solve against persistent device buffers;
        returns (device distance matrix [s_pad, n_pad] sharded
        P('batch', 'graph'), relaxation rounds).

        Persistent state per device is a [s_pad/batch, n_pad/graph] tile
        plus its partition's slice of the tiled edge arrays — no chip holds
        the full destination axis. The warm event path uploads the whole
        [g, e_tile] tiled weight array (the layout's native patch unit,
        like the edge-list form) and lets the device classify increases
        against the resident copy; overload toggles ride the same warm
        invalidation (newly-overloaded out-edges become seed edges, the
        repair relax uses the new transit mask), so only structural
        rebuilds and source-batch changes force a cold solve."""
        import jax.numpy as jnp

        from openr_tpu.ops.spf import _tile_solver, _tile_solver_warm
        from openr_tpu.parallel import tile_graph

        g = self.graph
        g_ax = self.mesh.shape["graph"]
        st = self._dev
        if (
            st is None
            or st.get("kind") != "tile2d"
            or st["src_ref"] is not g.src
        ):
            tiling = tile_graph(g, g_ax)
            self._pclock.enter("h2d")
            st = self._dev = {
                "kind": "tile2d",
                "src_ref": g.src,
                "tiling": tiling,
                "src_l": self._graph_sharded(tiling.src_l),
                "hseg": self._graph_sharded(tiling.hseg),
                "w2": self._graph_sharded(tiling.tile_weights(g.w)),
                "hcols": self._graph_sharded(tiling.hcols),
                "ov": self._replicated(g.overloaded),
                "w_host": g.w.copy(),
                "w_ver": g.version,
                "ov_host": g.overloaded.copy(),
                "rows": np.array(rows),
            }
            self.h2d_bytes += (
                tiling.src_l.nbytes
                + tiling.hseg.nbytes
                + tiling.w.nbytes
                + tiling.hcols.nbytes
                + g.overloaded.nbytes
            )
            self._mem_register(
                "tile",
                "tile2d",
                arrays=(st["src_l"], st["hseg"], st["w2"], st["ov"]),
            )
            self._mem_register("halo", "tile2d", arrays=(st["hcols"],))
        else:
            tiling = st["tiling"]
            ov_changed = not np.array_equal(st["ov_host"], g.overloaded)
            rows_same = np.array_equal(st["rows"], rows)
            st["rows"] = np.array(rows)
            if (
                g.changed_edges is not None
                and g.parent_version == st.get("w_ver")
            ):
                cand = g.changed_edges
                changed = cand[st["w_host"][cand] != g.w[cand]]
            else:
                changed = np.nonzero(st["w_host"][: g.e] != g.w[: g.e])[0]
            st["w_ver"] = g.version
            if (
                self.warm_start
                and rows_same
                and (len(changed) or ov_changed)
                and self._d_dev is not None
            ):
                self._pclock.enter("h2d")
                w2_new = self._graph_sharded(tiling.tile_weights(g.w))
                self.h2d_bytes += tiling.w.nbytes
                ov_new = st["ov"]
                if ov_changed:
                    ov_new = self._replicated(g.overloaded)
                    self.h2d_bytes += g.overloaded.nbytes
                # DeltaPath qualification: same contract as the other
                # layouts — my own out-link metrics and the transit mask
                # feed the route build outside D, so events touching
                # either cannot be described by changed columns alone
                delta_ok = not ov_changed and not np.any(
                    g.src[changed] == rows[0]
                )
                fn = _tile_solver_warm(
                    tiling.shape_key() + (g.n_pad,), self.mesh
                )
                d, rounds, inv_rounds, col_changed, num_changed = fn(
                    jnp.asarray(rows, dtype=jnp.int32),
                    st["src_l"],
                    st["hseg"],
                    w2_new,
                    st["w2"],
                    st["hcols"],
                    ov_new,
                    st["ov"],
                    self._d_dev,
                )
                self._pclock.enter("relax")
                st["w2"] = w2_new
                st["w_host"] = g.w.copy()
                st["ov"] = ov_new
                st["ov_host"] = g.overloaded.copy()
                self.incremental_solves += 1
                self.invalidation_rounds_last = self._to_int(inv_rounds)
                rounds = self._to_int(rounds)
                # seed exchange + one ring per invalidation and relax round
                self._account_halo(
                    (g_ax - 1) * (1 + self.invalidation_rounds_last + rounds)
                )
                self._finish_delta(col_changed, num_changed, d, delta_ok)
                return d, rounds
            if len(changed):
                st["w2"] = self._graph_sharded(tiling.tile_weights(g.w))
                st["w_host"] = g.w.copy()
                self.h2d_bytes += tiling.w.nbytes
            if ov_changed:
                st["ov"] = self._replicated(g.overloaded)
                st["ov_host"] = g.overloaded.copy()
                self.h2d_bytes += g.overloaded.nbytes

        fn = _tile_solver(st["tiling"].shape_key() + (g.n_pad,), self.mesh)
        self._pclock.enter("h2d")
        d, rounds = fn(
            jnp.asarray(rows, dtype=jnp.int32),
            st["src_l"],
            st["hseg"],
            st["w2"],
            st["hcols"],
            st["ov"],
        )
        self._pclock.enter("relax")
        self.full_solves += 1
        rounds = self._to_int(rounds)
        self._account_halo((g_ax - 1) * rounds)
        return d, rounds

    def _sell_solve_resident(self, rows: np.ndarray):
        """Sliced-ELL solve against persistent device buffers; returns
        (device distance matrix [s_pad, n_pad], relaxation rounds).

        The first call (or any structural rebuild, detected by src array
        identity) uploads the full layout; subsequent events diff the host
        weight/overload arrays against the device snapshot and upload only
        the changed slots (`.at[].set` with tiny index arrays) — a link
        flap moves a handful of ints over the host-device link instead of
        the whole LSDB. When the event is a pure weight patch (same source
        batch, same overload mask, fits _PATCH_SLOTS), the previous
        device-resident distances warm-start the fixpoint instead of
        re-relaxing from INF."""
        import jax.numpy as jnp

        from openr_tpu.ops.spf import (
            _sell_solver_counted,
            _sell_solver_patched,
            _sell_solver_warm,
        )

        g = self.graph
        sell = g.sell
        st = self._dev
        if st is None or st.get("kind") != "sell" or st["src_ref"] is not g.src:
            self._pclock.enter("h2d")
            st = self._dev = {
                "kind": "sell",
                "src_ref": g.src,
                "nbrs": tuple(self._replicated(a) for a in sell.nbr),
                "wgs": tuple(self._replicated(a) for a in sell.wg),
                "ov": self._replicated(g.overloaded),
                "w_host": g.w.copy(),
                "w_ver": g.version,
                "ov_host": g.overloaded.copy(),
                "rows": np.array(rows),
            }
            self.h2d_bytes += (
                sum(a.nbytes for a in sell.nbr)
                + sum(a.nbytes for a in sell.wg)
                + g.overloaded.nbytes
            )
            self._mem_register(
                "sell",
                "sell",
                arrays=(*st["nbrs"], *st["wgs"], st["ov"]),
            )
            # fixed-capacity weight-patch slots (rowcol [nb,64,2] + vals
            # [nb,64], int32): allocated fresh per patched event but the
            # capacity is layout-constant, so the ledger carries it as one
            # resident-equivalent entry per sell generation
            self._mem_register(
                "patch",
                "sell",
                nbytes=len(sell.nbr) * _PATCH_SLOTS * 3 * 4,
            )
        else:
            ov_changed = not np.array_equal(st["ov_host"], g.overloaded)
            ov_seed_edges = np.empty(0, dtype=np.int64)
            if ov_changed:
                # an overload toggle is a transit-mask change, but it is
                # expressible as weight increases on the node's incident
                # edges: for every other source, a newly-overloaded node's
                # out-edges just rose to INF, so exactly the entries whose
                # old shortest path witnesses one of those edges must be
                # invalidated — the same seed shape as a metric increase.
                # Un-overloading only ADDS paths (the old D stays an upper
                # bound) and warm-starts as-is. Either way the toggle rides
                # the existing warm invalidation path instead of forcing a
                # cold solve (ROADMAP open item).
                newly_on = np.nonzero(g.overloaded & ~st["ov_host"])[0]
                if len(newly_on):
                    ov_seed_edges = np.nonzero(
                        np.isin(g.src[: g.e], newly_on)
                    )[0]
                    # down edges (old weight INF) are never on the old DAG
                    ov_seed_edges = ov_seed_edges[
                        st["w_host"][ov_seed_edges] < INF
                    ]
                st["ov"] = self._replicated(g.overloaded)
                st["ov_host"] = g.overloaded.copy()
                self.h2d_bytes += g.overloaded.nbytes
            # warm start needs the previous fixpoint to describe the same
            # problem modulo edge weights: identical source batch (a flap
            # adjacent to me changes the rows); transit-mask changes are
            # folded into the invalidation seeds above
            rows_same = np.array_equal(st["rows"], rows)
            st["rows"] = np.array(rows)
            if (
                g.changed_edges is not None
                and g.parent_version == st.get("w_ver")
            ):
                # refresh provenance matches our snapshot: diff only the
                # positions the changelog touched instead of all of w
                cand = g.changed_edges
                changed = cand[st["w_host"][cand] != g.w[cand]]
            else:
                changed = np.nonzero(st["w_host"][: g.e] != g.w[: g.e])[0]
            st["w_ver"] = g.version  # snapshot is current even if no diff
            if len(changed) or ov_changed:
                # classify vs the weights that produced the resident D —
                # increases invalidate, decreases warm-start as-is
                increased = changed[g.w[changed] > st["w_host"][changed]]
                st["w_host"][changed] = g.w[changed]
                # invalidation seed set: weight increases plus the
                # out-edges of newly-overloaded nodes (duplicates are
                # harmless — seeding is an idempotent boolean max)
                inc_edges = (
                    np.concatenate([increased, ov_seed_edges])
                    if len(ov_seed_edges)
                    else increased
                )
                # fused patch+solve: one dispatch carries the changed slots
                # and returns the distances plus the patched buffers, which
                # stay device-resident for the next event. The patch shape
                # is FIXED (_PATCH_SLOTS per bucket) so every event shares
                # one executable — a varying pad would recompile the whole
                # fixpoint per new event size. Oversized events (SRLG-style
                # bulk changes) fall back to standalone scatters + plain
                # solve, whose small ops are cheap to compile per shape.
                nb = len(sell.nbr)
                per_bucket = [
                    changed[sell.edge_bucket[changed] == k]
                    for k in range(nb)
                ]
                fits_inc = all(
                    np.count_nonzero(sell.edge_bucket[inc_edges] == k)
                    <= _PATCH_SLOTS
                    for k in range(nb)
                )
                if all(len(s_) <= _PATCH_SLOTS for s_ in per_bucket):
                    idx = np.full(
                        (nb, _PATCH_SLOTS, 2), 1 << 30, dtype=np.int32
                    )
                    vals = np.zeros((nb, _PATCH_SLOTS), dtype=np.int32)
                    for k, sel in enumerate(per_bucket):
                        if len(sel):
                            idx[k, : len(sel), 0] = sell.edge_row[sel]
                            idx[k, : len(sel), 1] = sell.edge_slot[sel]
                            vals[k, : len(sel)] = g.w[sel]
                    self.h2d_bytes += idx.nbytes + vals.nbytes
                    warm = (
                        self.warm_start
                        and rows_same
                        and fits_inc
                        and self._d_dev is not None
                    )
                    if warm:
                        inc_idx = np.full(
                            (nb, _PATCH_SLOTS, 2), 1 << 30, dtype=np.int32
                        )
                        for k in range(nb):
                            sel = inc_edges[sell.edge_bucket[inc_edges] == k]
                            if len(sel):
                                inc_idx[k, : len(sel), 0] = sell.edge_row[sel]
                                inc_idx[k, : len(sel), 1] = sell.edge_slot[sel]
                        self.h2d_bytes += inc_idx.nbytes
                        # DeltaPath qualification: the host-visible route
                        # inputs besides D are my own out-link metrics (the
                        # nh_mask triangle w-column) and the transit mask —
                        # an event touching either cannot be described by
                        # changed D columns alone
                        delta_ok = not ov_changed and not np.any(
                            g.src[changed] == rows[0]
                        )
                    # the patch arrays are built: what follows uploads
                    # them and enqueues the program
                    self._pclock.enter("h2d")
                    args = (
                        jnp.asarray(rows, dtype=jnp.int32),
                        st["nbrs"],
                        st["wgs"],
                        st["ov"],
                        jnp.asarray(idx),
                        jnp.asarray(vals),
                    )
                    if warm:
                        fn = _sell_solver_warm(sell.shape_key(), self.mesh)
                        (
                            d,
                            new_wgs,
                            rounds,
                            inv_rounds,
                            col_changed,
                            num_changed,
                        ) = fn(*args, jnp.asarray(inc_idx), self._d_dev)
                        self._pclock.enter("relax")
                        st["wgs"] = new_wgs
                        self.incremental_solves += 1
                        self.invalidation_rounds_last = self._to_int(
                            inv_rounds
                        )
                        rounds = self._to_int(rounds)
                        self._finish_delta(
                            col_changed, num_changed, d, delta_ok
                        )
                        return d, rounds
                    if len(changed):
                        fn = _sell_solver_patched(sell.shape_key(), self.mesh)
                        d, new_wgs, rounds = fn(*args)
                        self._pclock.enter("relax")
                        st["wgs"] = new_wgs
                        self.full_solves += 1
                        return d, self._to_int(rounds)
                    # overload-only event with warm start unavailable:
                    # nothing to patch — plain cold solve below
                elif len(changed):
                    self._pclock.enter("h2d")
                    wgs = list(st["wgs"])
                    for k, sel in enumerate(per_bucket):
                        if len(sel):
                            wgs[k] = (
                                wgs[k]
                                .at[sell.edge_row[sel], sell.edge_slot[sel]]
                                .set(jnp.asarray(g.w[sel]))
                            )
                            # standalone scatters: row/slot + value uploads
                            self.h2d_bytes += 3 * 4 * len(sel)
                    st["wgs"] = tuple(wgs)

        fn = _sell_solver_counted(sell.shape_key(), self.mesh)
        self._pclock.enter("h2d")
        d, rounds = fn(
            jnp.asarray(rows, dtype=jnp.int32),
            st["nbrs"],
            st["wgs"],
            st["ov"],
        )
        self._pclock.enter("relax")
        self.full_solves += 1
        return d, self._to_int(rounds)

    def _bf_solve_resident(self, rows: np.ndarray):
        """Edge-list (non sliced-ELL) solve against persistent device
        buffers; returns (device distance matrix [s_pad, n_pad], rounds or
        None). The warm event path mirrors the sliced-ELL recipe with the
        layout's native patch unit — the whole [e_pad] weight vector —
        and derives the increased-edge set on device (ops.spf._bf_warm_core),
        so degree profiles that disqualify sliced-ELL no longer force a
        cold solve per event (and no longer silently mask the delta path)."""
        import jax.numpy as jnp

        from openr_tpu.ops.spf import _bf_fixpoint, _bf_solver_warm

        g = self.graph
        st = self._dev
        structural = (
            st is None or st.get("kind") != "bf" or st["src_ref"] is not g.src
        )
        if structural:
            self._pclock.enter("h2d")
            st = self._dev = {
                "kind": "bf",
                "src_ref": g.src,
                "src": self._replicated(g.src),
                "dst": self._replicated(g.dst),
                "w": self._replicated(g.w),
                "ov": self._replicated(g.overloaded),
                "w_host": g.w.copy(),
                "w_ver": g.version,
                "ov_host": g.overloaded.copy(),
                "rows": np.array(rows),
            }
            self.h2d_bytes += (
                g.src.nbytes + g.dst.nbytes + g.w.nbytes + g.overloaded.nbytes
            )
            self._mem_register(
                "bf",
                "bf",
                arrays=(st["src"], st["dst"], st["w"], st["ov"]),
            )
        else:
            ov_changed = not np.array_equal(st["ov_host"], g.overloaded)
            rows_same = np.array_equal(st["rows"], rows)
            st["rows"] = np.array(rows)
            if (
                g.changed_edges is not None
                and g.parent_version == st.get("w_ver")
            ):
                cand = g.changed_edges
                changed = cand[st["w_host"][cand] != g.w[cand]]
            else:
                changed = np.nonzero(st["w_host"][: g.e] != g.w[: g.e])[0]
            st["w_ver"] = g.version
            if ov_changed:
                st["ov"] = self._replicated(g.overloaded)
                st["ov_host"] = g.overloaded.copy()
                self.h2d_bytes += g.overloaded.nbytes
            if (
                self.warm_start
                and rows_same
                and not ov_changed
                and len(changed)
                and self._d_dev is not None
            ):
                # weight-only event: upload the new weight vector and let
                # the device classify increases against the resident copy
                delta_ok = not np.any(g.src[changed] == rows[0])
                self._pclock.enter("h2d")
                w_new = jnp.asarray(g.w)
                self.h2d_bytes += g.w.nbytes
                d, rounds, inv_rounds, col_changed, num_changed = (
                    _bf_solver_warm(
                        jnp.asarray(rows, dtype=jnp.int32),
                        st["src"],
                        st["dst"],
                        w_new,
                        st["w"],
                        st["ov"],
                        self._d_dev,
                    )
                )
                self._pclock.enter("relax")
                st["w"] = w_new
                st["w_host"] = g.w.copy()
                self.incremental_solves += 1
                self.invalidation_rounds_last = self._to_int(inv_rounds)
                rounds = self._to_int(rounds)
                self._finish_delta(col_changed, num_changed, d, delta_ok)
                return d, rounds
            if len(changed):
                st["w"] = self._replicated(g.w)
                st["w_host"] = g.w.copy()
                self.h2d_bytes += g.w.nbytes

        # this layout's cold program returns no round count: no host read
        # waits for it here, its time falls to whoever reads D first
        self._pclock.enter("h2d")
        d = _bf_fixpoint(
            jnp.asarray(rows, dtype=jnp.int32),
            st["src"],
            st["dst"],
            st["w"],
            st["ov"],
        )
        self.full_solves += 1
        return d, None

    def spf_result(self, source: str) -> _TpuSpfResult:
        """The view from one source of the batch, the same object while
        this solve stands."""
        result = self._spf_results.get(source)
        if result is None:
            result = self._spf_results[source] = _TpuSpfResult(self, source)
        return result

    def nh_up_links(self) -> List[Link]:
        """My ordered up-links into the batch: the rows of nh_mask."""
        me = self.me
        return [
            link
            for link in self.link_state.ordered_links_from_node(me)
            if link.is_up() and link.other_node_name(me) in self.row_map
        ]

    def _nh_link_arrays(self):
        """(names, batch rows [L], metrics [L], overloaded flags [L]) of
        my ordered up-links — the nh_mask triangle inputs, shared by the
        host mask build and the device delta extraction."""
        me, ls = self.me, self.link_state
        links = self.nh_up_links()
        names = [link.other_node_name(me) for link in links]
        return (
            names,
            [self.row_map[n] for n in names],
            [link.metric_from_node(me) for link in links],
            [ls.is_node_overloaded(n) for n in names],
        )

    def _drop_nh_mask(self) -> None:
        """The mask and what was read off it (rebuilt lazily)."""
        self._nh_links = None
        self._nh_mask = None
        self._nh_table: Optional[_NextHopTable] = None

    def next_hop_table(self) -> _NextHopTable:
        """The next-hop table over the current mask; built again where a
        link's next-hop address moved under it."""
        table = self._nh_table
        if (
            table is None
            or table.attr_version != self.link_state.link_attr_version
        ):
            table = self._nh_table = _NextHopTable(self)
        return table

    def _finish_delta(self, col_changed, num_changed, d_dev, delta_ok) -> None:
        """Complete a qualifying warm solve's DeltaPath extraction: read the
        changed-column count (4 bytes), size a compacted `_delta_extract`
        dispatch, and patch the persistent host mirrors (distance matrix +
        nexthop mask) in place. Sets self._last_solve_delta to the changed
        destination columns and self._last_route_delta to those among them
        whose route from here can have moved; leaving the first None makes
        _solve treat the event as full (mirrors reset, accumulated delta
        poisoned)."""
        if not delta_ok:
            return
        self._pclock.enter("delta_extract")
        num = self._to_int(num_changed)
        if num == 0:
            self._last_solve_delta = self._last_route_delta = np.empty(
                0, dtype=np.int64
            )
            return
        g = self.graph
        if num > max(_PATCH_SLOTS, int(g.n_pad * _DELTA_MAX_FRAC)):
            return  # full mirror is the cheaper copy-back for bulk events
        import jax.numpy as jnp

        from openr_tpu.ops.spf import _delta_extract

        names, rows_l, ws_l, ov_l = self._nh_link_arrays()
        l_pad = _next_bucket(max(len(rows_l), 1), minimum=8)
        nh_rows = np.zeros(l_pad, dtype=np.int32)
        nh_ws = np.full(l_pad, INF, dtype=np.int32)  # padding never matches
        nh_rows[: len(rows_l)] = rows_l
        nh_ws[: len(ws_l)] = ws_l
        cap = _next_bucket(num, minimum=8)
        t0 = time.perf_counter()
        self.h2d_bytes += nh_rows.nbytes + nh_ws.nbytes
        cols_d, dcols_d, nh_d = _delta_extract(
            col_changed, d_dev, jnp.asarray(nh_rows), jnp.asarray(nh_ws),
            cap=cap,
        )
        cols = self._to_host(cols_d)
        dcols = self._to_host(dcols_d)
        nh = self._to_host(nh_d)
        self.delta_extract_ms_last = (time.perf_counter() - t0) * 1e3
        self._pclock.enter("mirror_patch")
        xfer = cols.nbytes + dcols.nbytes + nh.nbytes + 4  # + count scalar
        self.d2h_bytes += xfer
        self.delta_bytes += xfer
        self.delta_columns += num
        self.delta_extracts += 1
        valid = cols < g.n_pad
        cols_real = cols[valid].astype(np.int64)
        # a route from here reads my own distance row and the first-hop
        # mask of its columns: a column where only other rows moved
        # (a neighbour's distance) routes as before. Where the old values
        # are not in hand, every changed column is a route column
        route_cols = cols_real
        own_old = None
        if self._d_host is not None:
            own_old = self._d_host[0, cols_real]
            self._d_host[:, cols_real] = dcols[:, valid]
        if self._nh_mask is not None and self._nh_links == names:
            mask_cols = nh[: len(names)][:, valid]
            for i, (nm, is_ov) in enumerate(zip(names, ov_l)):
                if is_ov:
                    # an overloaded neighbor relays nothing: valid only
                    # when it is itself the destination (nh_mask semantics)
                    mask_cols[i] &= cols_real == g.node_index[nm]
            if own_old is not None:
                moved = (own_old != dcols[0, valid]) | (
                    self._nh_mask[:, cols_real] != mask_cols
                ).any(axis=0)
                route_cols = cols_real[moved]
            self._nh_mask[:, cols_real] = mask_cols
        elif self._nh_mask is not None:
            self._drop_nh_mask()  # up-link set moved: rebuild lazily
        self.delta_route_columns += len(route_cols)
        self._last_solve_delta = cols_real
        self._last_route_delta = route_cols

    def take_route_delta(self, every_changed: bool = False) -> Optional[set]:
        """One-shot consumer handshake for the DeltaPath route build: the
        destination columns accumulated since the last take whose route
        from here can have moved (an empty set means solves ran but no
        such destination moved, or no solve ran), or None when any
        intervening solve could not produce a device delta — the caller
        must rebuild the full route db, which re-arms accumulation.
        `every_changed` asks for every column in which any row moved:
        what RFC 5286 alternates read, the neighbours' rows."""
        out = self._delta_pending if every_changed else self._route_pending
        self._delta_pending, self._route_pending = set(), set()
        return out

    def nh_mask(self) -> Tuple[List[str], np.ndarray]:
        """(neighbor names, [L, n_pad] bool): entry [i, t] is True iff the
        i-th up-link from me is an ECMP first hop toward node t.

        One vectorized triangle-condition broadcast over the solved rows
        (w(me,v) + D[v, t] == D[me, t], LinkState.cpp:855-871 semantics,
        with overloaded neighbors valid only as final destinations) replaces
        the per-destination link loop."""
        if self._nh_mask is None:
            names, rows, ws, ov = self._nh_link_arrays()
            if not names:
                self._nh_links = []
                self._nh_mask = np.zeros(
                    (0, self.graph.n_pad), dtype=bool
                )
                return self._nh_links, self._nh_mask
            w_col = np.asarray(ws, dtype=np.int32)[:, None]
            mask = (w_col + self.d[rows]) == self.d[0][None, :]
            # an overloaded neighbor relays nothing: valid only when it is
            # itself the destination
            for i, (n, is_ov) in enumerate(zip(names, ov)):
                if is_ov:
                    only = np.zeros(self.graph.n_pad, dtype=bool)
                    only[self.graph.node_index[n]] = True
                    mask[i] &= only
            self._nh_links = names
            self._nh_mask = mask
        return self._nh_links, self._nh_mask

    def refresh(self) -> None:
        """Re-solve against the current LinkState snapshot if it moved."""
        if self.graph.version == self.link_state.version:
            return
        self._solve(refresh=True)

    def cold_reference_d(self) -> np.ndarray:
        """Shadow cold solve from the HOST-side graph truth (the compiled
        arrays kept current by refresh_graph), independent of both the
        persistent device buffers and the resident distance state.

        This is the warm-state audit comparator: a diverged device-resident
        D (bit flip, donation bug, missed patch) differs from this
        recomputation, while an honest warm fixpoint is bit-identical to
        it. Runs off the hot path — no buffers are touched or reused."""
        rows = np.array(
            [self.graph.node_index[s] for s in self.sources], dtype=np.int32
        )
        s_pad = self._batch_pad(len(rows), minimum=8)
        rows = np.concatenate(
            [rows, np.full(s_pad - len(rows), rows[0], dtype=np.int32)]
        )
        cold = np.array(batched_spf(self.graph, rows))
        self.d2h_bytes += cold.nbytes  # audit copy-back, accounted too
        return cold

    # -- KSP (k-edge-disjoint shortest paths), device-batched ------------

    def kth_paths(self, dest: str, k: int) -> List[Path]:
        cached = self._ksp.get((dest, k))
        if cached is None:
            self.prefetch_ksp([dest], k)
            cached = self._ksp[(dest, k)]
        return cached

    def prefetch_ksp(self, dests: List[str], k: int) -> None:
        """Solve + trace the k-th path set for every dest in one device call.

        The reference runs one full penalized Dijkstra per destination
        (LinkState.cpp:777-780); here every destination's penalized solve is
        one batch row of a single per-row-weights fixpoint.
        """
        assert k >= 1
        idx = self.graph.node_index
        todo = [
            d
            for d in dests
            if (d, k) not in self._ksp and d != self.me and d in idx
        ]
        for d in dests:
            if (d, k) not in self._ksp and (d == self.me or d not in idx):
                self._ksp[(d, k)] = []
        if not todo:
            return
        if k == 1:
            # base solve row 0 is me with the unpenalized weights
            for dest in todo:
                self._ksp[(dest, 1)] = _trace_paths(
                    self.link_state, self.graph, self.d[0], self.me, dest, set()
                )
            return
        self.prefetch_ksp(todo, k - 1)

        # per-dest ignore set = links used by path sets 1..k-1
        ignores: List[Set[Link]] = []
        for dest in todo:
            ig: Set[Link] = set()
            for i in range(1, k):
                for path in self._ksp[(dest, i)]:
                    ig.update(path)
            ignores.append(ig)

        # pad the batch axis to a power-of-two bucket so every anycast group
        # size in a bucket shares one jitted executable (same convention as
        # n_pad/e_pad in compile_graph); filler rows re-solve unpenalized
        s_pad = self._batch_pad(len(todo), minimum=1)
        me_row = idx[self.me]
        sources = np.full(s_pad, me_row, dtype=np.int32)
        # warm layer seeding (docs/Apsp.md): the penalized layer-k problem
        # is the base problem plus weight INCREASES (ignored links -> INF),
        # so every batch row warm-starts from the resident base row of me —
        # the same row the all-pairs matrix serves — via the standard
        # increase-invalidation instead of cold-starting from INF. The
        # tiled 2-D layout keeps a different buffer set and the mesh vw
        # solvers shard d0 differently, so both keep the cold path.
        warm_prev = None
        if (
            self.warm_start
            and self.mesh is None
            and self._d_dev is not None
            and self._dev is not None
            and self._dev.get("kind") in ("sell", "bf")
        ):
            import jax.numpy as jnp

            base_row = self._d_dev[0]  # rows[0] is me's unpenalized row
            warm_prev = jnp.broadcast_to(
                base_row[None, :], (s_pad, base_row.shape[0])
            )
        if self.graph.sell is not None:
            # sliced layout: per-row ignores become device-side INF masks —
            # no [S, E] host tile, no bulk upload
            mask_positions: List[List[int]] = []
            for ig in ignores:
                pos: List[int] = []
                for link in ig:
                    fwd, rev = self.graph.link_edges[link]
                    pos.extend((fwd, rev))
                mask_positions.append(pos)
            mask_positions.extend([[] for _ in range(s_pad - len(todo))])
            # persistent buffers, synced by _solve() — only the sliced-ELL
            # resident state carries them (the tiled 2-D layout keeps a
            # different buffer set; KSP re-uploads the sell layout there)
            dev = self._dev
            if dev is not None and dev.get("kind") != "sell":
                dev = None
            d_rows = np.asarray(
                sell_fixpoint_masked(
                    self.graph.sell,
                    sources,
                    self.graph.overloaded,
                    mask_positions,
                    device_arrays=(
                        (dev["nbrs"], dev["wgs"], dev["ov"])
                        if dev is not None and dev.get("kind") == "sell"
                        else None
                    ),
                    mesh=self.mesh,
                    d_prev=(
                        warm_prev
                        if dev is not None and dev.get("kind") == "sell"
                        else None
                    ),
                )
            )
            if (
                warm_prev is not None
                and dev is not None
                and dev.get("kind") == "sell"
            ):
                self.ksp_warm_batches += 1
        elif warm_prev is not None and self._dev.get("kind") == "bf":
            from openr_tpu.ops.spf import _bf_solver_warm_vw

            import jax.numpy as jnp

            w_rows = np.tile(self.graph.w, (s_pad, 1))
            for row, ig in enumerate(ignores):
                for link in ig:
                    fwd, rev = self.graph.link_edges[link]
                    w_rows[row, fwd] = INF
                    w_rows[row, rev] = INF
            st = self._dev
            self._mem_register("ksp", "vw", arrays=(w_rows,))
            fault_point("ops.spf.batched_spf_vw", self.graph)
            d_dev, _rounds, _inv = _bf_solver_warm_vw(
                jnp.asarray(sources, dtype=jnp.int32),
                st["src"],
                st["dst"],
                jnp.asarray(w_rows, dtype=jnp.int32),
                st["w"],
                st["ov"],
                warm_prev,
            )
            d_rows = np.asarray(d_dev)
            self.h2d_bytes += w_rows.nbytes
            self.ksp_warm_batches += 1
        else:
            w_rows = np.tile(self.graph.w, (s_pad, 1))
            for row, ig in enumerate(ignores):
                for link in ig:
                    fwd, rev = self.graph.link_edges[link]
                    w_rows[row, fwd] = INF
                    w_rows[row, rev] = INF
            self._mem_register("ksp", "vw", arrays=(w_rows,))
            d_rows = np.asarray(
                batched_spf_vw(self.graph, sources, w_rows, mesh=self.mesh)
            )
            self.h2d_bytes += w_rows.nbytes
        # the penalized distance rows are consumed host-side by the greedy
        # back-trace — a real copy-back, so it rides the transfer counters
        # like the mirror fetch does; the per-row-weights layer upload is
        # transient, so its ledger entry releases with the batch
        self.d2h_bytes += d_rows.nbytes
        self._mem_release("ksp")
        self.ksp_device_batches += 1

        for row, (dest, ig) in enumerate(zip(todo, ignores)):
            self._ksp[(dest, k)] = _trace_paths(
                self.link_state, self.graph, d_rows[row], self.me, dest, ig
            )


def _trace_paths(
    link_state: LinkState,
    graph: CompiledGraph,
    d_row: np.ndarray,
    src: str,
    dest: str,
    ignore: Set[Link],
) -> List[Path]:
    """Greedy edge-disjoint path enumeration from a single-source distance
    row, byte-for-byte equivalent to tracing the Dijkstra SPF DAG
    (LinkState.cpp:398-419): path links into v are the up, non-ignored links
    from nodes u with d(u) + w(u→v) == d(v) that offer transit, ordered by
    u's settle order (= (d(u), u), valid since metrics ≥ 1) then by u's
    sorted link order."""
    idx = graph.node_index
    dd = d_row.tolist()
    dcol = idx.get(dest)
    if dcol is None or dd[dcol] >= INF:
        return []

    path_links: Dict[str, List[Tuple[Link, str]]] = {}

    def pl(v: str) -> List[Tuple[Link, str]]:
        cached = path_links.get(v)
        if cached is not None:
            return cached
        vi = idx[v]
        out: List[Tuple[Link, str]] = []
        for link in link_state.ordered_links_from_node(v):
            if not link.is_up() or link in ignore:
                continue
            u = link.other_node_name(v)
            ui = idx.get(u)
            if ui is None or dd[ui] >= INF:
                continue
            if u != src and link_state.is_node_overloaded(u):
                continue
            if dd[ui] + link.metric_from_node(u) == dd[vi]:
                out.append((link, u))
        out.sort(key=lambda t: (dd[idx[t[1]]], t[1], t[0]))
        path_links[v] = out
        return out

    visited: Set[Link] = set()

    def trace_one(node: str) -> Optional[Path]:
        if node == src:
            return []
        for link, prev in pl(node):
            if link not in visited:
                visited.add(link)
                sub = trace_one(prev)
                if sub is not None:
                    sub.append(link)
                    return sub
        return None

    paths: List[Path] = []
    path = trace_one(dest)
    while path:
        paths.append(path)
        path = trace_one(dest)
    return paths


class TpuSpfSolver(SpfSolver):
    """SpfSolver with the batched TPU distance backend.

    mesh: None (single device), a jax.sharding.Mesh, or a (batch, graph)
    shape tuple resolved against jax.devices() on first use — the
    DecisionConfig.solver_mesh production knob. Sharding rides entirely
    inside _AreaSolve (sources row-sharded, layout replicated), so the
    meshed solver passes the same parity suite as the single-device one.
    """

    def __init__(
        self,
        *args,
        mesh=None,
        warm_start: bool = True,
        apsp_max_nodes: int = 0,
        apsp_audit_interval: int = 0,
        **kwargs,
    ) -> None:
        super().__init__(*args, **kwargs)
        # (area name, node) -> (LinkState identity, solve); keyed by the
        # stable area name so a replaced LinkState object for the same area
        # overwrites its predecessor instead of leaking it; topology-version
        # tracking lives in _AreaSolve.refresh()
        self._solves: Dict[Tuple[str, str], Tuple[int, _AreaSolve]] = {}
        # area name -> (LinkState, its version, what _area_solve answered
        # for my node then): the reads of a route build look an area's
        # solve up once, and a LinkState that moved is asked about again
        self._resolved: Dict[
            str, Tuple[LinkState, int, Optional[_AreaSolve]]
        ] = {}
        # routes since the last sync_counters whose next hops came from a
        # solve's next-hop table / from the generic stack
        self._table_routes = 0
        self._generic_routes = 0
        # questions a build asked a next-hop table since then: one route's
        # (`next_hops`) or all the plain routes' at once (`read_unicast`)
        self._table_reads = 0
        # next-hop sets of the table's label routes that a reader made
        # (routes.LabelNextHops.make): every such route carries this tally
        self._label_sets_made = [0]
        # bumped by 0: the counters exist from the start
        self._bump("decision.route_build_table_routes", 0)
        self._bump("decision.route_build_table_reads", 0)
        self._bump("decision.route_build_generic_routes", 0)
        self._bump("decision.route_build_label_sets_made", 0)
        self.device_solves = 0  # counter: batched device calls
        # device-memory observatory: the process-global ledger plus the
        # compile caches as an informational external source; headroom-
        # gated admission refusals queue here until the supervisor drains
        # them into SOLVER_CAPACITY_REFUSED samples
        self._ledger = get_ledger()
        self._ledger.attach_external("compile_cache", compile_cache_memory)
        self._capacity_refusals: List[Dict] = []
        self.warm_start = warm_start
        # resident APSP matrix knobs (docs/Apsp.md): areas up to this many
        # real nodes keep a blocked-FW all-pairs matrix on device; 0 = off
        self.apsp_max_nodes = apsp_max_nodes
        self.apsp_audit_interval = apsp_audit_interval
        # set by SolverSupervisor.attach_supervisor: APSP closes dispatch
        # through its fault domain (classified errors feed the shared
        # breaker, numpy FW serves as the degraded path)
        self._supervisor = None
        # flight recorder (solver/flight_recorder.py), attached by the
        # supervisor before the first solve; every _AreaSolve records its
        # SolveTraces into it and the phase histograms drain through
        # _sync_spf_counters
        self._recorder = None
        # last-solve timing gauges surfaced by getSolverHealth next to
        # solve_ms_last (docs/Robustness.md observability surface)
        self.solve_ms_last: Optional[float] = None
        self.delta_extract_ms_last: Optional[float] = None
        self.apsp_close_ms_last: Optional[float] = None
        # resolved EAGERLY: a solver_mesh that doesn't fit the device set
        # must fail at daemon startup with a clear error, not inside the
        # first debounced rebuild callback mid-convergence
        if mesh is not None:
            from openr_tpu.parallel import resolve_mesh

            mesh = resolve_mesh(mesh)
        self.mesh = mesh
        self._ledger.set_devices(
            mesh.devices.flat if mesh is not None else None
        )

    def attach_supervisor(self, supervisor) -> None:
        """Wire the solver fault domain into non-solve device workloads
        owned by this backend (the APSP closes). Called by
        SolverSupervisor.__init__."""
        self._supervisor = supervisor

    def attach_recorder(self, recorder) -> None:
        """Wire the solver flight recorder (solver/flight_recorder.py)
        into every area solve. Called by SolverSupervisor.__init__ before
        the first solve; cached solves created earlier (none in the
        supervised construction order) keep recording disabled."""
        self._recorder = recorder

    def _area_solve(
        self, link_state: LinkState, node: str
    ) -> Optional[_AreaSolve]:
        """The cached device solve for this area, or None when the node is
        not present in this area's graph (multi-area: fall back to CPU)."""
        if not link_state.has_node(node) and not link_state.links_from_node(
            node
        ):
            return None
        key = (link_state.area, node)
        cached = self._solves.get(key)
        if cached is not None and cached[0] == id(link_state):
            solve = cached[1]
            before = solve.device_solves
            solve.refresh()  # incremental: patch arrays + one device call
            if solve.device_solves != before:
                # the LinkState had moved and a solve ran: a distance
                # read on a current solve folds nothing (the route build
                # that reads ends in sync_counters)
                self.device_solves += solve.device_solves - before
                self._sync_spf_counters(solve)
            return solve
        if cached is not None:
            # a replaced LinkState for the same area: release the stale
            # solve's device buffers from the ledger before the rebuild
            cached[1].close()
        solve = _AreaSolve(
            link_state,
            node,
            mesh=self.mesh,
            warm_start=self.warm_start,
            apsp_max_nodes=self.apsp_max_nodes,
            apsp_audit_interval=self.apsp_audit_interval,
            # supervised: APSP closes run in the solver fault domain
            # (classified faults feed the shared breaker). Bare: ApspState
            # serves its numpy fallback itself, logged and counted
            # (decision.spf.apsp_fallback_closes)
            apsp_dispatch=(
                self._supervisor.supervised_call
                if self._supervisor is not None
                else None
            ),
            recorder=self._recorder,
            on_capacity_refusal=self._note_capacity_refusal,
        )
        self.device_solves += solve.device_solves
        self._sync_spf_counters(solve)
        self._solves[key] = (id(link_state), solve)
        return solve

    def _my_solve(self, link_state: LinkState) -> Optional[_AreaSolve]:
        """`_area_solve` for my node, asked once per LinkState version:
        every read of a route build comes through here."""
        memo = self._resolved.get(link_state.area)
        if (
            memo is not None
            and memo[0] is link_state
            and memo[1] == link_state.version
        ):
            return memo[2]
        solve = self._area_solve(link_state, self.my_node_name)
        self._resolved[link_state.area] = (
            link_state,
            link_state.version,
            solve,
        )
        return solve

    def _note_capacity_refusal(self, verdict: Dict) -> None:
        """Queue a headroom-gated admission refusal for the supervisor to
        drain into a SOLVER_CAPACITY_REFUSED LogSample; also kept as the
        last_capacity_refusal gauge row in getSolverHealth."""
        self._capacity_refusals.append(dict(verdict))

    def take_capacity_refusals(self) -> List[Dict]:
        """Drain queued capacity refusals (supervisor sample emission)."""
        out, self._capacity_refusals = self._capacity_refusals, []
        return out

    def _sync_spf_counters(self, solve: _AreaSolve) -> None:
        """Fold an _AreaSolve's convergence + profiling stats into the
        decision.spf.* counters/histograms (merged into Decision's dicts
        for the monitor/ctrl API): incremental vs full solves and transfer
        bytes are monotonic, rounds/invalidation-rounds are gauges of the
        most recent solve, solve wall time lands in the warm/cold-split
        latency histograms. Runs after a solve and at the end of a poll
        and of a route build (`sync_counters`), never per distance read:
        decision.spf.counter_syncs counts the runs."""
        self._bump("decision.spf.counter_syncs")
        d_inc = solve.incremental_solves - solve._inc_synced
        d_full = solve.full_solves - solve._full_synced
        solve._inc_synced = solve.incremental_solves
        solve._full_synced = solve.full_solves
        counters = self._ensure_counters()
        if d_inc:
            self._bump("decision.spf.incremental_solves", d_inc)
        if d_full:
            self._bump("decision.spf.full_solves", d_full)
        # rows solved for, and the bucket-padded height of the resident
        # [s_pad, n_pad] matrix that was computed for them
        counters["decision.spf.rows_last"] = len(solve.sources)
        counters["decision.spf.rows_padded_last"] = solve._d_dev.shape[0]
        # the solved graph's padded width and its sliced-ELL layout: degree
        # classes after merging (0 on the edge-list form) and the padded
        # slots a sweep reads, the directed edges and the layout's waste
        sell = solve.graph.sell
        counters["decision.spf.nodes_padded_last"] = solve.graph.n_pad
        counters["decision.spf.sell_classes_last"] = len(sell.nbr) if sell else 0
        counters["decision.spf.sell_slots_last"] = (
            sum(a.size for a in sell.nbr) if sell else 0
        )
        if solve.rounds_last is not None:
            counters["decision.spf.rounds_last"] = solve.rounds_last
        if solve.invalidation_rounds_last is not None:
            counters["decision.spf.invalidation_rounds_last"] = (
                solve.invalidation_rounds_last
            )
        if (d_inc or d_full) and solve.solve_ms_last is not None:
            self.solve_ms_last = solve.solve_ms_last
            self._observe("decision.spf.solve_ms", solve.solve_ms_last)
            self._observe(
                "decision.spf.solve_warm_ms"
                if solve.last_solve_warm
                else "decision.spf.solve_cold_ms",
                solve.solve_ms_last,
            )
        # transfer-byte deltas since the last sync (the lazy d mirror fetch
        # happens after the solve's own sync, where the poll or the route
        # pipeline first reads solve.d: its bytes land with the sync that
        # ends that poll or that route build)
        d_h2d = solve.h2d_bytes - solve._h2d_synced
        if d_h2d:
            solve._h2d_synced = solve.h2d_bytes
            self._bump("decision.spf.host_to_device_bytes", d_h2d)
        d_d2h = solve.d2h_bytes - solve._d2h_synced
        if d_d2h:
            solve._d2h_synced = solve.d2h_bytes
            self._bump("decision.spf.device_to_host_bytes", d_d2h)
        d_syncs = solve.device_syncs - solve._device_syncs_synced
        if d_syncs:
            solve._device_syncs_synced = solve.device_syncs
            self._bump("decision.spf.device_syncs", d_syncs)
        # bumped also by 0, so that the counters exist from the first sync
        self._bump(
            "decision.spf.graph_recompiles",
            solve.graph_recompiles - solve._graph_recompiles_synced,
        )
        solve._graph_recompiles_synced = solve.graph_recompiles
        self._bump(
            "decision.spf.graph_links_patched",
            solve.graph_links_patched - solve._graph_links_patched_synced,
        )
        solve._graph_links_patched_synced = solve.graph_links_patched
        # DeltaPath extraction stats (docs/Monitoring.md): changed columns
        # and O(changes) copy-back bytes per warm event
        d_cols = solve.delta_columns - solve._delta_cols_synced
        if d_cols:
            solve._delta_cols_synced = solve.delta_columns
            self._bump("decision.spf.delta_columns", d_cols)
        # of those, the columns a route from here reads: bumped also by 0,
        # so that the counter exists from the first sync
        self._bump(
            "decision.spf.delta_route_columns",
            solve.delta_route_columns - solve._delta_route_cols_synced,
        )
        solve._delta_route_cols_synced = solve.delta_route_columns
        d_bytes = solve.delta_bytes - solve._delta_bytes_synced
        if d_bytes:
            solve._delta_bytes_synced = solve.delta_bytes
            self._bump("decision.spf.delta_bytes", d_bytes)
        # halo-exchange traffic of the destination-tiled layout: ring
        # rotations of the last solve (gauge) + cumulative frontier bytes
        d_halo = solve.halo_bytes - solve._halo_synced
        if d_halo:
            solve._halo_synced = solve.halo_bytes
            self._bump("decision.spf.halo_bytes", d_halo)
        if solve.halo_exchanges_last is not None:
            counters["decision.spf.halo_exchanges_last"] = (
                solve.halo_exchanges_last
            )
        if (
            solve.delta_extracts > solve._delta_extracts_synced
            and solve.delta_extract_ms_last is not None
        ):
            solve._delta_extracts_synced = solve.delta_extracts
            self.delta_extract_ms_last = solve.delta_extract_ms_last
            self._observe(
                "decision.spf.delta_extract_ms", solve.delta_extract_ms_last
            )
        # flight-recorder drain: the solves' phase observations land in the
        # decision.spf.phase.*_ms histograms (the names are literals in
        # flight_recorder.PHASE_HISTOGRAMS, pinned to the docs table by
        # registry-drift), and the ring/eviction accounting rides the
        # counter registry as absolute totals
        rec = self._recorder
        if rec is not None:
            for hist_name, value in rec.drain_observations():
                self._observe(hist_name, value)
            counters["decision.spf.traces_recorded"] = rec.recorded
            counters["decision.spf.traces_evicted"] = rec.evicted
        self._sync_apsp_counters(solve)
        from openr_tpu.apsp import apsp_compile_cache_stats
        from openr_tpu.ops.spf import compile_cache_stats

        stats = compile_cache_stats()
        fw_stats = apsp_compile_cache_stats()
        counters["decision.spf.compile_cache_hits"] = (
            stats["hits"] + fw_stats["hits"]
        )
        counters["decision.spf.compile_cache_misses"] = (
            stats["misses"] + fw_stats["misses"]
        )
        # device-memory observatory: fold the ledger's counters + gauges
        # (decision.mem.*) in on the same sync cadence as the transfer
        # bytes they complement
        self._ledger.fold_counters(counters)

    def _sync_apsp_counters(self, solve: _AreaSolve) -> None:
        """Fold the solve's APSP + KSP-warm stats into the decision.spf.*
        registry (docs/Apsp.md counter rows): close counts split
        warm/cold/fallback, staleness invalidations, shadow-audit runs,
        the re-close round gauge, transfer bytes, and the close-latency
        histogram — same monotonic-delta discipline as the batch stats."""
        counters = self._ensure_counters()
        d_ksp = solve.ksp_warm_batches - solve._ksp_warm_synced
        if d_ksp:
            solve._ksp_warm_synced = solve.ksp_warm_batches
            self._bump("decision.spf.ksp_warm_batches", d_ksp)
        apsp = solve.apsp
        if apsp is None:
            return
        if apsp.close_ms_last is not None:
            self.apsp_close_ms_last = apsp.close_ms_last
        d_closes = apsp.closes - apsp._closes_synced
        if d_closes:
            apsp._closes_synced = apsp.closes
            self._bump("decision.spf.apsp_closes", d_closes)
            if apsp.close_ms_last is not None:
                self._observe(
                    "decision.spf.apsp_close_ms", apsp.close_ms_last
                )
        for attr, name in (
            ("warm_closes", "decision.spf.apsp_warm_closes"),
            ("cold_closes", "decision.spf.apsp_cold_closes"),
            ("fallback_closes", "decision.spf.apsp_fallback_closes"),
            ("invalidations", "decision.spf.apsp_invalidations"),
            ("audit_runs", "decision.spf.apsp_audit_runs"),
            ("audit_mismatches", "decision.spf.apsp_audit_mismatches"),
            ("h2d_bytes", "decision.spf.apsp_h2d_bytes"),
            ("d2h_bytes", "decision.spf.apsp_d2h_bytes"),
        ):
            value = getattr(apsp, attr)
            synced = apsp._sync_marks.get(attr, 0)
            if value > synced:
                apsp._sync_marks[attr] = value
                self._bump(name, value - synced)
        if apsp.reclose_rounds_last is not None:
            counters["decision.spf.apsp_reclose_rounds_last"] = (
                apsp.reclose_rounds_last
            )

    # -- DeltaPath (device-side route-delta extraction) ------------------

    def poll_device_delta(
        self, area_link_states: Dict[str, LinkState]
    ) -> Optional[Set[str]]:
        """Refresh every area's device solve against the current LSDB and
        return the union of the destination NODE NAMES whose route from
        here can have moved, where my own distance or my first hops
        toward them moved — iff every area event since the last poll rode
        the device delta-extraction path. None means some event had no
        device delta (cold solve, overload change, flap incident to me,
        bulk event): the caller must rebuild the full route db, which
        re-arms delta accumulation.

        Areas where this node is absent contribute no routes (the pipeline
        sees an empty SPF there) and are skipped.

        Under `compute_lfa_paths` one extra column is load-bearing: the
        RFC 5286 inequality dist(neighbor, dst) < shortest + dist(neighbor,
        me) reads the ME column from every alt-neighbor row, so a delta
        whose changed set contains me would leave every OTHER prefix's LFA
        threshold stale — that event class is answered with None (full
        rebuild) — and the alternates read the neighbours' rows, so the
        answer holds every changed destination, not only the route
        columns. Every other LFA input is a changed-announcer column the
        delta already names (docs/Apsp.md "DeltaPath under LFA")."""
        me = self.my_node_name
        changed: Set[str] = set()
        ok = True
        for link_state in area_link_states.values():
            solve = self._my_solve(link_state)
            if solve is None:
                continue
            cols = solve.take_route_delta(self.compute_lfa_paths)
            if cols is None:
                ok = False  # keep draining the other areas' pending state
                # the full build that must follow reads the whole mirror:
                # fetched here, under its own phase, so that the build's
                # stage (decision.full_build) holds host work alone
                _ = solve.d
                continue
            names = solve.graph.names
            changed.update(names[c] for c in cols if c < len(names))
        self.sync_counters(area_link_states)
        if ok and self.compute_lfa_paths and me in changed:
            return None
        return changed if ok else None

    def sync_counters(self, area_link_states: Dict[str, LinkState]) -> None:
        """One counter sync per area's resident solve: what the reads
        since the solve's own sync left behind (the lazy mirror fetch's
        bytes, device sync and d2h phase, KSP and APSP work, the ledger's
        gauges), the build's routes by where their next hops came from,
        and the label routes' next-hop sets that were read since. Ends
        every poll and every route build."""
        for link_state in area_link_states.values():
            cached = self._solves.get((link_state.area, self.my_node_name))
            if cached is not None and cached[0] == id(link_state):
                self._sync_spf_counters(cached[1])
        self._bump("decision.route_build_table_routes", self._table_routes)
        self._bump("decision.route_build_table_reads", self._table_reads)
        self._bump(
            "decision.route_build_generic_routes", self._generic_routes
        )
        self._table_routes = self._table_reads = self._generic_routes = 0
        # a reader downstream of the build (Fib, ctrl) shows at the next sync
        self.counters["decision.route_build_label_sets_made"] = (
            self._label_sets_made[0]
        )

    def build_route_db(self, my_node_name, area_link_states, prefix_state):
        db = super().build_route_db(
            my_node_name, area_link_states, prefix_state
        )
        self.sync_counters(area_link_states)
        return db

    def lfa_delta_ready(self) -> bool:
        """DeltaPath-under-LFA capability gate (solver/delta.py): True when
        every resident area solve carries an APSP-capable state — the
        LFA-era delta build leans on the me-column poison test in
        poll_device_delta plus alt-neighbor rows served from the resident
        matrices; areas past the node cap fall back to the pre-APSP
        force-full behavior."""
        if self.apsp_max_nodes <= 0 or not self._solves:
            return False
        return all(
            solve.apsp is not None and solve.apsp.enabled_for(solve.graph)
            for _, solve in self._solves.values()
        )

    def borrow_apsp(self, area: str, version: int) -> Optional[np.ndarray]:
        """TE hard-scoring borrow (te/service.py): the exact [n, n]
        distance matrix for this area's CURRENT weights, or None when no
        fresh matrix can serve — wrong snapshot version, APSP off or the
        area over the node cap, or drained nodes present (TE excludes
        drained transit by pinning out-edges, which diverges from the
        per-source transit masks a drained topology closes under)."""
        cached = self._solves.get((area, self.my_node_name))
        if cached is None:
            return None
        solve = cached[1]
        g = solve.graph
        if g.version != version or np.any(g.overloaded[: g.n]):
            return None
        if not solve.ensure_apsp():
            return None
        return solve.apsp.d[: g.n, : g.n]

    # -- fault domain (SolverSupervisor seams) ---------------------------

    def degrade_mesh(self) -> bool:
        """Partial-mesh degradation: re-resolve the solver mesh over the
        surviving chips — the largest strictly-smaller (batch, graph)
        factorization that still answers probes — instead of tripping all
        the way to the CPU oracle on a single-chip loss. Returns whether a
        smaller mesh was installed; False means no viable mesh remains
        (single-device mesh, or no mesh at all) and the caller should trip.

        Warm state cannot be re-tiled across mesh shapes (tile ownership
        and frontier slots are functions of the factorization), so every
        cached solve is dropped and the next event cold-starts on the new
        mesh — re-tiled-or-cold, never silently wrong (docs/Decision.md)."""
        if self.mesh is None:
            return False
        from openr_tpu.parallel import plan_degraded_mesh

        new_mesh = plan_degraded_mesh(self.mesh)
        if new_mesh is None:
            return False
        self.mesh = new_mesh
        self._ledger.set_devices(new_mesh.devices.flat)
        self._close_solves()
        counters = self._ensure_counters()
        self._bump("decision.spf.mesh_degradations")
        counters["decision.spf.mesh_devices"] = int(new_mesh.devices.size)
        return True

    def invalidate_warm_state(self) -> None:
        """Drop every cached device solve: the next build_route_db
        recompiles the graph and solves cold. The supervisor calls this on
        breaker trips and audit mismatches — after a device fault or a
        detected divergence the resident buffers are not to be trusted."""
        self._close_solves()
        self._bump("decision.spf.warm_state_invalidations")

    def close(self) -> None:
        """Solver teardown (daemon stop): release every device-resident
        structure this solver registered with the memory ledger. Entries
        pinned by `solver.mem.retain` survive by design — that is the
        leak the observatory exists to show."""
        self._close_solves()

    def _close_solves(self) -> None:
        """Drop every cached device solve, releasing each one's ledger-
        registered buffers first — teardown must return the ledger to its
        pre-area baseline (the leak-regression contract)."""
        for _, solve in self._solves.values():
            solve.close()
        self._solves.clear()
        self._resolved.clear()
        self._ledger.fold_counters(self._ensure_counters())

    def audit_warm_state(self) -> List[dict]:
        """Shadow cold-audit of every resident warm solve: recompute each
        area's distance matrix from host-side truth and compare entrywise
        against the warm device-resident D. Returns one record per
        diverged area (empty list = all clean)."""
        mismatches: List[dict] = []
        for (area, node), (_, solve) in self._solves.items():
            cold = solve.cold_reference_d()
            warm = solve.d
            if warm.shape == cold.shape and np.array_equal(warm, cold):
                continue
            if warm.shape != cold.shape:
                entries = -1
                max_abs = -1
            else:
                diff = warm != cold
                entries = int(diff.sum())
                max_abs = int(
                    np.abs(
                        warm.astype(np.int64) - cold.astype(np.int64)
                    ).max()
                )
            mismatches.append(
                {
                    "area": area,
                    "node": node,
                    "entries": entries,
                    "max_abs_delta": max_abs,
                }
            )
        return mismatches

    # -- SPF access seam -------------------------------------------------

    def _spf(self, link_state: LinkState, node: str):
        solve = self._my_solve(link_state)
        if solve is not None and node in solve.row_map:
            return solve.spf_result(node)
        # source outside the solved batch (not me / my neighbor): the
        # resident all-pairs matrix serves its whole row — LFA-style
        # qualification from an arbitrary perspective reads alt-neighbor
        # rows from ApspState instead of a per-source Dijkstra column
        # solve (docs/Apsp.md)
        if (
            solve is not None
            and node in solve.graph.node_index
            and solve.ensure_apsp()
        ):
            return _ApspSpfResult(solve, node)
        # area this node does not participate in: CPU oracle fallback
        return link_state.get_spf_result(node)

    def _dist(self, link_state: LinkState, a: str, b: str) -> Optional[Metric]:
        if a == b:
            return 0
        solve = self._my_solve(link_state)
        if solve is not None:
            row = solve.row_map.get(a)
            col = solve.graph.node_index.get(b)
            if row is not None and col is not None:
                metric = int(solve.d[row, col])
                return metric if metric < INF else None
            if (
                col is not None
                and a in solve.graph.node_index
                and solve.ensure_apsp()
            ):
                metric = int(
                    solve.apsp.d[solve.graph.node_index[a], col]
                )
                return metric if metric < INF else None
        return link_state.get_metric_from_a_to_b(a, b)

    def build_unicast_routes(
        self,
        unicast_entries: Dict[IpPrefix, RibUnicastEntry],
        my_node_name: str,
        prefixes: Iterable[Tuple[IpPrefix, Dict[str, Dict[str, PrefixEntry]]]],
        area_link_states: Dict[str, LinkState],
        prefix_state: PrefixState,
    ) -> None:
        """The build's plain prefixes answered in one read of the next-hop
        table; the rest one by one, through `build_unicast_route`.

        Once a build: the one area that holds my node, its table, my
        distance row, who is drained. A prefix is plain where every
        advertisement is non-BGP, SP_ECMP and IP-forwarded and lies in
        that area: what takes `next_hops_toward` to the table. Their
        announcers become columns; reachability, the drained announcers'
        filter (`_maybe_filter_drained_nodes`' rule) and the nearest
        announcers (`get_min_cost_nodes`' rule) are array work over all
        of them, and `_NextHopTable.read_unicast` reads each nearest
        column's first hops once and makes a next-hop set once a (group,
        metric, family). Once a route: its entry, with a set of its own
        (RibPolicy rewrites an entry's next hops in place, and no
        sibling's may change with it). Entries, their order in
        `unicast_entries` and the counters are `build_unicast_route`'s.
        With LFA on, for another node's view, or where not exactly one
        area holds my node, every prefix goes one by one."""
        mine = None
        if not self.compute_lfa_paths and my_node_name == self.my_node_name:
            mine = self._my_one_area(area_link_states)
        if mine is None:
            return super().build_unicast_routes(
                unicast_entries,
                my_node_name,
                prefixes,
                area_link_states,
                prefix_state,
            )
        area, solve = mine
        index_of = solve.graph.node_index.get
        bgp = PrefixType.BGP
        ip = PrefixForwardingType.IP
        sp_ecmp = PrefixForwardingAlgorithm.SP_ECMP
        v4_enabled = self.enable_v4
        # in the order given, side by side: the prefix; its
        # advertisements; how many announcers it asks the table about,
        # else what became of it (_NO_ROUTE, _ONE_BY_ONE); a lone
        # announcer's advertisement. And the columns of all the
        # announcers asked about
        batch: List[IpPrefix] = []
        adverts: List[Dict[str, Dict[str, PrefixEntry]]] = []
        asks: List[int] = []
        lone: List[Optional[PrefixEntry]] = []
        cols: List[int] = []
        v4_disabled = 0
        for prefix, prefix_entries in prefixes:
            if not prefix_entries:
                continue
            batch.append(prefix)
            adverts.append(prefix_entries)
            mark = len(cols)
            for node, areas in prefix_entries.items():
                entry = areas.get(area)
                if (
                    entry is None
                    or len(areas) != 1
                    or entry.type == bgp
                    or entry.forwarding_type != ip
                    or entry.forwarding_algorithm != sp_ecmp
                ):
                    asked = _ONE_BY_ONE
                    break
                cols.append(index_of(node, -1))
            else:
                asked = len(cols) - mark
                if my_node_name in prefix_entries:
                    asked = _NO_ROUTE  # mine: no route needed
                elif not v4_enabled and prefix.is_v4:
                    v4_disabled += 1
                    asked = _NO_ROUTE
            if asked < 0:
                del cols[mark:]
            asks.append(asked)
            lone.append(entry if asked == 1 else None)
        if v4_disabled:
            self._bump("decision.skipped_unicast_route", v4_disabled)

        # all the announcers asked about, as arrays: reachable, healthy,
        # nearest; then the table's one read
        count = np.maximum(asks, 0)
        # a prefix asked about has no route until the table gives it one
        fate = np.minimum(asks, _NO_ROUTE)
        col = np.asarray(cols, dtype=np.intp)
        reach = np.zeros(0, dtype=bool)
        sets: List[FrozenSet[NextHop]] = []
        if len(col):
            item = np.repeat(np.arange(len(count)), count)
            # a node the graph does not hold reads the last column: masked
            dist = np.where(col >= 0, solve.d[0][col], INF)
            reach = dist < INF
            near = _nearest_announcers(
                item,
                dist,
                reach,
                self._drained_announcers(
                    area, area_link_states, solve, adverts, count, col
                ),
            )
            routes = 0
            if len(near):
                starts = _run_starts(item[near])
                routed = item[near][starts]
                self._table_reads += 1
                which, sets = solve.next_hop_table().read_unicast(
                    col[near],
                    starts,
                    dist[near][starts],
                    np.fromiter(
                        (batch[i].is_v4 for i in routed.tolist()),
                        dtype=bool,
                        count=len(routed),
                    ),
                )
                fate[routed] = which
                routes = int(np.count_nonzero(which >= 0))
                self._table_routes += routes
            unrouted = int(np.count_nonzero(count)) - routes
            if unrouted:
                self._bump("decision.no_route_to_prefix", unrouted)

        # a route: its entry and its own copy of the set
        reached = reach.tolist()
        first = (np.cumsum(count) - count).tolist()
        for prefix, prefix_entries, which_set, best_entry, at in zip(
            batch, adverts, fate.tolist(), lone, first
        ):
            if which_set >= 0:
                if best_entry is None:
                    # the smallest reachable announcer's name, drained
                    # or not (get_best_announcing_nodes)
                    best = min(
                        node
                        for node, ok in zip(
                            prefix_entries,
                            reached[at : at + len(prefix_entries)],
                        )
                        if ok
                    )
                    best_entry = prefix_entries[best][area]
                unicast_entries[prefix] = RibUnicastEntry(
                    prefix, set(sets[which_set]), best_entry, area
                )
            elif which_set == _ONE_BY_ONE:
                self.build_unicast_route(
                    unicast_entries,
                    my_node_name,
                    prefix,
                    prefix_entries,
                    area_link_states,
                    prefix_state,
                )

    @staticmethod
    def _drained_announcers(
        area, area_link_states, solve, adverts, count, col
    ) -> np.ndarray:
        """Which of a batch's announcers (by column, in the batch's order)
        are drained, in my area or in any other."""
        drained = solve.graph.overloaded[col]
        others = [
            ls for name, ls in area_link_states.items() if name != area
        ]
        if others:
            # an area that does not hold my node has its say on who is
            # drained all the same
            drained = drained | np.fromiter(
                (
                    any(ls.is_node_overloaded(node) for ls in others)
                    for prefix_entries, asked in zip(adverts, count.tolist())
                    if asked
                    for node in prefix_entries
                ),
                dtype=bool,
                count=len(col),
            )
        return drained

    def next_hops_toward(
        self,
        my_node_name: str,
        dst_node_names: Set[str],
        is_v4: bool,
        per_destination: bool,
        swap_label: Optional[int],
        area_link_states: Dict[str, LinkState],
        prefix_areas: Set[str],
    ) -> Union[Set[NextHop], LabelNextHops, None]:
        """From the area's next-hop table where the input allows: no LFA,
        no per-destination action, and one area alone that holds my node,
        among the prefix's. Anything else walks the generic stack."""
        table = None
        if (
            not self.compute_lfa_paths
            and not per_destination
            and my_node_name == self.my_node_name
        ):
            table = self._next_hop_table(area_link_states, prefix_areas)
        if table is not None:
            self._table_reads += 1
            next_hops = table.next_hops(
                dst_node_names, is_v4, swap_label, self._label_sets_made
            )
            if next_hops is not None:
                self._table_routes += 1
            return next_hops
        next_hops = super().next_hops_toward(
            my_node_name,
            dst_node_names,
            is_v4,
            per_destination,
            swap_label,
            area_link_states,
            prefix_areas,
        )
        if next_hops is not None:
            self._generic_routes += 1
        return next_hops

    def _next_hop_table(
        self, area_link_states: Dict[str, LinkState], prefix_areas: Set[str]
    ) -> Optional[_NextHopTable]:
        """The table of the one area that holds my node, where that area
        is among the prefix's; None for any other input."""
        mine = self._my_one_area(area_link_states)
        if mine is None or mine[0] not in prefix_areas:
            return None
        return mine[1].next_hop_table()

    def _my_one_area(
        self, area_link_states: Dict[str, LinkState]
    ) -> Optional[Tuple[str, _AreaSolve]]:
        """(area, my solve there) where one area alone holds my node."""
        mine = None
        for area, link_state in area_link_states.items():
            solve = self._my_solve(link_state)
            if solve is None:
                continue
            if mine is not None:
                return None
            mine = (area, solve)
        return mine

    def _kth_paths(
        self, link_state: LinkState, src: str, dest: str, k: int
    ) -> List[Path]:
        solve = self._my_solve(link_state)
        if solve is None or src != self.my_node_name:
            return link_state.get_kth_paths(src, dest, k)
        return solve.kth_paths(dest, k)

    def _prefetch_kth_paths(
        self, link_state: LinkState, src: str, dests: List[str], k: int
    ) -> None:
        solve = self._my_solve(link_state)
        if solve is not None and src == self.my_node_name:
            solve.prefetch_ksp(dests, k)
