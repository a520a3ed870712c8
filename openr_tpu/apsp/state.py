"""Device-resident all-pairs shortest-path state for one area graph.

`ApspState` keeps one [n_pad, n_pad] distance matrix resident on device
per area (the blocked Floyd–Warshall close of the compiled-graph weight
matrix) and serves every consumer that needs arbitrary-pair distances —
LFA nexthop qualification for sources outside the solved batch, KSP
penalized-layer seeding, and TE hard-scoring — from that one matrix
instead of per-source column solves.

Discipline mirrors `_AreaSolve` (solver/tpu.py):

  - **Device residency + lazy host mirror.** The matrix stays on device
    between events; host readers go through the lazy `d` mirror and the
    copy-back is accounted in `d2h_bytes` (the device-transfer analysis
    rule's sanctioned-seam convention).
  - **Warm re-close.** A weight-change event patches the resident weight
    matrix with the changed (u, v) pair minima and re-closes only the
    block rows/columns reachable from the changed edges
    (apsp/kernels.py:_fw_seed_solver/_fw_reclose_solver). Events that
    poison the warm state — structural rebuild, overload-mask change,
    more than `_APSP_PATCH_SLOTS` increased pairs, a numpy-resident
    matrix — fall back to a cold close.
  - **Staleness guard.** `invalidate()` drops the resident matrix; the
    owning `_AreaSolve` calls it whenever its own warm solve was poisoned
    (patch overflow, cold start) and resharding/breaker trips drop the
    whole solve (and this state with it), so a stale APSP matrix can
    never serve a consumer.
  - **Supervised dispatch.** Device closes route through the solver fault
    domain when a dispatch hook is attached (SolverSupervisor
    .supervised_call via TpuSpfSolver): classified compile/runtime/
    device-loss faults feed the shared breaker and the close degrades to
    the numpy Floyd–Warshall fallback instead of failing the event.
  - **Shadow audit.** Every `audit_interval`-th close compares the
    resident matrix against the numpy FW oracle recomputed from host-side
    graph truth (mirroring the warm-state audit): a mismatch invalidates
    and cold re-closes in place — self-healing, never silently wrong.
"""

from __future__ import annotations

import logging
import time
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from openr_tpu.apsp.kernels import (
    _APSP_PATCH_SLOTS,
    _fw_reclose_solver,
    _fw_seed_solver,
    _fw_solver,
    build_allow_matrix,
    build_weight_matrix,
    fw_block_shape,
    np_floyd_warshall,
)
from openr_tpu.ops.graph import CompiledGraph, _next_bucket
from openr_tpu.testing.faults import fault_point

log = logging.getLogger(__name__)

# re-close safety margin: the restricted fixpoint stitches at least one
# old-path segment per round, so rounds beyond the block count mean a bug
# — fall back to a cold close rather than loop
_RECLOSE_ROUND_MARGIN = 4


class ApspState:
    """One resident blocked-FW APSP matrix, warm-re-closed per event."""

    def __init__(
        self,
        max_nodes: int,
        dispatch: Optional[Callable] = None,
        audit_interval: int = 0,
        warm: bool = True,
        area: str = "",
        on_refusal: Optional[Callable] = None,
    ) -> None:
        self.max_nodes = max_nodes
        # device-memory observatory (monitor/memledger.py): the resident
        # FW triple registers under this area tag; residency admission is
        # headroom-gated through the ledger's capacity model with
        # max_nodes as the fallback when no capacity source exists
        from openr_tpu.monitor.memledger import get_ledger

        self._ledger = get_ledger()
        self._mem_area = area or "apsp"
        self._mem_handle: Optional[int] = None
        self._on_refusal = on_refusal
        self.last_refusal: Optional[Dict] = None
        self._refused_version: Optional[int] = None
        # dispatch(op, primary_fn, fallback_fn) -> (result, degraded):
        # the SolverSupervisor.supervised_call signature; None = bare
        # try/except with the numpy fallback
        self._dispatch = dispatch
        self.audit_interval = audit_interval
        self.warm = warm

        # convergence/observability (decision.spf.apsp_* counters)
        self.closes = 0
        self.warm_closes = 0
        self.cold_closes = 0
        self.fallback_closes = 0  # closes served by the numpy FW fallback
        self.invalidations = 0
        self.audit_runs = 0
        self.audit_mismatches = 0
        self.reclose_rounds_last: Optional[int] = None
        self.close_ms_last: Optional[float] = None
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.backend: Optional[str] = None  # "device" | "numpy"
        self.stale_reason: Optional[str] = None
        # counter-sync bookmarks (TpuSpfSolver._sync_apsp_counters)
        self._closes_synced = 0
        self._sync_marks: Dict[str, int] = {}

        # resident state
        self._src_ref: Optional[np.ndarray] = None
        self._version = -2
        self._n_pad = 0
        self._nb = 0
        self._bsz = 0
        self._w_host: Optional[np.ndarray] = None  # edge-array snapshot
        self._ov_host: Optional[np.ndarray] = None
        self._pair_pos: Dict[Tuple[int, int], np.ndarray] = {}
        self._d_dev = None
        self._w_dev = None
        self._allow_dev = None
        self._d_host: Optional[np.ndarray] = None
        self._closes_since_audit = 0

    # ------------------------------------------------------------------

    def enabled_for(self, graph: CompiledGraph) -> bool:
        """Dense FW residency admission. The PRIMARY gate is the memory
        ledger's predictive capacity model: the [n_pad, n_pad] triple is
        admitted only when `predict_fit` says it fits current headroom —
        a measured verdict from the same padding arithmetic the closer
        uses. The static `solver_apsp_max_nodes` cap is the FALLBACK,
        used only when no capacity source exists (the CPU backend exposes
        no memory stats). A definite no-fit is a refusal: counted,
        remembered for getSolverHealth, and surfaced through the owning
        solver as a SOLVER_CAPACITY_REFUSED sample instead of silent
        non-residency (docs/Apsp.md crossover)."""
        if graph.n <= 0:
            return False
        verdict = self._ledger.predict_fit(graph.n, "apsp", graph=graph)
        if verdict["fits"] is None:
            # no capacity source: the static node cap is the gate
            return graph.n <= self.max_nodes
        if verdict["fits"]:
            return True
        if self._refused_version != graph.version:
            # one refusal per graph snapshot: every consumer probe after
            # the first rides the remembered verdict
            self._refused_version = graph.version
            self._ledger.record_refusal(verdict)
            self.last_refusal = dict(verdict)
            if self._on_refusal is not None:
                self._on_refusal(verdict)
        return False

    def resident(self) -> bool:
        return self._d_dev is not None or self._d_host is not None

    def fresh_for(self, graph: CompiledGraph) -> bool:
        return (
            self.resident()
            and self._src_ref is graph.src
            and self._version == graph.version
        )

    def invalidate(self, reason: str) -> None:
        """Staleness guard: drop the resident matrix so the next ensure()
        cold-closes. Called by the owning solve whenever its own warm
        state was poisoned (patch overflow, cold start, resharding drops
        the solve wholesale) and by the shadow audit on a mismatch."""
        if self.resident():
            self.invalidations += 1
        self._d_dev = None
        self._d_host = None
        self._w_dev = None
        self._src_ref = None
        self._version = -2
        self.stale_reason = reason
        self._mem_register_resident()

    def _mem_register_resident(self) -> None:
        """Ledger seam: re-register the resident FW triple (d + w +
        allow) after a close, or release it when the matrix dropped
        (invalidation, numpy fallback, teardown) — staleness
        invalidation must return the ledger to its pre-close baseline."""
        self._ledger.release(self._mem_handle)
        self._mem_handle = None
        if self._d_dev is not None:
            self._mem_handle = self._ledger.register(
                self._mem_area,
                "apsp",
                layout="apsp",
                arrays=(self._d_dev, self._w_dev, self._allow_dev),
            )

    def close(self) -> None:
        """Teardown: release the ledger entry (owning solve dropped)."""
        self._ledger.release(self._mem_handle)
        self._mem_handle = None

    # ------------------------------------------------------------------

    def ensure(self, graph: CompiledGraph) -> bool:
        """Bring the resident matrix up to date with the graph snapshot;
        returns False when the graph exceeds the node cap (consumers fall
        back to their column-solve paths)."""
        if not self.enabled_for(graph):
            if self.resident():
                self.invalidate("graph_too_large")
            return False
        if self.fresh_for(graph):
            return True
        structural = (
            not self.resident()
            or self._src_ref is not graph.src
            or self._d_dev is None  # numpy-resident: no device warm base
        )
        ov_changed = not structural and not np.array_equal(
            self._ov_host, graph.overloaded
        )
        if structural or ov_changed or not self.warm:
            # an overload toggle re-masks every (i, j) pair: warm
            # invalidation would have to re-witness the whole matrix, so
            # the transit-mask change closes cold like a structural event
            self._close_cold(graph)
            return True
        changed = np.nonzero(self._w_host[: graph.e] != graph.w[: graph.e])[0]
        if not len(changed):
            self._version = graph.version  # snapshot is current, no diff
            return True
        inc, patch = self._classify_pairs(graph, changed)
        if len(inc) > _APSP_PATCH_SLOTS:
            # warm-patch overflow poisons the warm close (the same event
            # class that poisons the batch solver's warm state)
            self.invalidate("patch_overflow")
            self._close_cold(graph)
            return True
        self._close_warm(graph, inc, patch)
        return True

    # ------------------------------------------------------------------

    def _classify_pairs(self, graph: CompiledGraph, changed: np.ndarray):
        """Changed edge positions -> per-(u, v)-pair weight-minimum moves:
        (increases [(u, v, old_min)], patches [(u, v, new_min)]). Parallel
        edges collapse to the pair minimum, so an edge change only counts
        when it moves the pair's min."""
        pairs = {
            (int(graph.src[p]), int(graph.dst[p])) for p in changed
        }
        inc = []
        patch = []
        for u, v in sorted(pairs):
            pos = self._pair_pos[(u, v)]
            old = int(self._w_host[pos].min())
            new = int(graph.w[pos].min())
            if new == old:
                continue
            patch.append((u, v, new))
            if new > old:
                inc.append((u, v, old))
        return inc, patch

    def _run_close(self, op: str, primary, fallback):
        if self._dispatch is not None:
            return self._dispatch(op, primary, fallback)
        try:
            return primary(), False
        except Exception:
            # unsupervised embedding: the numpy close serves, but never
            # silently — fallback() counts it (fallback_closes) and the
            # device error is logged with its traceback
            log.exception("%s failed on device; numpy fallback serves", op)
            return fallback(), True

    def _close_cold(self, graph: CompiledGraph, audit: bool = True) -> None:
        t0 = time.perf_counter()
        self._compile(graph)
        nb, bsz = self._nb, self._bsz

        def primary():
            # named fault seam: the supervisor's APSP fault-domain tests
            # inject compile/runtime/device-loss faults here, exactly
            # where a real XLA dispatch would raise (docs/Robustness.md)
            fault_point("solver.apsp.close", self)
            import jax.numpy as jnp

            w_np = build_weight_matrix(graph)
            allow_np = build_allow_matrix(graph.overloaded)
            w_dev = jnp.asarray(w_np)
            allow_dev = jnp.asarray(allow_np)
            self.h2d_bytes += w_np.nbytes + allow_np.nbytes
            d, probe = _fw_solver((nb, bsz))(w_dev, allow_dev)
            int(probe)  # 4-byte scalar: force completion for the timing
            return d, w_dev, allow_dev

        def fallback():
            self.fallback_closes += 1
            d_np = np_floyd_warshall(
                build_weight_matrix(graph), graph.overloaded
            )
            return d_np, None, None

        (d, w_dev, allow_dev), degraded = self._run_close(
            "apsp.close", primary, fallback
        )
        if degraded or w_dev is None:
            self._d_dev = None
            self._d_host = np.asarray(d)
            self._w_dev = None
            self._allow_dev = None
            self.backend = "numpy"
        else:
            self._d_dev = d
            self._d_host = None
            self._w_dev = w_dev
            self._allow_dev = allow_dev
            self.backend = "device"
        self._mem_register_resident()
        self._snapshot(graph)
        self.closes += 1
        self.cold_closes += 1
        self.reclose_rounds_last = None
        self.close_ms_last = (time.perf_counter() - t0) * 1e3
        self.stale_reason = None
        if audit:
            self._maybe_audit(graph)

    def _close_warm(self, graph: CompiledGraph, inc, patch) -> None:
        t0 = time.perf_counter()
        nb, bsz = self._nb, self._bsz

        def primary():
            fault_point("solver.apsp.close", self)
            import jax.numpy as jnp

            us = np.array([u for u, _, _ in patch], dtype=np.int32)
            vs = np.array([v for _, v, _ in patch], dtype=np.int32)
            vals = np.array([w for _, _, w in patch], dtype=np.int32)
            w_dev = self._w_dev.at[us, vs].set(jnp.asarray(vals))
            self.h2d_bytes += us.nbytes + vs.nbytes + vals.nbytes
            p = _next_bucket(max(len(inc), 1), minimum=8)
            iu = np.full(p, 1 << 30, dtype=np.int32)
            iv = np.zeros(p, dtype=np.int32)
            iw = np.zeros(p, dtype=np.int32)
            for i, (u, v, old) in enumerate(inc):
                iu[i], iv[i], iw[i] = u, v, old
            self.h2d_bytes += iu.nbytes + iv.nbytes + iw.nbytes
            d0, dirty, num_dirty = _fw_seed_solver((nb, bsz, p))(
                self._d_dev,
                w_dev,
                jnp.asarray(iu),
                jnp.asarray(iv),
                jnp.asarray(iw),
            )
            rounds = 0
            nd = int(num_dirty)  # 4-byte scalar read per round
            d = d0
            while nd:
                if rounds > nb + _RECLOSE_ROUND_MARGIN:
                    raise RuntimeError(
                        f"APSP re-close did not converge in {rounds} "
                        f"rounds ({nd} dirty blocks)"
                    )
                kb = min(_next_bucket(nd, minimum=1), nb)
                d, dirty, num_dirty, changed = _fw_reclose_solver(
                    (nb, bsz, kb)
                )(d, self._allow_dev, dirty)
                rounds += 1
                if int(changed) == 0:
                    break
                nd = int(num_dirty)
            return d, w_dev, rounds

        def fallback():
            self.fallback_closes += 1
            d_np = np_floyd_warshall(
                build_weight_matrix(graph), graph.overloaded
            )
            return d_np, None, None

        (d, w_dev, rounds), degraded = self._run_close(
            "apsp.close", primary, fallback
        )
        if degraded or w_dev is None:
            self._d_dev = None
            self._d_host = np.asarray(d)
            self._w_dev = None
            self.backend = "numpy"
            self.cold_closes += 1
            self.reclose_rounds_last = None
        else:
            self._d_dev = d
            self._d_host = None
            self._w_dev = w_dev
            self.backend = "device"
            self.warm_closes += 1
            self.reclose_rounds_last = rounds
        self._mem_register_resident()
        self._snapshot(graph)
        self.closes += 1
        self.close_ms_last = (time.perf_counter() - t0) * 1e3
        self.stale_reason = None
        self._maybe_audit(graph)

    # ------------------------------------------------------------------

    def _compile(self, graph: CompiledGraph) -> None:
        """(Re)derive the per-structure layout: block shape and the
        (u, v) -> edge-position index the pair-minimum patches need."""
        self._n_pad = graph.n_pad
        self._nb, self._bsz = fw_block_shape(graph.n_pad)
        if self._src_ref is not graph.src:
            pair_pos: Dict[Tuple[int, int], list] = {}
            for p in range(graph.e):
                pair_pos.setdefault(
                    (int(graph.src[p]), int(graph.dst[p])), []
                ).append(p)
            self._pair_pos = {
                k: np.asarray(v, dtype=np.int64)
                for k, v in pair_pos.items()
            }

    def _snapshot(self, graph: CompiledGraph) -> None:
        self._src_ref = graph.src
        self._version = graph.version
        self._w_host = graph.w.copy()
        self._ov_host = graph.overloaded.copy()

    # ------------------------------------------------------------------

    @property
    def d(self) -> np.ndarray:
        """Host mirror of the resident [n_pad, n_pad] matrix, fetched on
        first access after each close. An OWNED copy (np.array, not
        asarray): a CPU-backend zero-copy view would alias device memory
        the next close overwrites."""
        if self._d_host is None:
            self._d_host = np.array(self._d_dev)
            self.d2h_bytes += self._d_host.nbytes
        return self._d_host

    def row(self, i: int) -> np.ndarray:
        """One source row of the resident matrix (through the mirror: APSP
        consumers read many rows per event, so the full fetch amortizes)."""
        return self.d[i]

    # ------------------------------------------------------------------

    def _maybe_audit(self, graph: CompiledGraph) -> None:
        """Every `audit_interval`-th close, compare the resident matrix
        against the numpy FW oracle recomputed from host-side graph truth
        (the warm-state audit's APSP mirror). A mismatch invalidates and
        cold re-closes in place — the corrected matrix serves the same
        event."""
        if self.audit_interval <= 0:
            return
        self._closes_since_audit += 1
        if self._closes_since_audit < self.audit_interval:
            return
        self._closes_since_audit = 0
        self.audit_runs += 1
        ref = np_floyd_warshall(build_weight_matrix(graph), graph.overloaded)
        if np.array_equal(self.d, ref):
            return
        self.audit_mismatches += 1
        self.invalidate("audit_mismatch")
        self._close_cold(graph, audit=False)

    def health(self) -> Dict:
        """Introspection record (tests, getSolverHealth wiring)."""
        return {
            "resident": self.resident(),
            "backend": self.backend,
            "closes": self.closes,
            "warm_closes": self.warm_closes,
            "cold_closes": self.cold_closes,
            "fallback_closes": self.fallback_closes,
            "invalidations": self.invalidations,
            "reclose_rounds_last": self.reclose_rounds_last,
            "audit_runs": self.audit_runs,
            "audit_mismatches": self.audit_mismatches,
            "stale_reason": self.stale_reason,
        }
