"""Batched multi-source shortest paths as min-plus relaxation on TPU.

Replaces the reference's per-source Dijkstra hot loop
(openr/decision/LinkState.cpp:806-880) with Bellman-Ford relaxation rounds
over the whole source batch at once:

    D[s, v] <- min(D[s, v], min over edges (u->v): Dt[s, u] + w(u, v))

where Dt masks transit through overloaded nodes per source (a source's own
row keeps its outgoing edges — LinkState.cpp:829-836 semantics). Each round is
a gather + add + segment-min, entirely fusible by XLA; rounds run under
lax.while_loop until the distance matrix reaches its fixpoint (≤ diameter
rounds, bounded by n for safety).

The ECMP first-hop DAG falls out of the triangle condition
    w(u, v) + D[v, t] == D[u, t]
which reproduces exactly the Dijkstra nexthop-union semantics of
LinkState.cpp:855-871 (proof: a pruned shortest path with first hop v exists
iff v is non-overloaded-or-destination and the triangle holds).

Sharding: all arrays are batched on the sources axis; `sharded_batched_spf`
in openr_tpu.parallel shards that axis over the device mesh so each chip
relaxes its slice of sources with the (small) edge list replicated — no
cross-chip traffic inside a round.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from openr_tpu.monitor.spans import stage
from openr_tpu.ops.graph import INF, CompiledGraph, _next_bucket
from openr_tpu.testing.faults import fault_point
from openr_tpu.utils.shape_contract import shape_contract


def _bf_allow(sources: jnp.ndarray, overloaded: jnp.ndarray) -> jnp.ndarray:
    """Row-major [S, N] transit mask: transit allowed through u for source
    row i unless u is overloaded and u is not the source itself."""
    n = overloaded.shape[0]
    node_ids = jnp.arange(n, dtype=jnp.int32)
    return (~overloaded)[None, :] | (node_ids[None, :] == sources[:, None])


@shape_contract(
    "d0:[S,n_pad]:int32:inf",
    "allow:[S,n_pad]:bool",
    "src_e:[E]:int32",
    "dst_e:[E]:int32",
)
def _bf_relax(d0, allow, src_e, dst_e, w_rows):
    """Edge-list min-plus relaxation from row-major initial state d0 to the
    fixpoint; returns (d [S, N], rounds). Like _sell_relax, any entrywise
    upper bound of the true distances with the source diagonal pinned to 0
    is a valid d0, which is what makes the edge-list warm path sound."""
    n = d0.shape[1]

    def body(state):
        d, _, it = state
        dt = jnp.where(allow, d, INF)
        contrib = jnp.minimum(dt[:, src_e] + w_rows, INF)  # [S, E]
        upd = jax.vmap(
            lambda row: jax.ops.segment_min(
                row, dst_e, num_segments=n, indices_are_sorted=True
            )
        )(contrib)
        new_d = jnp.minimum(d, upd)
        return new_d, jnp.any(new_d != d), it + 1

    def cond(state):
        _, changed, it = state
        return changed & (it < n)

    d, _, rounds = jax.lax.while_loop(cond, body, (d0, jnp.bool_(True), 0))
    return d, rounds


def _bf_fixpoint_vw_core(
    sources: jnp.ndarray,  # int32 [S]
    src_e: jnp.ndarray,  # int32 [E]
    dst_e: jnp.ndarray,  # int32 [E]
    w_rows: jnp.ndarray,  # int32 [S, E] or [1, E] (broadcast) edge weights
    overloaded: jnp.ndarray,  # bool [N]
) -> jnp.ndarray:
    """Distance matrix D [S, N]; each batch row may solve with its own
    edge-weight vector. Per-row weights are the device form of the
    reference's penalized re-solves: KSP's link-ignore runSpf
    (LinkState.cpp:760-789, ignore set ≙ INF weights) and
    multi-metric/multi-topology SPF become extra batch rows of one solve
    instead of sequential Dijkstra runs."""
    n = overloaded.shape[0]
    s = sources.shape[0]
    d0 = jnp.full((s, n), INF, dtype=jnp.int32)
    d0 = d0.at[jnp.arange(s), sources].set(0)
    allow = _bf_allow(sources, overloaded)
    d, _ = _bf_relax(d0, allow, src_e, dst_e, w_rows)
    return d


_bf_fixpoint_vw = jax.jit(_bf_fixpoint_vw_core)


@functools.lru_cache(maxsize=8)
def _bf_vw_solver(mesh=None):
    """Jitted per-row-weights edge-list solve, optionally mesh-sharded
    (sources and weight rows over 'batch'). The non-sliced analog of
    _sell_solver_vw(key, mesh) so KSP prefetch honors solver_mesh on
    graphs that disqualify the sliced-ELL layout."""
    if mesh is None:
        return _bf_fixpoint_vw
    row, repl, row2 = _mesh_shardings(mesh)
    return jax.jit(
        _bf_fixpoint_vw_core,
        in_shardings=(row, repl, repl, row2, repl),
        out_shardings=row2,
    )


@jax.jit
def _bf_fixpoint(
    sources: jnp.ndarray,  # int32 [S]
    src_e: jnp.ndarray,  # int32 [E]
    dst_e: jnp.ndarray,  # int32 [E]
    w_e: jnp.ndarray,  # int32 [E]
    overloaded: jnp.ndarray,  # bool [N]
) -> jnp.ndarray:
    """Shared-weights solve: one kernel, weights broadcast across the batch."""
    return _bf_fixpoint_vw(sources, src_e, dst_e, w_e[None, :], overloaded)


@functools.lru_cache(maxsize=64)
def _sell_solver_raw(key: Tuple):
    """Unjitted sliced-ELL fixpoint for one bucket structure (SlicedEll
    .shape_key()) — callers jit it themselves (with shardings for the mesh
    path). Weight patches keep the structure, so per-structure executables
    are reused across LSDB events; lru_cache bounds the executable
    population the way the size-bucket padding does.

    Each round processes the destination-major [N, S] distance matrix in
    contiguous equal-degree row slices: slice k relaxes via dk row-gathers
    + fused vector mins, writing only the [nk, S] slice — no scatter and no
    [E, S] contribution materialization, which is what makes this ~1.7x
    faster than the edge-list segment-min form at 100k nodes."""

    zero_end, starts, shapes = key

    def solve(sources, nbrs, wgs, overloaded):
        return _sell_fixpoint_core(
            sources, nbrs, wgs, overloaded, zero_end, starts, shapes
        )

    return solve


# bound trace-time unrolling for fat buckets (Clos spines etc.); the
# fori_loop body indexes nbr/wg columns dynamically instead
_UNROLL_MAX = 32


def _sell_d0_allow(sources, overloaded):
    """Cold-start dest-major initial state [N, S] plus the per-source
    transit mask (overloaded nodes relay nothing unless they are the
    source itself)."""
    (n,) = overloaded.shape
    s = sources.shape[0]
    node_ids = jnp.arange(n, dtype=jnp.int32)
    d0 = jnp.full((n, s), INF, dtype=jnp.int32)  # dest-major
    d0 = d0.at[sources, jnp.arange(s)].set(0)
    allow = (~overloaded)[:, None] | (node_ids[:, None] == sources[None, :])
    return d0, allow


def _sell_relax(d0, allow, nbrs, wgs, zero_end, starts, shapes):
    """Min-plus relaxation from dest-major initial state d0 to the fixpoint.

    Returns (d [N, S], rounds). Valid for ANY d0 that is an entrywise upper
    bound of the true distances with the source diagonal pinned to 0: the
    iteration map F(D) = min(D, relax(D)) is monotone, keeps D >= D*, and
    its only fixed point with D[s, s] = 0 at or above D* is D* itself —
    which is what makes warm-starting from a previous event's distances
    sound (cold start D0 = INF is just the trivial upper bound).

    wgs leaves are [nk, dk] (shared across the batch) or [nk, dk, S]
    (per-batch-row weights, the penalized-re-solve form); broadcasting
    handles both in one implementation so the two paths cannot diverge."""
    n = d0.shape[0]

    def body(state):
        d, _, it = state
        dt = jnp.where(allow, d, INF)
        parts = [d[:zero_end]] if zero_end else []
        end = zero_end
        for k, (nbr_k, wg_k) in enumerate(zip(nbrs, wgs)):
            nk, dk = shapes[k]
            bs = starts[k]
            acc = d[bs : bs + nk]
            if dk <= _UNROLL_MAX:
                for j in range(dk):
                    wj = (
                        wg_k[:, j][:, None]
                        if wg_k.ndim == 2
                        else wg_k[:, j, :]
                    )
                    acc = jnp.minimum(
                        acc, jnp.minimum(dt[nbr_k[:, j]] + wj, INF)
                    )
            else:

                def j_step(j, a, nbr_k=nbr_k, wg_k=wg_k):
                    ids = jax.lax.dynamic_index_in_dim(
                        nbr_k, j, axis=1, keepdims=False
                    )
                    wj = jax.lax.dynamic_index_in_dim(
                        wg_k, j, axis=1, keepdims=False
                    )
                    if wg_k.ndim == 2:
                        wj = wj[:, None]
                    return jnp.minimum(
                        a, jnp.minimum(dt[ids] + wj, INF)
                    )

                acc = jax.lax.fori_loop(0, dk, j_step, acc)
            parts.append(acc)
            end = bs + nk
        if end < n:
            parts.append(d[end:])  # array-padding rows never change
        new_d = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
        return new_d, jnp.any(new_d != d), it + 1

    def cond(state):
        _, changed, it = state
        return changed & (it < n)

    d, _, rounds = jax.lax.while_loop(cond, body, (d0, jnp.bool_(True), 0))
    return d, rounds


def _sell_fixpoint_core(
    sources, nbrs, wgs, overloaded, zero_end, starts, shapes
):
    """Cold-start fixpoint (distances only), row-major [S, N]."""
    d0, allow = _sell_d0_allow(sources, overloaded)
    d, _ = _sell_relax(d0, allow, nbrs, wgs, zero_end, starts, shapes)
    return d.T


def _mesh_shardings(mesh):
    """(row-sharded over 'batch', replicated) NamedShardings for a solver
    mesh. The sliced-ELL solve shards only its source batch; the layout
    leaves are replicated so each relaxation round stays collective-free
    (openr_tpu/parallel/mesh.py design)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return (
        NamedSharding(mesh, P("batch")),
        NamedSharding(mesh, P()),
        NamedSharding(mesh, P("batch", None)),
    )


@functools.lru_cache(maxsize=64)
def _sell_solver(key: Tuple, mesh=None):
    """Jitted form of _sell_solver_raw. With a mesh, the source batch is
    sharded over the 'batch' axis and D comes back row-sharded — the
    production multi-chip path (DecisionConfig.solver_mesh)."""
    if mesh is None:
        return jax.jit(_sell_solver_raw(key))
    row, repl, out = _mesh_shardings(mesh)
    return jax.jit(
        _sell_solver_raw(key),
        in_shardings=(row, repl, repl, repl),
        out_shardings=out,
    )


@functools.lru_cache(maxsize=64)
def _sell_solver_counted(key: Tuple, mesh=None):
    """Like _sell_solver, but also returns the relaxation round count:
    (D [S, N], rounds). The device-resident event path uses this for its
    cold solves so `decision.spf.rounds_last` covers every solve, warm or
    cold, and warm-start wins are observable as a round-count drop."""
    zero_end, starts, shapes = key

    def solve(sources, nbrs, wgs, overloaded):
        d0, allow = _sell_d0_allow(sources, overloaded)
        d, rounds = _sell_relax(d0, allow, nbrs, wgs, zero_end, starts, shapes)
        return d.T, rounds

    if mesh is None:
        return jax.jit(solve)
    row, repl, out = _mesh_shardings(mesh)
    return jax.jit(
        solve,
        in_shardings=(row, repl, repl, repl),
        out_shardings=(out, repl),
    )


def _sell_apply_patches(wgs, patch_idx, patch_vals):
    """Scatter the fixed-width per-bucket weight patches into the bucket
    arrays; padding rows carry out-of-range indices and are dropped."""
    return tuple(
        wg_k.at[patch_idx[k, :, 0], patch_idx[k, :, 1]].set(
            patch_vals[k], mode="drop"
        )
        for k, wg_k in enumerate(wgs)
    )


@functools.lru_cache(maxsize=64)
def _sell_solver_patched(key: Tuple, mesh=None):
    """Patch-and-solve in one dispatch: applies per-bucket weight patches
    (idx [Pk, 2] of (row, slot), vals [Pk]; out-of-range rows dropped) to
    the persistent wg buffers, solves cold, and returns (D, new_wgs,
    rounds) so the caller can keep the patched buffers device-resident.
    One device dispatch per LSDB event instead of scatter + solve — the
    host-side share of a flap event is mostly dispatch latency."""
    zero_end, starts, shapes = key

    def solve(sources, nbrs, wgs, overloaded, patch_idx, patch_vals):
        # patch_idx [B, P, 2] / patch_vals [B, P]: one upload each, sliced
        # per bucket at trace time (B is fixed by the shape key)
        new_wgs = _sell_apply_patches(wgs, patch_idx, patch_vals)
        d0, allow = _sell_d0_allow(sources, overloaded)
        d, rounds = _sell_relax(
            d0, allow, nbrs, new_wgs, zero_end, starts, shapes
        )
        return d.T, new_wgs, rounds

    # donate the replaced weight buffers: the caller always overwrites its
    # handle with new_wgs, so XLA may update in place instead of allocating
    # a second full set of buckets per event
    if mesh is None:
        return jax.jit(solve, donate_argnums=(2,))
    row, repl, out = _mesh_shardings(mesh)
    return jax.jit(
        solve,
        donate_argnums=(2,),
        in_shardings=(row, repl, repl, repl, repl, repl),
        out_shardings=(out, repl, repl),
    )


def _sell_invalidate(dp, nbrs, wgs, inc_idx, zero_end, starts, shapes):
    """Ramalingam–Reps-style invalidation, vectorized on the sliced layout.

    dp is the dest-major [N, S] OLD distance fixpoint and wgs the OLD
    bucket weights. inc_idx [B, P, 2] names the (row, slot) positions whose
    weight is about to increase (padding rows carry out-of-range indices).
    Returns (marks, rounds): marks is a bool [N, S] mask of entries whose
    old shortest-path witness may traverse an increased edge, rounds the
    boolean fixpoint's iteration count (the ROADMAP rounds-accounting gap:
    mark propagation is cheap per round but unbounded in principle on deep
    DAGs, so it is surfaced as decision.spf.invalidation_rounds_last).
    Seed marks where an increased edge sits on the old shortest-path DAG
    (triangle condition against the old weights), then propagate marks down
    the old DAG with a boolean fixpoint (`_sell_mark_fixpoint`, shared with
    the per-row KSP warm seed). Over-marking is safe (marked entries are
    recomputed from INF); under-marking is impossible because every true
    DAG edge passes the unmasked triangle test."""
    n, s = dp.shape
    marks = jnp.zeros((n, s), dtype=jnp.bool_)
    for k, (nbr_k, wg_k) in enumerate(zip(nbrs, wgs)):
        nk, dk = shapes[k]
        rows = inc_idx[k, :, 0]
        slots = inc_idx[k, :, 1]
        valid = rows < (1 << 29)  # padding rows are 1 << 30
        r = jnp.clip(rows, 0, nk - 1)
        j = jnp.clip(slots, 0, dk - 1)
        u = nbr_k[r, j]  # [P] in-neighbor of each increased edge
        w_old = wg_k[r, j]  # [P]
        v = starts[k] + r  # [P] global node row of each edge head
        dv = dp[v]  # [P, S]
        cond = (
            valid[:, None]
            & (dv < INF)
            & (jnp.minimum(dp[u] + w_old[:, None], INF) == dv)
        )
        marks = marks.at[v].max(cond)
    return _sell_mark_fixpoint(dp, marks, nbrs, wgs, zero_end, starts, shapes)


def _sell_mark_fixpoint(dp, marks, nbrs, wgs, zero_end, starts, shapes):
    """Propagate invalidation marks down the old shortest-path DAG (a
    boolean fixpoint over the sliced layout): an entry marks when any of
    its old-DAG in-edges carries a marked tail. Shared by the shared-
    weights warm path (_sell_invalidate seeds) and the per-row KSP warm
    seed (_sell_solver_vw_warm seeds). Returns (marks, rounds)."""
    n, _ = dp.shape

    def body(state):
        m, _, it = state
        parts = [m[:zero_end]] if zero_end else []
        end = zero_end
        for k, (nbr_k, wg_k) in enumerate(zip(nbrs, wgs)):
            nk, dk = shapes[k]
            bs = starts[k]
            dv = dp[bs : bs + nk]
            reach = dv < INF
            acc = m[bs : bs + nk]
            if dk <= _UNROLL_MAX:
                for j in range(dk):
                    ids = nbr_k[:, j]
                    wj = wg_k[:, j][:, None]
                    on_dag = jnp.minimum(dp[ids] + wj, INF) == dv
                    acc = acc | (m[ids] & on_dag & reach)
            else:

                def j_step(j, a, nbr_k=nbr_k, wg_k=wg_k, dv=dv, reach=reach):
                    ids = jax.lax.dynamic_index_in_dim(
                        nbr_k, j, axis=1, keepdims=False
                    )
                    wj = jax.lax.dynamic_index_in_dim(
                        wg_k, j, axis=1, keepdims=False
                    )[:, None]
                    on_dag = jnp.minimum(dp[ids] + wj, INF) == dv
                    return a | (m[ids] & on_dag & reach)

                acc = jax.lax.fori_loop(0, dk, j_step, acc)
            parts.append(acc)
            end = bs + nk
        if end < n:
            parts.append(m[end:])
        new_m = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
        return new_m, jnp.any(new_m != m), it + 1

    def cond(state):
        _, changed, it = state
        return changed & (it < n)

    # zero increased edges -> zero seed marks -> the loop is skipped whole,
    # so decrease-only events pay nothing for sharing this executable
    marks, _, rounds = jax.lax.while_loop(
        cond, body, (marks, jnp.any(marks), 0)
    )
    return marks, rounds


@functools.lru_cache(maxsize=64)
def _sell_solver_warm(key: Tuple, mesh=None):
    """Warm-start incremental patch-and-solve, one dispatch per LSDB event.

    (sources, nbrs, wgs, overloaded, patch_idx, patch_vals, inc_idx,
    d_prev) -> (D, new_wgs, rounds, inv_rounds, col_changed, num_changed):
    invalidates the entries of d_prev [S, N] whose old shortest path may
    witness an increased edge (_sell_invalidate, against the OLD weights),
    applies the weight patches, and relaxes from the repaired state instead
    of from INF — rounds scale with the affected radius of the event, not
    the graph diameter. inv_rounds is the invalidation mark fixpoint's own
    round count (0 for decrease-only events, whose empty inc_idx skips the
    loop and warm-starts directly).

    col_changed is a DEVICE-resident bool [N]: destination columns whose
    distance row moved vs d_prev — the DeltaPath seed. num_changed is its
    scalar popcount; the host reads only that int (4 bytes) and then sizes
    a compacted `_delta_extract` dispatch, so the per-event copy-back is
    O(changes), never the [S, N] mirror. All patch shapes are fixed
    (_PATCH_SLOTS per bucket) so one executable serves every event; d_prev
    and the weight buffers are donated since the caller always replaces
    its handles."""
    zero_end, starts, shapes = key

    def solve(
        sources, nbrs, wgs, overloaded, patch_idx, patch_vals, inc_idx, d_prev
    ):
        s = sources.shape[0]
        dp = d_prev.T  # dest-major [N, S], like the relaxation state
        marks, inv_rounds = _sell_invalidate(
            dp, nbrs, wgs, inc_idx, zero_end, starts, shapes
        )
        new_wgs = _sell_apply_patches(wgs, patch_idx, patch_vals)
        d0 = jnp.where(marks, INF, dp)
        d0 = d0.at[sources, jnp.arange(s)].set(0)  # re-pin marked sources
        _, allow = _sell_d0_allow(sources, overloaded)
        d, rounds = _sell_relax(
            d0, allow, nbrs, new_wgs, zero_end, starts, shapes
        )
        col_changed = jnp.any(d != dp, axis=1)  # dest-major: [N]
        num_changed = jnp.sum(col_changed, dtype=jnp.int32)
        return d.T, new_wgs, rounds, inv_rounds, col_changed, num_changed

    if mesh is None:
        return jax.jit(solve, donate_argnums=(2, 7))
    row, repl, out = _mesh_shardings(mesh)
    return jax.jit(
        solve,
        donate_argnums=(2, 7),
        in_shardings=(row, repl, repl, repl, repl, repl, repl, out),
        out_shardings=(out, repl, repl, repl, repl, repl),
    )


def _bf_warm_core(
    sources: jnp.ndarray,  # int32 [S]
    src_e: jnp.ndarray,  # int32 [E]
    dst_e: jnp.ndarray,  # int32 [E] (sorted ascending)
    w_new: jnp.ndarray,  # int32 [E] weights after the event
    w_old: jnp.ndarray,  # int32 [E] weights that produced d_prev
    overloaded: jnp.ndarray,  # bool [N]
    d_prev: jnp.ndarray,  # int32 [S, N] previous fixpoint (donated)
):
    """Warm-start solve on the edge-list (non sliced-ELL) layout: the same
    Ramalingam–Reps-style recipe as _sell_solver_warm, but with the
    increased-edge set derived on device from w_new > w_old instead of a
    host-built index patch (the edge-list form has no fixed-width slot
    structure to patch into; uploading the [E] weight vector per event is
    the layout's native cost anyway).

    Seed marks where an increased edge sits on the old shortest-path DAG
    (triangle condition against w_old), propagate marks down the old DAG
    with a boolean segment-max fixpoint, reset marked entries to INF, then
    relax from the repaired state with the new weights. Returns
    (d, rounds, inv_rounds, col_changed [N] bool, num_changed) — the same
    delta outputs as the sliced path, so `_delta_extract` serves both."""
    n = overloaded.shape[0]
    s = sources.shape[0]
    dp = d_prev
    du = dp[:, src_e]  # [S, E]
    dv = dp[:, dst_e]
    on_old = (jnp.minimum(du + w_old[None, :], INF) == dv) & (dv < INF)
    seeds = on_old & (w_new > w_old)[None, :]

    def seg_any(rows):  # bool [S, E] -> bool [S, N] (OR per destination)
        return (
            jax.vmap(
                lambda row: jax.ops.segment_max(
                    row.astype(jnp.int32),
                    dst_e,
                    num_segments=n,
                    indices_are_sorted=True,
                )
            )(rows)
            > 0
        )

    marks0 = seg_any(seeds)

    def body(state):
        m, _, it = state
        new_m = m | seg_any(m[:, src_e] & on_old)
        return new_m, jnp.any(new_m != m), it + 1

    def cond(state):
        _, changed, it = state
        return changed & (it < n)

    # zero increased edges -> zero seed marks -> the loop is skipped whole
    marks, _, inv_rounds = jax.lax.while_loop(
        cond, body, (marks0, jnp.any(marks0), 0)
    )
    d0 = jnp.where(marks, INF, dp)
    d0 = d0.at[jnp.arange(s), sources].set(0)  # re-pin marked sources
    allow = _bf_allow(sources, overloaded)
    d, rounds = _bf_relax(d0, allow, src_e, dst_e, w_new[None, :])
    col_changed = jnp.any(d != dp, axis=0)  # row-major: reduce sources
    num_changed = jnp.sum(col_changed, dtype=jnp.int32)
    return d, rounds, inv_rounds, col_changed, num_changed


_bf_solver_warm = jax.jit(_bf_warm_core, donate_argnums=(6,))


def _bf_warm_vw_core(
    sources: jnp.ndarray,  # int32 [S]
    src_e: jnp.ndarray,  # int32 [E]
    dst_e: jnp.ndarray,  # int32 [E] (sorted ascending)
    w_rows: jnp.ndarray,  # int32 [S, E] per-row weights after the event
    w_base: jnp.ndarray,  # int32 [E] shared weights that produced d_prev
    overloaded: jnp.ndarray,  # bool [N]
    d_prev: jnp.ndarray,  # int32 [S, N] base fixpoint (NOT donated)
):
    """Per-row-weights warm solve on the edge-list layout: the KSP
    layer-seeding form of _bf_warm_core. Every per-row weight change is an
    INCREASE (link-ignore masks pin base weights to INF), so each batch
    row warm-starts from the shared unpenalized base fixpoint: seed marks
    where a row's masked edge sits on the base DAG, propagate down the
    base DAG, reset, and relax with the per-row weights. d_prev is a
    broadcast view of the resident base row, so it is not donated."""
    n = overloaded.shape[0]
    s = sources.shape[0]
    dp = d_prev
    du = dp[:, src_e]  # [S, E]
    dv = dp[:, dst_e]
    on_old = (jnp.minimum(du + w_base[None, :], INF) == dv) & (dv < INF)
    seeds = on_old & (w_rows > w_base[None, :])

    def seg_any(rows):  # bool [S, E] -> bool [S, N] (OR per destination)
        return (
            jax.vmap(
                lambda row: jax.ops.segment_max(
                    row.astype(jnp.int32),
                    dst_e,
                    num_segments=n,
                    indices_are_sorted=True,
                )
            )(rows)
            > 0
        )

    marks0 = seg_any(seeds)

    def body(state):
        m, _, it = state
        new_m = m | seg_any(m[:, src_e] & on_old)
        return new_m, jnp.any(new_m != m), it + 1

    def cond(state):
        _, changed, it = state
        return changed & (it < n)

    marks, _, inv_rounds = jax.lax.while_loop(
        cond, body, (marks0, jnp.any(marks0), 0)
    )
    d0 = jnp.where(marks, INF, dp)
    d0 = d0.at[jnp.arange(s), sources].set(0)  # re-pin marked sources
    allow = _bf_allow(sources, overloaded)
    d, rounds = _bf_relax(d0, allow, src_e, dst_e, w_rows)
    return d, rounds, inv_rounds


_bf_solver_warm_vw = jax.jit(_bf_warm_vw_core)


# -- destination-tiled 2-D P('batch', 'graph') kernels ----------------------
#
# The row-sharded layouts above keep a full [S, n_pad] distance replica per
# chip; the tiled kernels below keep only a [S/batch, n_pad/graph] tile and
# run under shard_map over both mesh axes. Edges are regrouped by SOURCE
# tile (openr_tpu/parallel/mesh.py:GraphTiling), so every tail read in a
# relaxation round is tile-local; the per-round cross-chip traffic is the
# halo exchange: each device's compact per-destination frontier minima
# (ctr [S_l, h] plus the slot->column map) travel one hop at a time around
# a lax.ppermute ring along 'graph', and every device scatter-mins the
# passing frontier into the columns it owns, dropping the rest. Nothing the
# size of a distance row ever moves.


@shape_contract(
    "tile:[S_l,n_tile]:int32:inf",
    "ctr:[S_l,h]:int32:inf",
    "cols:[h]:int32",
    returns="[S_l,n_tile]:int32:inf",
)
def _tile_fold_min(tile, ctr, cols, me, n_tile):
    """Fold a frontier into the columns this device owns: cols outside
    [me*n_tile, (me+1)*n_tile) map to the out-of-range sentinel and are
    dropped by the scatter (sentinel 1<<30 padding slots included)."""
    local = cols - me * n_tile
    local = jnp.where((local >= 0) & (local < n_tile), local, n_tile)
    return tile.at[:, local].min(ctr, mode="drop")


def _tile_halo_min(ctr, cols, base, me, n_tile, g):
    """The halo exchange: fold every partition's frontier (ctr [S_l, h],
    cols [h]) into `base` [S_l, n_tile], rotating the frontier g-1 hops
    around the 'graph' ring. Returns the folded tile; per hop each device
    forwards only its compact frontier — O(h) per device, never O(n_pad)."""
    perm = [(i, (i + 1) % g) for i in range(g)]
    out = _tile_fold_min(base, ctr, cols, me, n_tile)
    for _ in range(g - 1):
        ctr = jax.lax.ppermute(ctr, "graph", perm)
        cols = jax.lax.ppermute(cols, "graph", perm)
        out = _tile_fold_min(out, ctr, cols, me, n_tile)
    return out


@shape_contract(
    "vals:[S_l,e_tile]:int32:inf",
    "hseg:[e_tile]:int32",
    returns="[S_l,h]:int32:inf",
)
def _tile_seg_min(vals, hseg, h):
    """Per-frontier-slot minima of per-edge values [S_l, e_tile] -> [S_l, h]
    (empty slots clamp to INF; hseg is per-tile dst-sorted, so the sorted
    fast path holds)."""
    out = jax.vmap(
        lambda row: jax.ops.segment_min(
            row, hseg, num_segments=h, indices_are_sorted=True
        )
    )(vals)
    return jnp.minimum(out, INF)


def _tile_d0_allow(sources, overloaded, me, n_tile):
    """Cold initial tile [S_l, n_tile] + the per-source transit mask for
    the columns this device owns (overloaded nodes relay nothing unless
    they are the source itself — same semantics as _bf_allow)."""
    s_l = sources.shape[0]
    offset = me * n_tile
    ov_t = jax.lax.dynamic_slice(overloaded, (offset,), (n_tile,))
    ids = offset + jnp.arange(n_tile, dtype=jnp.int32)
    allow = (~ov_t)[None, :] | (ids[None, :] == sources[:, None])
    local = sources - offset
    local = jnp.where((local >= 0) & (local < n_tile), local, n_tile)
    d0 = jnp.full((s_l, n_tile), INF, dtype=jnp.int32)
    d0 = d0.at[jnp.arange(s_l), local].set(0, mode="drop")
    return d0, allow


@shape_contract(
    "d0:[S_l,n_tile]:int32:inf",
    "allow:[S_l,n_tile]:bool",
    "src_l:[e_tile]:int32",
    "hseg:[e_tile]:int32",
    "w2:[e_tile]:int32:inf",
    "hcols:[h]:int32",
)
def _tile_relax(d0, allow, src_l, hseg, w2, hcols, me, *, g, n_tile, n_pad):
    """Min-plus relaxation of the local tile to the GLOBAL fixpoint.

    Each round relaxes the locally-tailed edges (src_l is tile-local by
    construction) into compact frontier minima and halo-exchanges them;
    convergence is the psum of per-device change flags over both mesh
    axes, so every device leaves the loop in lockstep. Same warm-start
    contract as _sell_relax/_bf_relax: any entrywise upper bound of the
    true distances with the source diagonal pinned to 0 is a valid d0."""
    h = hcols.shape[0]

    def body(state):
        d, _, it = state
        dt = jnp.where(allow, d, INF)
        contrib = jnp.minimum(dt[:, src_l] + w2, INF)  # [S_l, e_tile]
        ctr = _tile_seg_min(contrib, hseg, h)
        new_d = _tile_halo_min(ctr, hcols, d, me, n_tile, g)
        changed = (
            jax.lax.psum(
                jnp.any(new_d != d).astype(jnp.int32), ("batch", "graph")
            )
            > 0
        )
        return new_d, changed, it + 1

    def cond(state):
        _, changed, it = state
        return changed & (it < n_pad)

    d, _, rounds = jax.lax.while_loop(cond, body, (d0, jnp.bool_(True), 0))
    return d, rounds


@functools.lru_cache(maxsize=64)
def _tile_solver(key: Tuple, mesh):
    """Cold destination-tiled solve: key = GraphTiling.shape_key() +
    (n_pad,). (sources, src_l, hseg, w2, hcols, overloaded) -> (D, rounds)
    with D sharded P('batch', 'graph') — each device keeps only its
    [S/batch, n_pad/graph] tile."""
    from jax.sharding import PartitionSpec as P

    g, n_tile, e_tile, h, n_pad = key
    assert mesh.shape["graph"] == g, (dict(mesh.shape), g)

    def solve(sources, src_l, hseg, w2, hcols, overloaded):
        me = jax.lax.axis_index("graph")
        d0, allow = _tile_d0_allow(sources, overloaded, me, n_tile)
        d, rounds = _tile_relax(
            d0, allow, src_l[0], hseg[0], w2[0], hcols[0], me,
            g=g, n_tile=n_tile, n_pad=n_pad,
        )
        return d, rounds

    fn = jax.shard_map(
        solve,
        mesh=mesh,
        in_specs=(
            P("batch"),
            P("graph", None),
            P("graph", None),
            P("graph", None),
            P("graph", None),
            P(),
        ),
        out_specs=(P("batch", "graph"), P()),
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _tile_solver_warm(key: Tuple, mesh):
    """Warm-start incremental solve on the tiled layout, one dispatch per
    LSDB event: (sources, src_l, hseg, w2_new, w2_old, hcols, ov_new,
    ov_old, d_prev) -> (D, rounds, inv_rounds, col_changed, num_changed).

    The invalidation fixpoint is halo-aware: marks cannot be pushed along
    edges directly (a tail's owner does not hold the head's column), so
    the old-DAG membership test runs RECEIVER-side on the same frontier
    machinery as the relaxation. At the old fixpoint every masked tail
    value satisfies dt_old[u] + w_old >= dp[v], so the min over any edge
    subset's candidates equals dp[v] exactly when the subset contains an
    old-DAG edge: each round the devices exchange per-destination minima
    of dt_old[u] + w_old over marked-tail edges and a device marks the
    columns where the received min matches its resident dp. Seeds use the
    same test over the increased-edge set — weight increases derived on
    device from w2_new > w2_old, plus the out-edges of newly-overloaded
    nodes, which is how an overload toggle rides the warm path here too
    (the repair relax then uses the NEW transit mask). Un-overloading
    only adds paths, so the old tile stays a valid upper bound as-is.

    col_changed comes back sharded P('graph') (each device reports its
    own columns, reduced over 'batch'); num_changed is the replicated
    scalar popcount the host reads to size the compacted _delta_extract
    dispatch — the DeltaPath handshake is unchanged by the resharding."""
    from jax.sharding import PartitionSpec as P

    g, n_tile, e_tile, h, n_pad = key
    assert mesh.shape["graph"] == g, (dict(mesh.shape), g)

    def solve(
        sources, src_l, hseg, w2_new, w2_old, hcols, ov_new, ov_old, d_prev
    ):
        me = jax.lax.axis_index("graph")
        src = src_l[0]
        seg = hseg[0]
        wn = w2_new[0]
        wo = w2_old[0]
        cols = hcols[0]
        s_l = sources.shape[0]
        offset = me * n_tile
        _, allow_old = _tile_d0_allow(sources, ov_old, me, n_tile)
        _, allow_new = _tile_d0_allow(sources, ov_new, me, n_tile)
        dp = d_prev
        dt_old = jnp.where(allow_old, dp, INF)
        # per-edge old-DAG candidates; down edges (w_old == INF) clamp to
        # INF and can never match a finite dp[v]
        cand = jnp.minimum(dt_old[:, src] + wo, INF)  # [S_l, e_tile]
        newly_on = ov_new & ~ov_old  # [n_pad] replicated
        seed_edge = (wn > wo) | newly_on[offset + src]
        inf_tile = jnp.full((s_l, n_tile), INF, dtype=jnp.int32)
        ctr0 = _tile_seg_min(jnp.where(seed_edge[None, :], cand, INF), seg, h)
        recv0 = _tile_halo_min(ctr0, cols, inf_tile, me, n_tile, g)
        marks0 = (recv0 == dp) & (dp < INF)

        def body(state):
            m, _, it = state
            vals = jnp.where(m[:, src], cand, INF)
            ctr = _tile_seg_min(vals, seg, h)
            recv = _tile_halo_min(ctr, cols, inf_tile, me, n_tile, g)
            new_m = m | ((recv == dp) & (dp < INF))
            changed = (
                jax.lax.psum(
                    jnp.any(new_m != m).astype(jnp.int32),
                    ("batch", "graph"),
                )
                > 0
            )
            return new_m, changed, it + 1

        def cond(state):
            _, changed, it = state
            return changed & (it < n_pad)

        # zero seed marks everywhere -> the loop is skipped whole, so
        # decrease-only events pay one seed exchange and nothing more
        any_seed = (
            jax.lax.psum(
                jnp.any(marks0).astype(jnp.int32), ("batch", "graph")
            )
            > 0
        )
        marks, _, inv_rounds = jax.lax.while_loop(
            cond, body, (marks0, any_seed, 0)
        )
        d0 = jnp.where(marks, INF, dp)
        local = sources - offset
        local = jnp.where((local >= 0) & (local < n_tile), local, n_tile)
        d0 = d0.at[jnp.arange(s_l), local].set(0, mode="drop")
        d, rounds = _tile_relax(
            d0, allow_new, src, seg, wn, cols, me,
            g=g, n_tile=n_tile, n_pad=n_pad,
        )
        col_changed = jnp.any(d != dp, axis=0)  # [n_tile] this shard
        col_changed = jax.lax.pmax(col_changed.astype(jnp.int32), "batch") > 0
        num_changed = jax.lax.psum(
            jnp.sum(col_changed.astype(jnp.int32)), "graph"
        )
        return d, rounds, inv_rounds, col_changed, num_changed

    fn = jax.shard_map(
        solve,
        mesh=mesh,
        in_specs=(
            P("batch"),
            P("graph", None),
            P("graph", None),
            P("graph", None),
            P("graph", None),
            P("graph", None),
            P(),
            P(),
            P("batch", "graph"),
        ),
        out_specs=(P("batch", "graph"), P(), P(), P("graph"), P()),
        check_vma=False,
    )
    # d_prev is donated: the caller always replaces its resident handle
    # and the output tile matches its shape and sharding exactly
    return jax.jit(fn, donate_argnums=(8,))


@functools.partial(jax.jit, static_argnames=("cap",))
def _delta_extract(
    col_changed: jnp.ndarray,  # bool [N] device-resident changed-dest mask
    d: jnp.ndarray,  # int32 [S, N] device-resident distance matrix
    nh_rows: jnp.ndarray,  # int32 [L] batch row of each up-link neighbor
    nh_ws: jnp.ndarray,  # int32 [L] metric of each up-link from me
    cap: int,  # static: compacted column capacity (power-of-two bucket)
):
    """Compact the changed destinations and recompute the triangle-condition
    nexthop memberships for just those columns — the O(changes) copy-back
    that replaces the full [S, N] mirror fetch on the warm event path.

    Returns (cols [cap] int32 changed-destination indices, fill = N for
    padding; dcols [S, cap] their distance columns; nh [L, cap] bool: link
    l is an ECMP first hop toward cols[c], the exact _AreaSolve.nh_mask
    formula w(me, n) + D[n, t] == D[me, t]). The caller picks cap =
    _next_bucket(num_changed) so a handful of executables (one per
    power-of-two bucket) serve every event size."""
    n = col_changed.shape[0]
    (cols,) = jnp.nonzero(col_changed, size=cap, fill_value=n)
    safe = jnp.clip(cols, 0, n - 1)
    dcols = d[:, safe]  # [S, cap]
    nh = (nh_ws[:, None] + dcols[nh_rows, :]) == dcols[0][None, :]
    return cols, dcols, nh


@functools.lru_cache(maxsize=64)
def _sell_solver_vw(key: Tuple, mesh=None):
    """Per-row-weights sliced-ELL fixpoint (jitted): the device form of the
    reference's penalized re-solves — KSP's link-ignore runSpf
    (LinkState.cpp:760-789) — on the sliced layout.

    Instead of materializing per-row edge weights host-side ([S, E] ints
    uploaded per call), callers pass the shared bucket weights plus per-
    bucket mask index arrays [Mk, 3] of (row-in-bucket, slot, batch-col)
    positions to pin to INF; out-of-range rows (padding) are dropped. The
    [nk, dk, S] expanded weights are built on device.
    """
    zero_end, starts, shapes = key

    def solve(sources, nbrs, wgs, masks, overloaded):
        s = sources.shape[0]
        wgv = []
        for k, wg_k in enumerate(wgs):
            nk, dk = shapes[k]
            full = jnp.broadcast_to(wg_k[:, :, None], (nk, dk, s))
            m = masks[k]
            full = full.at[m[:, 0], m[:, 1], m[:, 2]].set(INF, mode="drop")
            wgv.append(full)
        return _sell_fixpoint_core(
            sources, nbrs, tuple(wgv), overloaded, zero_end, starts, shapes
        )

    if mesh is None:
        return jax.jit(solve)
    row, repl, out = _mesh_shardings(mesh)
    return jax.jit(
        solve,
        in_shardings=(row, repl, repl, repl, repl),
        out_shardings=out,
    )


@functools.lru_cache(maxsize=64)
def _sell_solver_vw_warm(key: Tuple, mesh=None):
    """Warm per-row-weights sliced-ELL solve: the KSP layer-seeding form.

    (sources, nbrs, wgs, masks, overloaded, d_prev [S, N]) -> D [S, N].
    The mask positions ARE the increased edges (base weight -> INF), so
    the penalized layer-k solve warm-starts from the unpenalized base
    fixpoint d_prev instead of cold-starting from INF: seed invalidation
    marks where a masked edge sits on the base shortest-path DAG (per
    batch column, since each row ignores its own link set), propagate
    them down the base DAG (`_sell_mark_fixpoint`), reset marked entries
    to INF, and relax with the masked per-row weights. Rounds scale with
    the penalized detour radius, not the graph diameter — the KSP
    warm-start carry-over (ROADMAP FatPaths item)."""
    zero_end, starts, shapes = key

    def solve(sources, nbrs, wgs, masks, overloaded, d_prev):
        s = sources.shape[0]
        dp = d_prev.T  # dest-major [N, S]
        marks = jnp.zeros(dp.shape, dtype=jnp.bool_)
        wgv = []
        for k, (nbr_k, wg_k) in enumerate(zip(nbrs, wgs)):
            nk, dk = shapes[k]
            m = masks[k]
            valid = m[:, 0] < (1 << 29)  # padding rows are 1 << 30
            r = jnp.clip(m[:, 0], 0, nk - 1)
            j = jnp.clip(m[:, 1], 0, dk - 1)
            c = jnp.clip(m[:, 2], 0, s - 1)
            u = nbr_k[r, j]  # [M] in-neighbor of each masked edge
            w_old = wg_k[r, j]  # [M] base weight (pre-mask)
            v = starts[k] + r  # [M] global node row of each edge head
            dv = dp[v, c]  # [M]
            cond = (
                valid
                & (dv < INF)
                & (jnp.minimum(dp[u, c] + w_old, INF) == dv)
            )
            marks = marks.at[v, c].max(cond)
            # the masked per-row weights, as in _sell_solver_vw
            full = jnp.broadcast_to(wg_k[:, :, None], (nk, dk, s))
            full = full.at[m[:, 0], m[:, 1], m[:, 2]].set(INF, mode="drop")
            wgv.append(full)
        marks, _ = _sell_mark_fixpoint(
            dp, marks, nbrs, wgs, zero_end, starts, shapes
        )
        d0 = jnp.where(marks, INF, dp)
        d0 = d0.at[sources, jnp.arange(s)].set(0)  # re-pin marked sources
        _, allow = _sell_d0_allow(sources, overloaded)
        d, _ = _sell_relax(
            d0, allow, nbrs, tuple(wgv), zero_end, starts, shapes
        )
        return d.T

    if mesh is None:
        return jax.jit(solve)
    row, repl, out = _mesh_shardings(mesh)
    return jax.jit(
        solve,
        in_shardings=(row, repl, repl, repl, repl, out),
        out_shardings=out,
    )


def sell_fixpoint_masked(
    sell,  # ops.graph.SlicedEll
    sources,  # int32 [S]
    overloaded,  # bool [n_pad]
    mask_positions,  # per batch row: list of edge positions to pin to INF
    device_arrays=None,  # optional (nbrs, wgs, ov) already on device
    mesh=None,  # optional solver mesh: sources sharded over 'batch'
    d_prev=None,  # optional [S, N] base fixpoint: warm-start the solve
) -> jnp.ndarray:
    """Per-row link-ignore solve on the sliced layout.

    mask_positions[i] is an iterable of edge positions (dst-sorted edge
    array indices, e.g. from CompiledGraph.link_edges) whose weight becomes
    INF for batch row i only. Mask arrays are bucket-padded so repeated
    calls with similar mask counts share jitted executables. Pass
    device_arrays (e.g. an _AreaSolve's persistent buffers) to avoid
    re-uploading the layout per call. With d_prev — the UNPENALIZED base
    distance rows for the same sources and weights — the penalized solve
    warm-starts via increase-invalidation instead of relaxing from INF
    (`_sell_solver_vw_warm`): sound because masking only raises weights,
    so the base fixpoint plus mark-reset is a valid upper-bound seed.
    """
    nb = len(sell.nbr)
    per_bucket: list = [[] for _ in range(nb)]
    for col, positions in enumerate(mask_positions):
        for p in positions:
            per_bucket[sell.edge_bucket[p]].append(
                (sell.edge_row[p], sell.edge_slot[p], col)
            )
    masks = []
    for k in range(nb):
        entries = per_bucket[k]
        m_pad = _next_bucket(max(len(entries), 1))
        arr = np.full((m_pad, 3), 1 << 30, dtype=np.int32)  # dropped rows
        if entries:
            arr[: len(entries)] = np.asarray(entries, dtype=np.int32)
        masks.append(jnp.asarray(arr))
    if device_arrays is not None:
        nbrs, wgs, ov = device_arrays
    else:
        nbrs = tuple(jnp.asarray(a) for a in sell.nbr)
        wgs = tuple(jnp.asarray(a) for a in sell.wg)
        ov = jnp.asarray(overloaded)
    if d_prev is not None:
        fn = _sell_solver_vw_warm(sell.shape_key(), mesh)
        with stage("spf.ksp_masked_warm"):
            return fn(
                jnp.asarray(sources, dtype=jnp.int32),
                nbrs,
                wgs,
                tuple(masks),
                ov,
                d_prev,
            )
    fn = _sell_solver_vw(sell.shape_key(), mesh)
    with stage("spf.ksp_masked"):
        return fn(
            jnp.asarray(sources, dtype=jnp.int32), nbrs, wgs, tuple(masks), ov
        )



def sell_fixpoint(
    sell,  # ops.graph.SlicedEll
    sources,  # int32 [S] device or host
    wgs,  # tuple of [nk, dk] weight arrays (device or host)
    overloaded,  # bool [n_pad]
) -> jnp.ndarray:
    """Distance matrix D [S, N] via the sliced-ELL pull relaxation."""
    fn = _sell_solver(sell.shape_key(), None)
    with stage("spf.sell_fixpoint"):
        return fn(
            jnp.asarray(sources, dtype=jnp.int32),
            tuple(jnp.asarray(a) for a in sell.nbr),
            tuple(jnp.asarray(a) for a in wgs),
            jnp.asarray(overloaded),
        )


def batched_spf(graph: CompiledGraph, source_rows: np.ndarray) -> jnp.ndarray:
    """Run the batched solve for the given source node indices.

    Dispatches to the sliced-ELL pull kernel when the graph's degree
    profile qualifies (ops.graph._build_sell), else the edge-list
    segment-min form.
    """
    # named fault seam for injected dispatch failures (docs/Robustness.md)
    fault_point("ops.spf.batched_spf", graph)
    if graph.sell is not None:
        return sell_fixpoint(
            graph.sell, source_rows, graph.sell.wg, graph.overloaded
        )
    with stage("spf.batched_cold"):
        return _bf_fixpoint(
            jnp.asarray(source_rows, dtype=jnp.int32),
            jnp.asarray(graph.src),
            jnp.asarray(graph.dst),
            jnp.asarray(graph.w),
            jnp.asarray(graph.overloaded),
        )


def batched_spf_vw(
    graph: CompiledGraph, source_rows: np.ndarray, w_rows: np.ndarray,
    mesh=None,
) -> jnp.ndarray:
    """Batched solve with per-row weight vectors (shape [S, e_pad]).

    With a mesh, sources and weight rows shard over 'batch' (S must be a
    multiple of the batch-axis size)."""
    fault_point("ops.spf.batched_spf_vw", graph)
    with stage("spf.batched_vw"):
        return _bf_vw_solver(mesh)(
            jnp.asarray(source_rows, dtype=jnp.int32),
            jnp.asarray(graph.src),
            jnp.asarray(graph.dst),
            jnp.asarray(w_rows, dtype=jnp.int32),
            jnp.asarray(graph.overloaded),
        )


@jax.jit
def _ecmp_dag(
    d: jnp.ndarray,  # int32 [N, N] all-pairs distances (row = source)
    src_e: jnp.ndarray,
    dst_e: jnp.ndarray,
    w_e: jnp.ndarray,
    overloaded: jnp.ndarray,
) -> jnp.ndarray:
    """Per-edge shortest-DAG membership: out[e, t] == True iff directed edge
    e = (u -> v) is the first hop of some shortest path u -> t."""
    n = overloaded.shape[0]
    node_ids = jnp.arange(n, dtype=jnp.int32)
    du = d[src_e]  # [E, N] distances from each edge's source
    dv = d[dst_e]  # [E, N] distances from each edge's destination
    triangle = jnp.minimum(w_e[:, None] + dv, INF) == du
    # v may not relay traffic when overloaded, unless v is the destination
    transit_ok = (~overloaded[dst_e])[:, None] | (
        node_ids[None, :] == dst_e[:, None]
    )
    reachable = du < INF
    return triangle & transit_ok & reachable


def ecmp_dag(graph: CompiledGraph, d: jnp.ndarray) -> jnp.ndarray:
    """First-hop DAG for all-pairs distance matrix d (rows must be indexed by
    node id, i.e. computed with source_rows = arange(n_pad))."""
    return _ecmp_dag(
        d,
        jnp.asarray(graph.src),
        jnp.asarray(graph.dst),
        jnp.asarray(graph.w),
        jnp.asarray(graph.overloaded),
    )


def compile_cache_stats() -> dict:
    """Aggregate executable-cache totals across the jitted solver factories.

    Each factory's lru_cache is keyed by (SlicedEll.shape_key(), mesh), so a
    miss is one new trace+XLA compile for a new bucket structure and a hit
    is an executable reused across LSDB events — the shape-bucketing design
    working as intended. `_delta_extract`'s executables, one per power-of-two
    bucket of changed columns, count among the misses: a deployment whose
    events are of many sizes (a WAN: 8 buckets) meets a new one in steady
    state, and pays for it there. TpuSpfSolver surfaces these as the
    decision.spf.compile_cache_{hits,misses} gauges (process-wide: the
    caches are module-level, shared by every solver instance)."""
    hits = misses = entries = 0
    for fn in (
        _sell_solver_raw,
        _sell_solver,
        _sell_solver_counted,
        _sell_solver_patched,
        _sell_solver_warm,
        _sell_solver_vw,
        _sell_solver_vw_warm,
        _bf_vw_solver,
        _tile_solver,
        _tile_solver_warm,
    ):
        info = fn.cache_info()
        hits += info.hits
        misses += info.misses
        entries += info.currsize
    # the DeltaPath extraction is one jitted function with an executable
    # per (shapes, cap bucket): the first event of each size paid a trace
    # and a compile for it, a miss like the factories'. jit keeps no hit
    # count, so its reuse is not among the hits
    extract = _delta_extract._cache_size()
    return {
        "hits": hits,
        "misses": misses + extract,
        "entries": entries + extract,
    }


def compile_cache_memory() -> dict:
    """Device-memory ledger external source (monitor/memledger.py): the
    compiled executables are device-resident state too, but they live
    behind module-level lru_caches the ledger does not allocate — so they
    ride snapshots as an informational row (entry counts per family +
    the APSP closer's caches) outside the exact-accounting invariant."""
    from openr_tpu.apsp import apsp_compile_cache_stats

    stats = compile_cache_stats()
    fw = apsp_compile_cache_stats()
    return {
        "structure": "compile_cache",
        "spf_entries": stats["entries"],
        "apsp_entries": fw["entries"],
        "entries": stats["entries"] + fw["entries"],
    }


