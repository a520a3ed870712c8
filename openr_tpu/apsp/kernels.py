"""Blocked (min,+) Floyd–Warshall kernels for dense all-pairs shortest paths.

The tensorized-FW formulation (PAPERS.md, arXiv:2310.03983) expresses APSP
as blocked tropical "matmuls" that ride the accelerator's matrix tiles
instead of gather/scatter: the [N, N] distance matrix is carved into
MXU-tile-sized B x B blocks (B = 128, the systolic-array edge) and one
classic three-phase sweep closes it exactly —

  for each diagonal stage k:
    1. close block (k, k) under (min,+) self-multiplication,
    2. panel updates: row panel D[k, j] <- min(D[k, j], C_kk (x) D[k, j])
       and column panel D[i, k] <- min(D[i, k], D[i, k] (x) C_kk),
    3. outer-product sweep D[i, j] <- min(D[i, j], D[i, k] (x) D[k, j]).

All arithmetic is int32 with the ops/graph.py INF = 1 << 29 sentinel:
INF + INF = 1 << 30 stays in range, and every (min,+) product clamps back
to INF, so unreachable never wraps (the same convention the batched solver
kernels in ops/spf.py follow).

Transit pruning (overloaded nodes relay nothing unless they are the source
itself, LinkState.cpp:829-836) composes with blocked FW through a LEFT
mask: every product masks its left operand's intermediate columns with the
per-source `allow` matrix (allow[i, k] = not overloaded[k] or k == i).
Shortest paths are simple under metrics >= 1, so a sub-path computed under
its own source's mask never traverses anything a composing source's mask
would forbid — the masked sweep is exact, the same argument the batched
per-source kernels rely on.

The warm **re-close** path serves weight-change events without the full
O(N^3/B^3) sweep:

  - `_fw_seed_solver` marks the rows whose old shortest-path witness may
    traverse an increased edge (the Ramalingam–Reps triangle test
    D[i, u] + w_old + D[v, j] == D[i, j], over-marking is safe), resets
    them to their direct edges, folds the new weight matrix in as an
    entrywise min, and reports which block rows are dirty.
  - `_fw_reclose_solver` runs one re-close round over ONLY the dirty
    block rows/columns: dirty block rows rebuild through every
    intermediate block, and every row relaxes through the dirty blocks as
    intermediates — a round costs O(kb · nb · B^3 · nb) against the full
    sweep's O(nb^3 · B^3), so local events pay ~ (dirty blocks / nb) of a
    cold close. Iterated to a fixpoint this is exact: at the fixpoint
    every (i, j, k) triangle is covered either by a dirty row rule, a
    dirty intermediate rule, or the old matrix's closure (which never
    moved for clean rows).

The numpy mirror `np_floyd_warshall` is the CPU fallback the supervisor's
fault domain degrades to, and the oracle the shadow audit and differential
tests compare against. It is never traced.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from openr_tpu.monitor.spans import stage
from openr_tpu.ops.graph import INF, CompiledGraph
from openr_tpu.utils.shape_contract import shape_contract

# MXU tile edge: blocks are B x B with B = min(128, n_pad); n_pad is a
# power of two (ops/graph.py bucket padding), so B always divides it
_FW_BLOCK = 128

# fixed warm-patch width: events increasing more (u, v) pair minima than
# this fall back to a cold close (the ApspState staleness guard)
_APSP_PATCH_SLOTS = 64


def fw_block_shape(n_pad: int) -> Tuple[int, int]:
    """(nb, bsz): block count and block edge for a padded node count."""
    bsz = min(_FW_BLOCK, n_pad)
    assert n_pad % bsz == 0, (n_pad, bsz)  # bucket padding: power of two
    return n_pad // bsz, bsz


def _to_blocks(x, nb: int, bsz: int):
    """[N, N] -> block-major [nb, nb, B, B]."""
    return x.reshape(nb, bsz, nb, bsz).transpose(0, 2, 1, 3)


def _from_blocks(x4, nb: int, bsz: int):
    """Block-major [nb, nb, B, B] -> [N, N]."""
    return x4.transpose(0, 2, 1, 3).reshape(nb * bsz, nb * bsz)


@shape_contract(
    "a:[B,B]:int32:inf", "b:[B,B]:int32:inf", returns="[B,B]:int32:inf"
)
def _mp(a, b):
    """(min,+) product of a [B, B] tile pair, INF-clamped.

    The tropical analog of one MXU tile matmul: out[i, j] =
    min_m (a[i, m] + b[m, j]); both operands are <= INF so the int32 sum
    never wraps and the clamp keeps unreachable at the sentinel."""
    return jnp.min(jnp.minimum(a[:, :, None] + b[None, :, :], INF), axis=1)


@functools.lru_cache(maxsize=16)
def _fw_solver(key: Tuple):
    """Cold blocked Floyd–Warshall close: key = (nb, bsz).

    (w [N, N] int32 direct-edge matrix with 0 diagonal, allow [N, N] bool
    per-source transit mask) -> (d [N, N], probe scalar). The probe scalar
    is read host-side to force completion so close timing covers device
    execution, matching the batched solver's rounds-output convention."""
    nb, bsz = key
    # log2(B) masked self-multiplications close a B x B block: each
    # squaring doubles the stitched segment count, and within-block paths
    # stitch at most B - 1 segments
    sq = max(bsz.bit_length() - 1, 1)

    def close(w, allow):
        d4 = _to_blocks(w, nb, bsz)
        a4 = _to_blocks(allow, nb, bsz)

        def stage(k, d4):
            diag = d4[k, k]
            adiag = a4[k, k]

            def sq_step(_, c):
                return jnp.minimum(c, _mp(jnp.where(adiag, c, INF), c))

            diag = jax.lax.fori_loop(0, sq, sq_step, diag)
            dmask = jnp.where(adiag, diag, INF)
            rowk = d4[k]  # [nb, B, B]
            colk = d4[:, k]
            ak = a4[:, k]
            row = jax.vmap(lambda bj: jnp.minimum(bj, _mp(dmask, bj)))(rowk)
            col = jax.vmap(
                lambda bi, ai: jnp.minimum(
                    bi, _mp(jnp.where(ai, bi, INF), diag)
                )
            )(colk, ak)
            row = row.at[k].set(diag)
            col = col.at[k].set(diag)
            colm = jnp.where(ak, col, INF)

            # outer-product sweep, one block row of the matrix per step so
            # the [nb, B, B, B] (min,+) intermediates stay bounded
            def outer_i(i, acc):
                upd = jax.vmap(lambda rj: _mp(colm[i], rj))(row)
                return acc.at[i].set(jnp.minimum(acc[i], upd))

            d4 = jax.lax.fori_loop(0, nb, outer_i, d4)
            d4 = d4.at[k, :].set(row)
            d4 = d4.at[:, k].set(col)
            return d4

        d4 = jax.lax.fori_loop(0, nb, stage, d4)
        d = _from_blocks(d4, nb, bsz)
        return d, jnp.min(d)

    fit = jax.jit(close)

    def dispatch(w, allow):
        # named profiling seam: on-demand jax.profiler windows
        # (monitor/profiling.py) show the cold close under this label
        with stage(f"apsp.fw_close.{nb}x{bsz}"):
            return fit(w, allow)

    return dispatch


@functools.lru_cache(maxsize=16)
def _fw_seed_solver(key: Tuple):
    """Warm re-close seed: key = (nb, bsz, p) with p the padded
    increased-pair slot count.

    (d_prev [N, N], w_new [N, N], inc_u [p], inc_v [p], inc_w [p]) ->
    (d0 [N, N], dirty [nb] bool, num_dirty). Rows whose old shortest-path
    witness may traverse an increased (u, v) pair (old pair weight inc_w)
    reset to INF; the new weight matrix folds in as an entrywise min so
    direct edges and every decrease apply; the diagonal stays pinned at 0
    by w_new's zero diagonal. Padding slots carry u = 1 << 30 and drop via
    the in-range test. dirty marks the block rows that differ from d_prev
    (or were reset) — the re-close loop's initial work set."""
    nb, bsz, p = key

    def seed(d_prev, w_new, inc_u, inc_v, inc_w):
        n = d_prev.shape[0]

        def body(i, aff):
            u = inc_u[i]
            v = inc_v[i]
            w_old = inc_w[i]
            ok = u < n
            us = jnp.clip(u, 0, n - 1)
            vs = jnp.clip(v, 0, n - 1)
            du = jax.lax.dynamic_index_in_dim(
                d_prev, us, axis=1, keepdims=False
            )
            dv = jax.lax.dynamic_index_in_dim(
                d_prev, vs, axis=0, keepdims=False
            )
            cand = jnp.minimum(
                jnp.minimum(du[:, None] + w_old, INF) + dv[None, :], INF
            )
            hit = (cand == d_prev) & (d_prev < INF)
            return aff | (ok & jnp.any(hit, axis=1))

        aff = jax.lax.fori_loop(0, p, body, jnp.zeros((n,), jnp.bool_))
        d0 = jnp.where(aff[:, None], INF, d_prev)
        d0 = jnp.minimum(d0, w_new)
        dirty_rows = aff | jnp.any(d0 != d_prev, axis=1)
        dirty = jnp.any(dirty_rows.reshape(nb, bsz), axis=1)
        return d0, dirty, jnp.sum(dirty.astype(jnp.int32))

    fit = jax.jit(seed)

    def dispatch(d_prev, w_new, inc_u, inc_v, inc_w):
        with stage(f"apsp.fw_seed.{nb}x{bsz}"):
            return fit(d_prev, w_new, inc_u, inc_v, inc_w)

    return dispatch


@functools.lru_cache(maxsize=32)
def _fw_reclose_solver(key: Tuple):
    """One warm re-close round: key = (nb, bsz, kb) with kb the padded
    dirty-block capacity (power-of-two bucket, so a handful of executables
    serve every event size).

    (d [N, N], allow [N, N] bool, dirty [nb] bool) ->
    (d_new, dirty_new [nb] bool, num_dirty, changed_blocks). The dirty
    block indices are compacted ON DEVICE (nonzero with a static size);
    rule (a) rebuilds each dirty block row through every intermediate
    block, rule (b) relaxes every row through the dirty blocks as
    intermediates. Dirty only grows (monotone), and a round that changes
    nothing certifies the fixpoint — at that point every (i, j, k)
    triangle is covered by (a) when i is dirty, by (b) when k is dirty,
    and by the previous close's untouched rows otherwise."""
    nb, bsz, kb = key

    def reclose(d, allow, dirty):
        d4 = _to_blocks(d, nb, bsz)
        a4 = _to_blocks(allow, nb, bsz)
        (blk,) = jnp.nonzero(dirty, size=kb, fill_value=nb)
        ok = blk < nb
        safe = jnp.clip(blk, 0, nb - 1)

        # (a) dirty block rows rebuilt through ALL intermediate blocks
        a_rows = d4[safe]  # [kb, nb, B, B]
        a_allow = a4[safe]

        def rebuild(ac, aac):
            def over_k(k, acc):
                left = jnp.where(aac[k], ac[k], INF)
                upd = jax.vmap(lambda bj: _mp(left, bj))(d4[k])
                return jnp.minimum(acc, upd)

            return jax.lax.fori_loop(0, nb, over_k, ac)

        rows_new = jax.vmap(rebuild)(a_rows, a_allow)
        rows_new = jnp.where(ok[:, None, None, None], rows_new, INF)
        d4 = d4.at[safe].min(rows_new)

        # (b) every row relaxes through the dirty blocks as intermediates
        def over_c(c, d4c):
            k = safe[c]
            row_k = jax.lax.dynamic_index_in_dim(
                d4c, k, axis=0, keepdims=False
            )
            col_k = jax.lax.dynamic_index_in_dim(
                d4c, k, axis=1, keepdims=False
            )
            a_k = jax.lax.dynamic_index_in_dim(a4, k, axis=1, keepdims=False)
            colm = jnp.where(a_k, col_k, INF)

            def outer_i(i, acc):
                upd = jax.vmap(lambda rj: _mp(colm[i], rj))(row_k)
                return acc.at[i].set(jnp.minimum(acc[i], upd))

            upd4 = jax.lax.fori_loop(0, nb, outer_i, d4c)
            return jax.lax.cond(ok[c], lambda: upd4, lambda: d4c)

        d4 = jax.lax.fori_loop(0, kb, over_c, d4)
        d_new = _from_blocks(d4, nb, bsz)
        changed_rows = jnp.any(d_new != d, axis=1)
        changed_blocks = jnp.any(changed_rows.reshape(nb, bsz), axis=1)
        dirty_new = dirty | changed_blocks
        return (
            d_new,
            dirty_new,
            jnp.sum(dirty_new.astype(jnp.int32)),
            jnp.sum(changed_blocks.astype(jnp.int32)),
        )

    fit = jax.jit(reclose)

    def dispatch(d, allow, dirty):
        with stage(f"apsp.fw_reclose.{nb}x{bsz}"):
            return fit(d, allow, dirty)

    return dispatch


def build_weight_matrix(graph: CompiledGraph) -> np.ndarray:
    """Dense [n_pad, n_pad] int32 direct-edge matrix from the compiled
    arrays: parallel edges collapse to their pair minimum, down links stay
    at INF (they carry INF in graph.w), the diagonal is 0, and padding
    nodes are isolated (INF rows/columns) so they never perturb real
    distances."""
    n = graph.n_pad
    w = np.full((n, n), INF, dtype=np.int32)
    e = graph.e
    if e:
        np.minimum.at(w, (graph.src[:e], graph.dst[:e]), graph.w[:e])
    np.fill_diagonal(w, 0)
    return w


def build_allow_matrix(overloaded: np.ndarray) -> np.ndarray:
    """[N, N] bool per-source transit mask: allow[i, k] — source i may
    relay through k — unless k is overloaded and k is not i itself (the
    _bf_allow semantics on the all-sources batch)."""
    n = overloaded.shape[0]
    return (~overloaded)[None, :] | np.eye(n, dtype=bool)


def np_floyd_warshall(w: np.ndarray, overloaded: np.ndarray) -> np.ndarray:
    """Numpy masked Floyd–Warshall: the CPU fallback the APSP fault domain
    degrades to, and the shadow-audit / differential-test oracle. One
    vectorized rank-1 relaxation per intermediate k, int64 internally so
    the INF sums cannot wrap, clamped back to the int32 sentinel. Never
    traced (pinned out of the traced set by tests/test_analysis.py)."""
    n = w.shape[0]
    d = w.astype(np.int64).copy()
    np.fill_diagonal(d, 0)
    allow = build_allow_matrix(overloaded)
    big = np.int64(INF)
    for k in range(n):
        dk = np.where(allow[:, k], d[:, k], big)
        d = np.minimum(d, np.minimum(dk[:, None] + d[k][None, :], big))
    return d.astype(np.int32)


def apsp_compile_cache_stats() -> dict:
    """Executable-cache totals for the FW kernel factories, folded into
    `decision.spf.compile_cache_{hits,misses}` next to the batched-solver
    factories (ops/spf.py:compile_cache_stats)."""
    hits = misses = entries = 0
    for fn in (_fw_solver, _fw_seed_solver, _fw_reclose_solver):
        info = fn.cache_info()
        hits += info.hits
        misses += info.misses
        entries += info.currsize
    return {"hits": hits, "misses": misses, "entries": entries}
