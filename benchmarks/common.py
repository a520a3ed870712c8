"""Shared benchmark helpers: raw edge-list compilation and device timing.

The timing methodology matches bench.py: R independent solves are chained
inside one jitted lax.scan (a data dependency folds each result into a
carry so no solve can be elided), and throughput is the marginal time
between a short and a long chain — this cancels the fixed dispatch/sync
latency of one host round trip.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Dict, List, Sequence, Tuple

import numpy as np

from openr_tpu.ops.graph import INF  # noqa: F401  (re-exported for benches)
from openr_tpu.ops.graph import compile_edges as graph_compile_edges

Edge = Tuple[str, str, int]


def compile_edges(
    edges: Sequence[Edge],
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, Dict[str, int]]:
    """Edge list -> padded (src, dst, w, overloaded, node_index) arrays.

    Thin wrapper over ops.graph.compile_edges (the numpy-vectorized fast
    path) for the edge-list-form benchmark consumers; node ids follow its
    in-degree renumbering, which consumers must reach through node_index.
    """
    graph = graph_compile_edges(edges)
    return graph.src, graph.dst, graph.w, graph.overloaded, graph.node_index


def time_marginal(run, reps_small: int, reps_big: int, rounds: int = 3) -> float:
    """Median marginal seconds/rep between a short and a long chained run.

    `run(reps)` must block until the device is done. The median of the
    positive per-round marginals is reported — taking the minimum would
    systematically favor rounds where sync jitter happened to inflate the
    short chain and deflate the long one.

    When EVERY round's marginal is non-positive (jitter swamped the
    chain-length delta) there is no measurement: raises, so the caller
    lengthens the chains instead of reporting a number under another
    method.
    """
    run(reps_small)  # compile/warm
    run(reps_big)
    marginals = []
    for _ in range(rounds):
        t0 = time.time()
        run(reps_small)
        t_small = time.time() - t0
        t0 = time.time()
        run(reps_big)
        t_big = time.time() - t0
        marginal = (t_big - t_small) / (reps_big - reps_small)
        if marginal > 0:  # noise guard: jitter can invert tiny pairs
            marginals.append(marginal)
    if not marginals:
        raise RuntimeError(
            f"time_marginal: all {rounds} round marginals non-positive "
            f"({reps_small} vs {reps_big} reps); lengthen the chains"
        )
    return float(np.median(marginals))


def emit(result: dict) -> None:
    """One JSON result line to stdout."""
    print(json.dumps(result), flush=True)


def note(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
