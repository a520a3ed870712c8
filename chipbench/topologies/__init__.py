"""Topology generators, one module each, found by the name in a
configuration's file: `{"generator": "fabric", "args": {...}}` is
`chipbench/topologies/fabric.py`, `edges(**args)`. They are copies: a
later change to openr_tpu/topology.py cannot change the yardstick, and a
deployment of a new shape is a new module here, no edit.

Shapes follow upstream's openr/decision/tests/DecisionBenchmark.cpp.
"""

from __future__ import annotations

import importlib
import re
from typing import List, Tuple

Edge = Tuple[str, str, int]  # (node_a, node_b, metric), undirected


def build_edges(topology: dict) -> List[Edge]:
    """`{"generator": <module>, "args": {...}}` -> edge list."""
    name = str(topology.get("generator"))
    if not re.fullmatch(r"[a-z][a-z0-9_]*", name):
        raise ValueError(f"bad topology generator name {name!r}")
    try:
        module = importlib.import_module(f"chipbench.topologies.{name}")
    except ModuleNotFoundError:
        raise ValueError(
            f"unknown topology generator {name!r}: no chipbench/topologies/{name}.py"
        ) from None
    return module.edges(**topology.get("args", {}))
