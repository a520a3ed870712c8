"""The work a kernel has to do, counted from the configuration alone, and
the chip's peaks to hold it against.

Never from the kernel's padded shapes or its round count: the same work is
read whatever later implements the solve.
"""

from __future__ import annotations

import json
import os
from typing import Dict

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def solve_rows(config: dict) -> int:
    """S: the vantage's own distance row and one per up-neighbour."""
    return 1 + int(config["vantage_up_neighbours"])


def sweep_bytes(config: dict) -> int:
    """Bytes one relaxation sweep of the configuration's graph has to move:
    every directed edge's neighbour index and weight (int32 each), and the
    S distance rows read and written once (int32)."""
    edges = int(config["directed_edges"])
    nodes = int(config["nodes"])
    return edges * 8 + 2 * solve_rows(config) * nodes * 4


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip; an unknown kind is an error."""
    with open(_PEAKS_FILE) as fh:
        table = json.load(fh)["device_kinds"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in {_PEAKS_FILE}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


def sweep_floor_s(config: dict, device_kind: str) -> float:
    """Least seconds the chip could take for one sweep: it is bound by
    memory bandwidth (a sweep does one add and one min per edge and row)."""
    return sweep_bytes(config) / peaks(device_kind)["hbm_bytes_per_s"]
