"""The platform agent of a run: where the timed path ends.

The program's in-memory `FibService` (the one its own tests program
against), with every programming call stamped on the host's monotonic
clock and logged. `unicast_routes` / `mpls_routes` are what was
programmed; `log` is every call in order, holding the very route lists Fib
handed over (they are immutable, so keeping them copies nothing).
"""

from __future__ import annotations

import asyncio
import time
from typing import List, Tuple

from openr_tpu.platform import MockFibHandler

_STAMPED = (
    "add_unicast_routes",
    "delete_unicast_routes",
    "sync_fib",
    "add_mpls_routes",
    "delete_mpls_routes",
    "sync_mpls_fib",
)


class StampingAgent(MockFibHandler):
    def __init__(self) -> None:
        super().__init__()
        # (stamp, call name, payload) for every programming call
        self.log: List[Tuple[float, str, list]] = []
        self.programmed = asyncio.Event()  # set on every programming call

    def _stamp(self, name: str, payload: list) -> None:
        self.log.append((time.perf_counter(), name, payload))
        self.programmed.set()


def _stamped(name: str):
    inner = getattr(MockFibHandler, name)

    async def call(self, client_id, payload):
        await inner(self, client_id, payload)
        self._stamp(name, payload)

    call.__name__ = name
    return call


for _name in _STAMPED:
    setattr(StampingAgent, _name, _stamped(_name))
