"""Traffic kind `prefix_swap`: the withdrawn /24 moves.

At any time exactly one node of the cell's candidates does not announce
its /24; every other node announces its own. One event is one KvStore
write of two `prefix:` keys that announces the /24 that was withdrawn and
withdraws another candidate's: the vantage deletes one route and adds one,
and no link moves, so Decision has nothing to solve. Every such event is of
this one kind, so the latencies have one mode and a median means something.

The cell names its candidates as `nodes`, patterns like
`{"node": "rsw{p}_{r}", "ranges": {"p": [1, 172], "r": [0, 47]}}`: every
combination of the inclusive ranges, as in `link_metric_swap.expand`. They come in blocks: a block is every
candidate once, in an order shuffled from the seed. So every seed sends the
same nodes in another order, and with more candidates than a run has events
no two events of a run leave the LSDB in the same state.

`link_event_every` (0 or absent: never) puts an event of kind
`link_metric_swap` over the cell's `groups` in the place of every that-many-th
event of the stream, the first included: a traced second then holds work of
the device, which a stream of prefix events alone never asks for.

Parameters: `nodes` and `groups` (the cell's file); `link_event_every`,
`high`, `low` (the mix's file).
"""

from __future__ import annotations

import itertools
import random
from typing import Iterator, List, Optional

from chipbench.lsdb import Lsdb
from chipbench.traffic_kinds import link_metric_swap


class PrefixSwap:
    """One event: `announce` (if any) has its /24 back, `withdraw` loses it."""

    def __init__(self, announce: Optional[str], withdraw: str) -> None:
        self.announce, self.withdraw = announce, withdraw

    def apply(self, lsdb: Lsdb) -> List[str]:
        """Mutates `lsdb`; returns the KvStore keys that changed."""
        nodes: List[str] = []
        if self.announce is not None:
            nodes += lsdb.set_announced(self.announce, True)
        nodes += lsdb.set_announced(self.withdraw, False)
        return [f"prefix:{n}" for n in nodes]

    def __repr__(self) -> str:
        return f"{self.withdraw} withdraws, {self.announce or 'nobody'} announces"


def expand(group: dict) -> List[str]:
    """A group's pattern -> its nodes, in `link_metric_swap.expand`'s language."""
    pattern = group["node"]
    return [a for a, _ in link_metric_swap.expand(dict(group, a=pattern, b=pattern))]


def blocks(candidates: list, rng: random.Random) -> Iterator:
    """Endlessly: every candidate once, in an order shuffled anew per block."""
    while True:
        block = list(candidates)
        rng.shuffle(block)
        yield from block


def generate(params: dict, seed: int) -> Iterator[object]:
    """Endless event stream; the same `seed` gives the same stream."""
    deck = blocks([n for g in params["nodes"] for n in expand(g)], random.Random(seed))
    every = int(params.get("link_event_every") or 0)
    link_events = link_metric_swap.generate(params, seed) if every else None
    current: Optional[str] = None
    for turn in itertools.count():
        if every and turn % every == 0:
            yield next(link_events)
            continue
        node = next(deck)
        while node == current:  # a block's last may be the next block's first
            node = next(deck)
        yield PrefixSwap(current, node)
        current = node
