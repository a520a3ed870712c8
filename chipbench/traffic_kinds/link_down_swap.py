"""Traffic kind `link_down_swap`: the down link moves.

At any time exactly one link of the cell's candidates is down; every other
link is up, at its configured metric. One event is one KvStore write that
brings up the link that was down and takes another candidate down: both
ends' adjacency databases lose or regain the adjacency. With the vantage's
own links as candidates the number of its up neighbours, and with it the
solve's rows, is the same after every event, and every event is of this one
kind.

The cell names its candidates as `groups`, in the pattern language of
`link_metric_swap.expand`. They come in blocks: a block is every candidate
once, in an order shuffled from the seed. A cell with a handful of
candidates sees the same LSDB states again and again: a fixed replayed set,
and its mix has to say so.

Parameters: `groups` (the cell's file).
"""

from __future__ import annotations

import random
from typing import Iterator, List, Optional

from chipbench.lsdb import Lsdb
from chipbench.traffic_kinds.link_metric_swap import Link, expand
from chipbench.traffic_kinds.prefix_swap import blocks


class LinkSwap:
    """One event: `up` (if any) comes back, `down` goes."""

    def __init__(self, up: Optional[Link], down: Link) -> None:
        self.up, self.down = up, down

    def apply(self, lsdb: Lsdb) -> List[str]:
        """Mutates `lsdb`; returns the KvStore keys that changed."""
        nodes: List[str] = []
        if self.up is not None:
            nodes += lsdb.set_link_up(*self.up, True)
        nodes += lsdb.set_link_up(*self.down, False)
        return [f"adj:{n}" for n in dict.fromkeys(nodes)]

    def __repr__(self) -> str:
        back = "<->".join(self.up) if self.up else "nothing"
        return f"{'<->'.join(self.down)} down, {back} up"


def generate(params: dict, seed: int) -> Iterator[LinkSwap]:
    """Endless event stream; the same `seed` gives the same stream."""
    links = [link for g in params["groups"] for link in expand(g)]
    if len(links) < 2:
        raise ValueError("link_down_swap needs two candidate links or more")
    current: Optional[Link] = None
    for link in blocks(links, random.Random(seed)):
        if link == current:  # a block's last may be the next block's first
            continue
        yield LinkSwap(current, link)
        current = link
