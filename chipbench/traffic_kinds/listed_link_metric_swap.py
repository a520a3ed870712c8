"""Traffic kind `listed_link_metric_swap`: the degraded link moves, over
candidates that the cell lists one by one.

`link_metric_swap` names its candidates by pattern, which a fabric or a grid
allows; the links of a random graph that move a route at the vantage are a
list. The cell gives them as `links`, `[[a, b], ...]`, and they are dealt
from one `link_metric_swap._deck` seeded by the run's seed: blocks of every
link once, in an order shuffled from the seed, a link's metric stepping
through `high` from block to block. The events, what they write and the
keys they return are `link_metric_swap.Swap`'s; an event never raises the
link that is high already.

Parameters: `links` (the cell's file); `high`, `low` (the mix's file).
"""

from __future__ import annotations

import random
from typing import Iterator, Optional

from chipbench.traffic_kinds.link_metric_swap import Link, Swap, _deck


def generate(params: dict, seed: int) -> Iterator[Swap]:
    """Endless event stream; the same `seed` gives the same stream."""
    (low,) = params["low"]
    links = [(a, b) for a, b in params["links"]]
    if len(links) < 2:
        raise ValueError("listed_link_metric_swap needs two candidate links or more")
    current: Optional[Link] = None
    for link, metric in _deck(links, params["high"], random.Random(seed)):
        if link == current:  # a block's last may be the next block's first
            continue
        yield Swap(current, link, metric, low)
        current = link
