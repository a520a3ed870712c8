"""Traffic kind `link_metric_swap`: the degraded link moves.

At any time exactly one link of the cell's candidates carries a metric of
`high`; every other link is at its configured metric. One event is one
KvStore write that restores the link that was high (to `low`) and raises
another candidate, both directions of each: the solve has to invalidate
what ran over the raised link, relax, and take the restored link back in,
and the vantage's routes toward both links' far sides change. Every event
is of this one kind, so the latencies have one mode and a median means
something; and no two events of a run leave the LSDB in the same state,
so nothing keyed on the LSDB's state can answer an event from memory.

The cell names its candidates as `groups`; consecutive events raise a link
of consecutive groups, round robin. A group is a pattern
`{"a": "g0_{k}", "b": "g0_{k1}", "ranges": {"k": [1, 33]}}`: every
combination of the inclusive ranges, `{x1}` standing for x + 1. A cell
lists only links whose move changes a programmed route at the vantage
whatever the other candidates' state; the comparison counts an event that
programs nothing as `events_unprogrammed`.

Each group's links come in blocks: a block is every link of the group
once, in an order shuffled from the seed, and a link's metric steps
through `high` from block to block. So every seed sends the same links in
another order, and a (link, metric) pair comes again only after
`len(high)` blocks.

Parameters: `groups` (the cell's file); `high`, `low` (the mix's file).
"""

from __future__ import annotations

import itertools
import random
from typing import Iterator, List, Optional, Tuple

from chipbench.lsdb import Lsdb

Link = Tuple[str, str]


class Swap:
    """One event: `restore` (if any) back to `low`, `raised` up to `metric`."""

    def __init__(
        self, restore: Optional[Link], raised: Link, metric: int, low: int
    ) -> None:
        self.restore, self.raised = restore, raised
        self.metric, self.low = metric, low

    def apply(self, lsdb: Lsdb) -> List[str]:
        """Mutates `lsdb`; returns the KvStore keys that changed."""
        nodes: List[str] = []
        if self.restore is not None:
            nodes += lsdb.set_metric(*self.restore, self.low)
        nodes += lsdb.set_metric(*self.raised, self.metric)
        return [f"adj:{n}" for n in dict.fromkeys(nodes)]

    def __repr__(self) -> str:
        back = "<->".join(self.restore) if self.restore else "nothing"
        return f"{'<->'.join(self.raised)} -> {self.metric}, {back} -> {self.low}"


def expand(group: dict) -> List[Link]:
    """A group's pattern -> its links."""
    names = sorted(group.get("ranges", {}))
    spans = [range(lo, hi + 1) for lo, hi in (group["ranges"][n] for n in names)]
    links = []
    for values in itertools.product(*spans):
        env = dict(zip(names, values))
        env.update({f"{n}1": v + 1 for n, v in zip(names, values)})
        links.append((group["a"].format(**env), group["b"].format(**env)))
    return links


def _deck(
    links: List[Link], high: List[int], rng: random.Random
) -> Iterator[Tuple[Link, int]]:
    offset = [rng.randrange(len(high)) for _ in links]
    for block in itertools.count():
        order = list(range(len(links)))
        rng.shuffle(order)
        for i in order:
            yield links[i], high[(offset[i] + block) % len(high)]


def generate(params: dict, seed: int) -> Iterator[Swap]:
    """Endless event stream; the same `seed` gives the same stream."""
    rng = random.Random(seed)
    (low,) = params["low"]
    decks = [_deck(expand(g), params["high"], rng) for g in params["groups"]]
    current: Optional[Link] = None
    for turn in itertools.count():
        deck = decks[turn % len(decks)]
        link, metric = next(deck)
        while link == current:  # a new metric on the high link moves no route
            link, metric = next(deck)
        yield Swap(current, link, metric, low)
        current = link
