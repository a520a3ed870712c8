"""Synthetic WAN: a ring `w0`-`w1`-...-`w{n-1}`-`w0` plus seeded random
chords, every link's metric drawn from 1-100.

Upstream publishes no WAN shape (DecisionBenchmark.cpp has the grid and the
fabric). This one is the repository's own: BASELINE.json config 3, "100k-node
synthetic WAN graph", as `openr_tpu/topology.py:wan_edges` has generated it
since the solver's first tests: the ring keeps the graph connected, then
`n * (degree - 2) / 2` distinct chords between uniformly drawn ends give a
mean degree of `degree` with a skewed spread (2 to 13 at n = 65,536,
degree 4), and one stream of `random.Random(seed)` draws ring metrics, chord
ends and chord metrics in that order. No parallel link, no self-loop. A copy,
as the other generators are: a later change to openr_tpu/topology.py cannot
change the yardstick (tests/chipbench/test_wan.py holds the two equal).
"""

import random
from typing import List, Tuple


def edges(n: int, degree: int = 4, seed: int = 0) -> List[Tuple[str, str, int]]:
    rng = random.Random(seed)
    out = [(f"w{i}", f"w{(i + 1) % n}", rng.randint(1, 100)) for i in range(n)]
    seen = {(min(i, (i + 1) % n), max(i, (i + 1) % n)) for i in range(n)}
    chords = min(n * max(0, degree - 2) // 2, n * (n - 1) // 2 - len(seen))
    while len(out) < n + chords:
        a, b = rng.randrange(n), rng.randrange(n)
        pair = (min(a, b), max(a, b))
        if a == b or pair in seen:
            continue
        seen.add(pair)
        out.append((f"w{a}", f"w{b}", rng.randint(1, 100)))
    return out
