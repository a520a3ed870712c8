"""n x n grid, node 'g<row>_<col>' (DecisionBenchmark.cpp's createGrid)."""

from typing import List, Tuple


def edges(n: int, metric: int = 1) -> List[Tuple[str, str, int]]:
    out = []
    for r in range(n):
        for c in range(n):
            if c + 1 < n:
                out.append((f"g{r}_{c}", f"g{r}_{c + 1}", metric))
            if r + 1 < n:
                out.append((f"g{r}_{c}", f"g{r + 1}_{c}", metric))
    return out
