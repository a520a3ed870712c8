"""3-tier Clos fabric in the shape of DecisionBenchmark.cpp's createFabric:
rsw (rack) - fsw (fabric, per pod) - ssw (spine, per plane). There are as
many planes as a pod has fsw, and fsw `f` of every pod connects to every
ssw of plane `f`. Upstream's constants are 36 ssw per plane and 8 fsw +
48 rsw per pod; its sizes are whole pods: n = 344 is 8 x 36 spines and
one pod, and `pods = (n - 288) div 56` gives 12 pods at n = 1,000 and 84
at n = 5,000."""

from typing import List, Tuple


def edges(
    pods: int,
    ssw_per_plane: int = 36,
    fsw_per_pod: int = 8,
    rsw_per_pod: int = 48,
) -> List[Tuple[str, str, int]]:
    out = []
    for p in range(pods):
        for f in range(fsw_per_pod):
            fsw = f"fsw{p}_{f}"
            for r in range(rsw_per_pod):
                out.append((fsw, f"rsw{p}_{r}", 1))
            for s in range(ssw_per_plane):
                out.append((fsw, f"ssw{f}_{s}", 1))
    return out
