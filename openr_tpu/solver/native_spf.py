"""ctypes binding for the native C++ SPF oracle (native/spf/onl_spf.cpp).

This is the rebuild's equivalent of keeping the reference's C++ SpfSolver
around (openr/decision/LinkState.cpp:806-880) as the small-graph fallback
and the honest CPU baseline the TPU batched solver is measured against —
a Python Dijkstra would flatter the TPU numbers.

Operates directly on the CompiledGraph edge arrays (openr_tpu/ops/graph.py),
so link flaps/metric changes are `set_weight` patches, mirroring the device
path's weight-patch incrementality.

Builds openr_tpu/_native/libopenr_spf.so through `make` on first use (a
no-op when the binary is fresh); `native_spf_available()` gates callers,
who fall back to the Python LinkState oracle when the toolchain is
missing.
"""

from __future__ import annotations

import ctypes
import logging
from typing import List, Optional, Set

import numpy as np

from openr_tpu.ops.graph import INF, CompiledGraph
from openr_tpu.utils.native_build import build_native

log = logging.getLogger(__name__)

_lib: Optional[ctypes.CDLL] = None
_load_attempted = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    if _lib is not None or _load_attempted:
        return _lib
    _load_attempted = True
    try:
        lib = ctypes.CDLL(build_native("libopenr_spf.so"))
    except Exception as exc:
        log.warning(
            "native SPF oracle unavailable (%r); Python oracle serves", exc
        )
        return None
    i32p = ctypes.POINTER(ctypes.c_int32)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.onl_spf_create.restype = ctypes.c_void_p
    lib.onl_spf_create.argtypes = [
        ctypes.c_int32,
        ctypes.c_int64,
        i32p,
        i32p,
        i32p,
        ctypes.POINTER(ctypes.c_uint8),
    ]
    lib.onl_spf_destroy.argtypes = [ctypes.c_void_p]
    lib.onl_spf_set_weight.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.c_int32,
    ]
    lib.onl_spf_set_overloaded.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.c_uint8,
    ]
    lib.onl_spf_out_degree.restype = ctypes.c_int32
    lib.onl_spf_out_degree.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.onl_spf_out_neighbors.restype = ctypes.c_int32
    lib.onl_spf_out_neighbors.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        i32p,
        ctypes.c_int32,
    ]
    lib.onl_spf_run.restype = ctypes.c_int64
    lib.onl_spf_run.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        i32p,
        u64p,
        ctypes.c_int32,
    ]
    lib.onl_spf_run_many.restype = ctypes.c_int64
    lib.onl_spf_run_many.argtypes = [ctypes.c_void_p, i32p, ctypes.c_int32]
    _lib = lib
    return _lib


def native_spf_available() -> bool:
    return _load() is not None


def _as_i32_ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


class NativeSpfSolver:
    """Dijkstra over a CompiledGraph's real edges, run by the C++ engine.

    Only the `graph.e` real edge slots are passed down (array padding never
    relaxes anyway); edge positions used by `set_weight` are therefore the
    same positions `CompiledGraph.link_edges` records.
    """

    def __init__(self, graph: CompiledGraph):
        lib = _load()
        if lib is None:
            raise RuntimeError("native SPF library unavailable")
        self._lib = lib
        self.graph = graph
        self.n = graph.n
        src = np.ascontiguousarray(graph.src[: graph.e], dtype=np.int32)
        dst = np.ascontiguousarray(graph.dst[: graph.e], dtype=np.int32)
        w = np.ascontiguousarray(graph.w[: graph.e], dtype=np.int32)
        ov = np.ascontiguousarray(
            graph.overloaded[: graph.n], dtype=np.uint8
        )
        self._h = lib.onl_spf_create(
            graph.n,
            graph.e,
            _as_i32_ptr(src),
            _as_i32_ptr(dst),
            _as_i32_ptr(w),
            ov.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        )
        if not self._h:
            raise RuntimeError("onl_spf_create failed")

    def close(self) -> None:
        if getattr(self, "_h", None):
            self._lib.onl_spf_destroy(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:
            pass

    def set_weight(self, edge_pos: int, w: int) -> None:
        self._lib.onl_spf_set_weight(self._h, edge_pos, int(w))

    def set_overloaded(self, node: int, overloaded: bool) -> None:
        self._lib.onl_spf_set_overloaded(self._h, node, 1 if overloaded else 0)

    def out_neighbors(self, source: int) -> np.ndarray:
        deg = self._lib.onl_spf_out_degree(self._h, source)
        out = np.zeros(max(deg, 1), dtype=np.int32)
        self._lib.onl_spf_out_neighbors(self._h, source, _as_i32_ptr(out), deg)
        return out[:deg]

    def run(self, source: int) -> np.ndarray:
        """Distances int32 [n] from `source` (INF = unreachable)."""
        dist = np.empty(self.n, dtype=np.int32)
        r = self._lib.onl_spf_run(self._h, source, _as_i32_ptr(dist), None, 0)
        if r < 0:
            raise ValueError(f"bad source {source}")
        return dist

    def run_with_nexthops(self, source: int):
        """(distances [n], first-hop neighbor-id sets per node)."""
        deg = self._lib.onl_spf_out_degree(self._h, source)
        words = max(1, (deg + 63) // 64)
        dist = np.empty(self.n, dtype=np.int32)
        nh = np.zeros((self.n, words), dtype=np.uint64)
        r = self._lib.onl_spf_run(
            self._h,
            source,
            _as_i32_ptr(dist),
            nh.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            words,
        )
        if r < 0:
            raise ValueError(f"bad source {source}")
        nbrs = self.out_neighbors(source)
        sets: List[Set[int]] = []
        for v in range(self.n):
            s: Set[int] = set()
            row = nh[v]
            for word_i in range(words):
                bits = int(row[word_i])
                while bits:
                    b = bits & -bits
                    slot = word_i * 64 + b.bit_length() - 1
                    if slot < len(nbrs):
                        s.add(int(nbrs[slot]))
                    bits ^= b
            sets.append(s)
        return dist, sets

    def run_many(self, sources: np.ndarray) -> int:
        """Benchmark path: Dijkstra from each source, results discarded."""
        src = np.ascontiguousarray(sources, dtype=np.int32)
        r = self._lib.onl_spf_run_many(self._h, _as_i32_ptr(src), len(src))
        if r < 0:
            raise ValueError("bad source in batch")
        return int(r)
