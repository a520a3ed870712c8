"""Solver flight recorder: per-solve traces, phase timing, and
fault-forensics dumps.

Every solver path this repo has shipped — cold, warm-invalidation,
edge-list, tiled-halo, blocked-FW APSP — reported exactly one wall-clock
number per solve (`decision.spf.solve_ms`), so nothing could attribute an
event's latency to h2d upload, the relax fixpoint, delta extraction or
the lazy d2h mirror fetch; and when the fault domain fired, the
supervisor threw away exactly the context (the recent solve history)
needed to diagnose it. This module is the missing observability layer:

  - **SolveTrace** — one structured record per supervised solve: event
    class, layout kind (sell / bf / tile2d / cpu), warm/cold disposition,
    wall time, fixpoint rounds, transfer bytes, compile-cache deltas,
    breaker state, and a per-phase millisecond breakdown of the host's
    time.
  - **PhaseClock** — the phase timer of every solve. `enter(phase)` is a
    seam: it ends the phase in progress and begins the next, two clock
    reads and a profiler annotation, and never waits for the device. The
    seams of the solve path lie where the path blocks on a device value
    anyway (a round count, the changed-column count, the copied
    columns), so `relax` is the host's wait for the device solve and
    `h2d` means "uploads issued and program enqueued". Device time per
    event is the profiler trace's to give (docs/Monitoring.md).
  - **FlightRecorder** — a bounded per-area ring of traces with exact
    eviction accounting (`recorded == retained + evicted`), plus the
    forensics side: `dump(reason)` snapshots the rings, the solver
    config, a mesh/device digest and a counter snapshot into one JSON
    artifact, referenced by id from the breaker/audit LogSamples
    (`SOLVER_FORENSICS_DUMPED`, docs/Monitoring.md).

The recorder owns no registry: phase samples queue in a pending list the
owning backend drains into its `decision.spf.phase.*_ms` histograms on
the existing counter-sync path (solver/tpu.py:_sync_spf_counters), so
monitor/ctrl/exporter all see them through the normal substrate.
"""

from __future__ import annotations

import collections
import json
import os
import time
from dataclasses import asdict, dataclass, field
from typing import Any, Deque, Dict, List, Optional, Tuple

from openr_tpu.monitor.spans import stage

# phase vocabulary, in dispatch order. The fused warm kernels run the
# invalidation-mark fixpoint and (on the tiled layout) the halo exchange
# inside the same dispatch as the relax rounds, so those phases are
# attributed inside `relax` with the per-trace round/exchange gauges
# splitting them (docs/Monitoring.md "Flight recorder & profiling").
# `refresh` comes before the solve's own clock (`solve_ms`) starts, and
# `d2h` after it stopped: the mirror is fetched when a reader wants it.
PHASES = (
    "refresh",
    "prepare",
    "h2d",
    "relax",
    "delta_extract",
    "mirror_patch",
    "d2h",
)

# phase -> registry histogram (docs/Monitoring.md histogram table); the
# full names live here as literals so the doc rows stay pinned to code
# by the registry-drift analyzer's string universe. A phase's profiler
# span is its histogram's name without `_ms` (monitor/spans.py).
PHASE_HISTOGRAMS: Dict[str, str] = {
    "refresh": "decision.spf.phase.refresh_ms",
    "prepare": "decision.spf.phase.prepare_ms",
    "h2d": "decision.spf.phase.h2d_ms",
    "relax": "decision.spf.phase.relax_ms",
    "delta_extract": "decision.spf.phase.delta_extract_ms",
    "mirror_patch": "decision.spf.phase.mirror_patch_ms",
    "d2h": "decision.spf.phase.d2h_ms",
}


def phase_stage(phase: str, build: Optional[int] = None) -> stage:
    """The started stage of one solve phase; its milliseconds go to the
    phase's histogram through the recorder (`observe_phase`), which owns
    no registry."""
    return stage(PHASE_HISTOGRAMS[phase][: -len("_ms")], build=build).start()


class PhaseClock:
    """Per-solve phase timer: the phases tile the solve.

    `enter(phase)` is a seam: one clock reading ends the phase in
    progress, credited to `phases`, and begins `phase`, so the phases add
    up to the stretch from the first `enter` to `stop()`. No seam waits
    for the device. `build` tags the phases' profiler spans with
    Decision's route build number."""

    __slots__ = ("phases", "build", "_phase", "_since", "_open")

    def __init__(self, build: Optional[int] = None) -> None:
        self.phases: Dict[str, float] = {}
        self.build = build
        self._phase: Optional[str] = None
        self._since = 0.0
        self._open: Optional[stage] = None  # the phase's profiler span

    def enter(self, phase: str) -> None:
        now = time.perf_counter()
        self._end(now)
        self._phase, self._since = phase, now
        self._open = phase_stage(phase, self.build)

    def stop(self) -> None:
        self._end(time.perf_counter())

    def _end(self, now: float) -> None:
        if self._open is not None:
            self._open.stop()
            self._open = None
            self.phases[self._phase] = (
                self.phases.get(self._phase, 0.0) + (now - self._since) * 1e3
            )


@dataclass
class SolveTrace:
    """One supervised solve, structured (docs/Monitoring.md field table)."""

    seq: int
    ts: float  # wall clock (forensics correlation across nodes)
    area: str
    node: str
    event: str  # solve | fallback_solve | fault
    layout: str  # sell | bf | tile2d | replicated | cpu | none
    warm: bool
    solve_ms: Optional[float]
    rounds: Optional[int]
    invalidation_rounds: Optional[int]
    halo_exchanges: Optional[int]
    h2d_bytes: int
    d2h_bytes: int
    halo_bytes: int
    delta_columns: Optional[int]
    compile_cache_misses: int  # executables compiled BY this solve
    breaker_state: str
    phases: Dict[str, float] = field(default_factory=dict)
    fault_kind: Optional[str] = None
    detail: Optional[str] = None

    def to_dict(self) -> Dict[str, Any]:
        return asdict(self)


class FlightRecorder:
    """Bounded per-area SolveTrace rings + forensics dump snapshots."""

    def __init__(
        self,
        ring_size: int = 64,
        forensics_dir: Optional[str] = None,
        forensics_last_n: int = 16,
        max_dumps: int = 8,
        node: str = "",
    ) -> None:
        self.ring_size = max(int(ring_size), 1)
        self.forensics_dir = forensics_dir
        self.forensics_last_n = max(int(forensics_last_n), 1)
        self.max_dumps = max(int(max_dumps), 1)
        self.node = node
        # stamped by the supervisor on breaker transitions so traces and
        # dumps carry the serving state they were recorded under
        self.breaker_state = "closed"
        # Decision's route build number, set before each build: the
        # identifier the solve's profiler spans share with the event's
        self.build: Optional[int] = None
        self._rings: Dict[str, Deque[SolveTrace]] = {}
        self._seq = 0
        self.recorded = 0
        self.evicted = 0
        self._pending_obs: List[Tuple[str, float]] = []
        self.dumps: List[Dict[str, Any]] = []
        self.dumps_written = 0
        self.last_dump_id: Optional[str] = None
        self.last_dump_reason: Optional[str] = None

    # -- recording -------------------------------------------------------

    def next_seq(self) -> int:
        self._seq += 1
        return self._seq

    def record(self, trace: SolveTrace, clock: Optional[PhaseClock] = None):
        """Append one trace to its area ring (evicting with accounting)
        and queue the solve's phase observations for the histogram
        drain."""
        ring = self._rings.get(trace.area)
        if ring is None:
            ring = self._rings[trace.area] = collections.deque()
        while len(ring) >= self.ring_size:
            ring.popleft()
            self.evicted += 1
        ring.append(trace)
        self.recorded += 1
        if clock is not None:
            for phase, ms in clock.phases.items():
                self.observe_phase(phase, ms)

    def observe_phase(self, phase: str, ms: float) -> None:
        """Queue one phase sample for the owning backend's histogram
        drain (also used post-hoc: the lazy d2h mirror fetch lands after
        the trace was recorded)."""
        name = PHASE_HISTOGRAMS.get(phase)
        if name is not None:
            self._pending_obs.append((name, ms))

    def drain_observations(self) -> List[Tuple[str, float]]:
        out, self._pending_obs = self._pending_obs, []
        return out

    # -- read surfaces ---------------------------------------------------

    def retained(self) -> int:
        return sum(len(r) for r in self._rings.values())

    def snapshot(
        self, area: Optional[str] = None, last_n: Optional[int] = None
    ) -> List[Dict[str, Any]]:
        """Trace dicts, oldest first, optionally filtered/limited."""
        traces: List[SolveTrace] = []
        for ring_area, ring in sorted(self._rings.items()):
            if area is not None and ring_area != area:
                continue
            traces.extend(ring)
        traces.sort(key=lambda t: t.seq)
        if last_n is not None and last_n >= 0:
            traces = traces[-last_n:]
        return [t.to_dict() for t in traces]

    def stats(self) -> Dict[str, Any]:
        return {
            "ring_size": self.ring_size,
            "areas": sorted(self._rings),
            "recorded": self.recorded,
            "retained": self.retained(),
            "evicted": self.evicted,
        }

    # -- forensics -------------------------------------------------------

    def dump(
        self,
        reason: str,
        *,
        solver_config: Optional[Dict[str, Any]] = None,
        counters: Optional[Dict[str, int]] = None,
        mesh_digest: Optional[Dict[str, Any]] = None,
        extra: Optional[Dict[str, Any]] = None,
        device_memory: Optional[Dict[str, Any]] = None,
    ) -> Dict[str, Any]:
        """Snapshot the rings + context into one JSON-serializable
        forensics artifact; kept in a bounded in-memory list and, when
        `forensics_dir` is configured, written to
        `<dir>/<id>.json` (best-effort: an unwritable dir must never
        turn a breaker trip into a crash)."""
        self.dumps_written += 1
        dump_id = (
            f"forensics-{self.node or 'node'}-"
            f"{self.dumps_written:04d}-{int(time.time())}"
        )
        dump: Dict[str, Any] = {
            "id": dump_id,
            "reason": reason,
            "ts": time.time(),
            "node": self.node,
            "breaker_state": self.breaker_state,
            "trace_stats": self.stats(),
            "traces": {
                area: [t.to_dict() for t in list(ring)][
                    -self.forensics_last_n:
                ]
                for area, ring in sorted(self._rings.items())
            },
            "solver_config": solver_config or {},
            "mesh_digest": mesh_digest or device_digest(None),
            "counters": dict(counters or {}),
        }
        if extra:
            dump["extra"] = extra
        if device_memory is not None:
            # memory-ledger snapshot (monitor/memledger.py): resident
            # structures + capacity picture at dump time — the device_oom
            # post-mortem's primary evidence
            dump["device_memory"] = device_memory
        self.dumps.append(dump)
        while len(self.dumps) > self.max_dumps:
            self.dumps.pop(0)
        self.last_dump_id = dump_id
        self.last_dump_reason = reason
        dump["path"] = None
        if self.forensics_dir:
            try:
                os.makedirs(self.forensics_dir, exist_ok=True)
                path = os.path.join(self.forensics_dir, f"{dump_id}.json")
                tmp = f"{path}.tmp.{os.getpid()}"
                with open(tmp, "w") as fh:
                    json.dump(dump, fh, sort_keys=True)
                os.replace(tmp, path)
                dump["path"] = path
            except OSError:
                pass
        return dump

    def dump_summaries(self) -> List[Dict[str, Any]]:
        """Compact dump index (getSolverHealth / getSolveTraces): id,
        reason, timestamp, trace count, artifact path."""
        return [
            {
                "id": d["id"],
                "reason": d["reason"],
                "ts": d["ts"],
                "breaker_state": d["breaker_state"],
                "traces": sum(len(ts) for ts in d["traces"].values()),
                "path": d.get("path"),
            }
            for d in self.dumps
        ]

    def forensics_stats(self) -> Dict[str, Any]:
        return {
            "dumps": self.dumps_written,
            "retained_dumps": len(self.dumps),
            "last_id": self.last_dump_id,
            "last_reason": self.last_dump_reason,
            "dir": self.forensics_dir,
        }


def device_digest(mesh) -> Dict[str, Any]:
    """Mesh/device context for forensics dumps, degrade-safe: a dead or
    absent backend yields an error string, never an exception (the dump
    runs exactly when the device is suspect)."""
    digest: Dict[str, Any] = {
        "mesh_shape": dict(mesh.shape) if mesh is not None else None,
    }
    try:
        import jax

        devices = jax.devices()
        digest["devices"] = len(devices)
        digest["platform"] = devices[0].platform if devices else None
        digest["device_kind"] = (
            getattr(devices[0], "device_kind", "") if devices else None
        )
    except Exception as exc:  # device loss is exactly when dumps happen
        digest["error"] = f"{type(exc).__name__}: {exc}"
    return digest
