"""From a profiler trace to device time: busy and idle, programs, gaps.

Read with `jax.profiler.ProfileData` and nothing else. The arithmetic
(interval union, idle gaps and their labels, totals by name) is in plain
functions over lists so that it can be tested without a trace.

Intervals are (start, end) pairs in seconds on the trace's clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Named = Tuple[str, float, float]  # name, start, end


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[Interval] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def union_seconds(intervals: Sequence[Interval]) -> float:
    return sum(end - start for start, end in merge(intervals))


def idle_share(intervals: Sequence[Interval], window_s: float) -> float:
    """1 - (time in which some operation ran) / window."""
    if window_s <= 0:
        raise ValueError("window must be longer than 0")
    return 1.0 - union_seconds(intervals) / window_s


def gaps(intervals: Sequence[Interval], start: float, end: float) -> List[Interval]:
    """The idle stretches of [start, end], longest first."""
    out, cursor = [], start
    for a, b in merge(intervals):
        if b <= start or a >= end:
            continue
        if a > cursor:
            out.append((cursor, a))
        cursor = max(cursor, b)
    if end > cursor:
        out.append((cursor, end))
    return sorted(out, key=lambda g: g[0] - g[1])


NO_SPAN = "host, no profiler span"


def label_gap(gap: Interval, host: Sequence[Named]) -> str:
    """What the host was doing in the gap: the span that covers most of
    it, the shorter one where two cover the same. A span that covers less
    than half of the gap does not name it."""
    best, best_key = NO_SPAN, (0.5 * (gap[1] - gap[0]), 0.0)
    for name, a, b in host:
        overlap = min(b, gap[1]) - max(a, gap[0])
        if overlap <= 0:
            continue
        key = (overlap, -(b - a))
        if key > best_key:
            best, best_key = name, key
    return best


def totals_by_name(events: Sequence[Named]) -> List[Tuple[str, float]]:
    totals: Dict[str, float] = {}
    for name, a, b in events:
        totals[name] = totals.get(name, 0.0) + (b - a)
    return sorted(totals.items(), key=lambda kv: -kv[1])


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # mean over the device planes
    ops: List[Named]  # device operations, first plane, first OPS_SAMPLE_S
    programs: List[Named]  # runs of device programs (XLA modules), first plane
    host: List[Named]  # host spans
    events: List[Named]  # the benchmark's own "chipbench.event" spans

    def breakdown(self) -> dict:
        """Programs by their device seconds over the traced window, then
        the operations that took most of the first OPS_SAMPLE_S seconds
        (an op inside a loop counts in the loop's time too); the longest
        gaps between programs, by the host span that covers most of each
        (the program's and the runtime's spans, not the benchmark's own
        event span, which covers every gap of an event)."""
        runs = [(a, b) for _, a, b in self.programs]
        span = (
            (min(a for a, _ in runs), max(b for _, b in runs))
            if runs else (0.0, 0.0)
        )
        idle = gaps(runs, *span)[:10]
        by_program = [
            [name.split("(")[0], s] for name, s in totals_by_name(self.programs)
        ]
        by_op = [
            [f"{name} (first {OPS_SAMPLE_S}s)", s]
            for name, s in totals_by_name(self.ops)
        ]
        return {
            "device_ops": (by_program + by_op)[:10],
            "idle_gaps": [
                [label_gap(g, self.host), g[1] - g[0]] for g in idle
            ],
        }

    def program_seconds(self, program: str) -> Tuple[float, int]:
        """Total device seconds and number of runs of the device program
        `program` (its runs are named `<program>(<id>)`). A trace that
        holds device programs and none of this name is an error: the
        program was renamed, and a metric that read it must not fall
        silent. A trace with no device plane (a rehearsal) gives (0, 0)."""
        hits = [
            (a, b) for name, a, b in self.programs
            if name.split("(")[0] == program
        ]
        if self.programs and not hits:
            known = sorted({name.split("(")[0] for name, _, _ in self.programs})
            raise LookupError(
                f"no run of device program {program!r} in the trace; it holds {known}"
            )
        return sum(b - a for a, b in hits), len(hits)


def _trace_file(trace_dir: str) -> str:
    files = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


OPS_SAMPLE_S = 0.5  # of the trace's start, read op by op for the breakdown


def short_name(name: str) -> str:
    """An XLA op's event is named by its whole HLO line: keep what names it,
    `%while.31`, and the kind of instruction, not its operands' shapes."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    # shapes hold `T(8,128)` and `S(1)`; an instruction starts in lower case
    kind = re.search(r"\b([a-z][a-z0-9_-]*)\(", rest)
    return f"{head} {kind.group(1) if kind else ''}".strip()[:120]


def _line_events(line, until_s: Optional[float] = None) -> List[Named]:
    """The line's events; with `until_s`, those that start in the first
    `until_s` seconds after the line's first (a device's op line holds
    millions: every op of every round of every loop)."""
    out: List[Named] = []
    first = None
    for ev in line.events:
        start = ev.start_ns * 1e-9
        if first is None:
            first = start
        if until_s is not None and start - first > until_s:
            break
        out.append((ev.name, start, start + ev.duration_ns * 1e-9))
    return out


def read_trace(trace_dir: str, window_s: float) -> TraceSummary:
    """Device planes are '/device:TPU:<i>'. Their 'XLA Modules' line holds
    one event per run of a program (`jit_solve(<id>)`), and the device runs
    some operation of the program all through it (on the first traces read
    by hand, the union of the 'XLA Ops' line's 2.2 million events equalled
    the modules' total to 0.05 %), so busy time is the union of the
    modules. The ops line is read for the breakdown alone, and only its
    first OPS_SAMPLE_S seconds. Off the chip (a rehearsal) there is no
    device plane and busy time reads 0."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(_trace_file(trace_dir))
    ops: List[Named] = []
    programs: List[Named] = []
    host: List[Named] = []
    events: List[Named] = []
    busy: List[float] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            plane_ops: List[Named] = []
            plane_programs: List[Named] = []
            for line in plane.lines:
                if line.name == "XLA Ops" and not ops:
                    plane_ops = [
                        (short_name(n), a, b)
                        for n, a, b in _line_events(line, OPS_SAMPLE_S)
                    ]
                elif line.name == "XLA Modules":
                    plane_programs = _line_events(line)
            busy.append(union_seconds([(a, b) for _, a, b in plane_programs]))
            if not programs:
                ops, programs = plane_ops, plane_programs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in _line_events(line):
                    (events if ev[0] == "chipbench.event" else host).append(ev)
    return TraceSummary(
        window_s=window_s,
        busy_s=sum(busy) / len(busy) if busy else 0.0,
        ops=ops,
        programs=programs,
        host=host,
        events=events,
    )


def dump(trace_dir: str, out_path: str, top: int = 40) -> None:
    """A trace by hand: planes, lines, and the names that took most time
    in each line's first second."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(_trace_file(trace_dir))
    with open(out_path, "w") as fh:
        for plane in data.planes:
            fh.write(f"PLANE {plane.name}\n")
            for line in plane.lines:
                evs = _line_events(line, 1.0)
                fh.write(f"  LINE {line.name!r}: {len(evs)} events\n")
                for name, total in totals_by_name(evs)[:top]:
                    count = sum(1 for e in evs if e[0] == name)
                    fh.write(f"    {total * 1e3:10.3f} ms {count:6d}x {name[:150]}\n")
