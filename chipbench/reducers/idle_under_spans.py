"""`idle_under_spans`: of the device's idle time between the first and the
last program run of the traced window, the share in % that lies under the
host spans the source names, or, with `"complement": true`, under none of
them.

`spans` holds names of the program's stages (openr_tpu/monitor/spans.py:
`jax.profiler.TraceAnnotation`s on the profiler's own clock, the clock of
the device plane); a name that ends in `*` matches every span that starts
with what stands before it. Where one gap is named by the one span that
covers most of it (`trace_reduce.label_gap`), this says how the idle time
as a whole is made up: the shares of disjoint span lists and of the
complement of all of them add up to 100.

Nothing to read, and no number: a trace with no device plane (a
rehearsal), no idle time between the programs, or a program that emits
none of the named spans (one from before they existed).
"""

from chipbench import trace_reduce


def matches(name, patterns):
    return any(
        name.startswith(p[:-1]) if p.endswith("*") else name == p
        for p in patterns
    )


def overlap_seconds(idle, covered):
    """Seconds of the disjoint intervals `idle` that lie inside the
    disjoint, sorted intervals `covered`."""
    total = 0.0
    for gap_start, gap_end in idle:
        for start, end in covered:
            if start >= gap_end:
                break
            total += max(0.0, min(end, gap_end) - max(start, gap_start))
    return total


def idle_share_under(programs, host, patterns, complement=False):
    """The arithmetic alone, over (name, start, end) lists."""
    runs = [(a, b) for _, a, b in programs]
    if not runs:
        return None
    first, last = min(a for a, _ in runs), max(b for _, b in runs)
    idle = trace_reduce.gaps(runs, first, last)
    idle_s = sum(b - a for a, b in idle)
    named = [(a, b) for name, a, b in host if matches(name, patterns)]
    if idle_s <= 0.0 or not named:
        return None
    share = 100.0 * overlap_seconds(idle, trace_reduce.merge(named)) / idle_s
    return 100.0 - share if complement else share


def reduce(ctx, source):
    return idle_share_under(
        ctx.trace.programs,
        ctx.trace.host,
        source["spans"],
        bool(source.get("complement", False)),
    )
