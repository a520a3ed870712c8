"""`program_ms`: mean device milliseconds per event of one device program,
named by the source's `program` (`jit_solve`, `jit__delta_extract`): its
runs' total device time in the traced window over the events completed
there. Every event is in it, where the program's own phase histograms see
every 16th solve."""


def reduce(ctx, source):
    trace = ctx.trace
    seconds, count = trace.program_seconds(source["program"])
    n_events = len(trace.events)
    if not count or not n_events:
        return None
    return 1e3 * seconds / n_events
