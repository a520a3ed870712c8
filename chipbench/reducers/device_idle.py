"""`device_idle`: the share of the traced window in which no operation ran
on the device, in %, from the profiler's device plane."""


def reduce(ctx, source):
    trace = ctx.trace
    if not trace.programs:
        return None  # no device plane: nothing to read (a rehearsal)
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
