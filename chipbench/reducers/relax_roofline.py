"""`relax_roofline`: the least time the chip could take for ONE relaxation
sweep of the configuration's graph (work.py: bytes over HBM bandwidth),
over the mean device time per event of the solve program, in %.

The solve program is named by the source's `program` (`jit_solve`: the
jitted inner function of `_sell_solver_warm` and, on a cold solve, of
`_sell_solver_counted`, which the trace prints under the same name); its
time per event is its total device time in the traced window over the
events completed there.
"""

from chipbench import work


def reduce(ctx, source):
    trace = ctx.trace
    seconds, count = trace.program_seconds(source["program"])
    n_events = len(trace.events)
    if not count or not n_events:
        return None
    per_event = seconds / n_events
    return 100.0 * work.sweep_floor_s(ctx.config, ctx.device_kind) / per_event
