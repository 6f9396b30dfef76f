"""Device idle time at an epoch boundary, in ms, worst device: each idle
stretch that begins between the start of an epoch's ``session.readback``
and the end of the next epoch's ``session.dispatch`` (counted from the
readback's start to the stretch's end), over the window's boundaries."""

from bench import program
from bench import trace_reduce as tr


def read(ctx):
    steps = program.window_steps(ctx)
    to_trace = program.clock_map(ctx, steps)
    if to_trace is None or len(steps) < 2 or not ctx.trace.ops:
        return None
    bounds = []
    for a, b in zip(steps, steps[1:]):
        readback = program.child(a, "session.readback")
        dispatch = program.child(b, "session.dispatch")
        if readback is None or dispatch is None:
            return None
        bounds.append((to_trace(readback.start), to_trace(dispatch.end)))
    worst = 0.0
    for ops in ctx.trace.ops.values():
        gaps = tr.idle_gaps(ops, ctx.lo, ctx.hi)
        idle = sum(g1 - max(g0, r) for r, d in bounds for g0, g1 in gaps
                   if g0 < d and g1 > r)
        worst = max(worst, idle)
    return worst * 1e-6 / len(bounds)
