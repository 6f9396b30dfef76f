"""Share of the window in which no operation ran on the device, in %:
100 * (1 - union of the device's op intervals / window), worst device."""

from bench import trace_reduce as tr


def read(ctx):
    if not ctx.trace.ops:
        return None
    window = ctx.hi - ctx.lo
    return max(100.0 * (1.0 - tr.busy_ns(ops, ctx.lo, ctx.hi) / window)
               for ops in ctx.trace.ops.values())
