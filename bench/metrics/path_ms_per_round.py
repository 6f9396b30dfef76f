"""Device time of the path walk (ops under ``sample_path`` in the name
stack) per sampling round, in ms: each device's time over its rounds,
averaged over the devices."""

from bench import trace_reduce as tr


def read(ctx):
    where = tr.in_stack("sample_path")
    ms = [tr.op_seconds(ops, ctx.lo, ctx.hi, where) * 1e3 / ctx.rounds
          for ops in ctx.trace.ops.values()]
    return tr.mean(ms) if ms and max(ms) > 0 and ctx.rounds else None
