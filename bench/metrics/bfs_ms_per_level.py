"""Device time of one BFS level, in ms: the self time of the ops under the
named scope ``bfs_level`` over the levels the window's epochs ran, on all
devices together."""

from bench import program
from bench import trace_reduce as tr


def _level(op):
    return "bfs_level" in op.stack.split("/")


def read(ctx):
    levels = program.total(program.window_steps(ctx), "bfs_levels")
    secs = sum(tr.op_seconds(ops, ctx.lo, ctx.hi, _level)
               for ops in ctx.trace.ops.values())
    return secs * 1e3 / levels if levels and secs > 0 else None
