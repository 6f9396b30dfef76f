"""Device time per epoch of the epoch step's own ops, outside the two
sampler functions: keys, frame accumulation, the reduction over workers
and the stopping check, in ms, averaged over the devices."""

from bench import trace_reduce as tr

SAMPLER = tr.in_stack("bfs_sssp", "sample_path")


def _engine(op):
    return op.stack.startswith("jit(step_raw)") and not SAMPLER(op)


def read(ctx):
    ms = [tr.op_seconds(ops, ctx.lo, ctx.hi, _engine) * 1e3 / ctx.epochs
          for ops in ctx.trace.ops.values()]
    return tr.mean(ms) if ms and max(ms) > 0 and ctx.epochs else None
