"""Share of the path walk's steps that moved, in %: the steps up to each
reachable lane's ``dist(s, t)`` over the ``batch × max_len`` steps a round
takes, in the window's epochs."""

from bench import program


def read(ctx):
    steps = program.window_steps(ctx)
    taken = program.total(steps, "path_steps")
    live = program.total(steps, "path_live_steps")
    return 100.0 * live / taken if taken else None
