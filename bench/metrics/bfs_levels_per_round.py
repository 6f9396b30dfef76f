"""BFS levels per sampling round: the levels the batched BFS loop ran (the
deepest lane's, counted by the sampler) over the rounds, in the window's
epochs."""

from bench import program


def read(ctx):
    steps = program.window_steps(ctx)
    rounds = program.total(steps, "rounds")
    return program.total(steps, "bfs_levels") / rounds if rounds else None
