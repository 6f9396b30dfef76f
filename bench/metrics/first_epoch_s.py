"""The process's first epoch step, in s: its ``session.step`` span less
the compile or load charged inside it."""

from bench import program


def read(ctx):
    first = next((r for r in program.records() if r.name == "session.step"),
                 None)
    if first is None:
        return None
    return first.seconds - first.total.get("compile_s", 0.0)
