"""Set-up time spent compiling programs or loading them from the
persistent compilation cache, in s: ``compile_s`` charged to the
program's spans that ended before the window."""

from bench import program


def read(ctx):
    top = [r for r in program.before_window(ctx) if r.parent is None]
    return program.total(top, "compile_s") if top else None
