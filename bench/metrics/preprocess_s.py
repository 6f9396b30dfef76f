"""Set-up time of KADABRA's preprocessing (components and the double
sweep), in s: the program's ``kadabra.preprocess`` spans before the
window."""

from bench import program


def read(ctx):
    spans = [r for r in program.before_window(ctx)
             if r.name == "kadabra.preprocess"]
    return sum(r.seconds for r in spans) if spans else None
