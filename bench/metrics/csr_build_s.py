"""Set-up time of the host CSR build and its upload to the device, in s:
the program's ``graph.csr`` spans before the window."""

from bench import program


def read(ctx):
    spans = [r for r in program.before_window(ctx) if r.name == "graph.csr"]
    return sum(r.seconds for r in spans) if spans else None
