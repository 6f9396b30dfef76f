"""The program's own spans and counters (``repro.runtime.spans``), as the
per-layer readers see them: the records in this process, the window's
epochs among them, and the map from their clock onto the trace's.

A program without that module gives no records; the readers that need
them then read nothing."""

from __future__ import annotations

import statistics
import sys


def records() -> list:
    try:
        from repro.runtime import spans
    except ImportError:
        return []
    return spans.records()


def window_steps(ctx) -> list:
    """The ``session.step`` records of the window's epochs: the last
    ``round(ctx.epochs)`` of them."""
    steps = [r for r in records() if r.name == "session.step"]
    n = round(ctx.epochs)
    return steps[-n:] if 0 < n <= len(steps) else []


def before_window(ctx) -> list:
    """The records that ended before the window's first epoch began."""
    steps = window_steps(ctx)
    if not steps:
        return []
    return [r for r in records() if r.end <= steps[0].start]


def total(recs, name: str) -> float:
    return sum(r.total.get(name, 0) for r in recs)


def child(parent, name: str):
    return next((r for r in records()
                 if r.parent == parent.id and r.name == name), None)


def clock_map(ctx, steps):
    """``to_trace(seconds) -> ns``: the program's clock on the trace's, an
    offset fitted through the harness's ``step`` spans, each of which holds
    one ``session.step``, paired from the end; None without a pair.  The
    residual, the largest distance of a pair's midpoint offset from the
    fitted one, goes to stderr."""
    outer = [(a, b) for name, a, b in ctx.trace.spans if name == "step"]
    k = min(len(outer), len(steps))
    if k == 0:
        return None
    offsets = [(a + b) / 2 - (s.start + s.end) / 2 * 1e9
               for (a, b), s in zip(outer[-k:], steps[-k:])]
    offset = statistics.median(offsets)
    residual = max(abs(o - offset) for o in offsets)
    print(f"[bench] program clock on the trace's: {k} steps, residual "
          f"{residual * 1e-3:.3f} us", file=sys.stderr, flush=True)
    return lambda seconds: seconds * 1e9 + offset
