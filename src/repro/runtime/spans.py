"""Host spans and counters of the program, kept in memory.

``span(name, query=..., **attrs)`` times a stretch of host code: its name,
start and end on the ``time.perf_counter`` clock, the span it is nested in
and the query it works for.  Closed spans go into a ring that keeps the
last ``RING`` of them (``records()``).  The same call opens a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so a profiler
trace shows the span on the clock of the device's ops; with no profiler
session running the annotation costs nothing.

``count(name, value)`` adds to the innermost open span: to its own
``counts`` and to the ``total`` of it and of every span it is nested in.
Backend compiles or loads from the persistent compilation cache are
counted the same way, from JAX's monitoring events: ``compile_s`` (compile
or load, cache retrieval included), ``cache_load_s`` (the retrieval alone)
and ``programs``.  What is counted outside any span goes to ``outside``.

Host spans sit at the layers' boundaries, an epoch or coarser; inside a
jitted program the layers are named with ``jax.named_scope`` instead.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Any, Optional

import jax

RING = 4096

_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


@dataclasses.dataclass
class Span:
    name: str
    id: int
    parent: Optional[int]       # the id of the span this one is nested in
    query: Any                  # the query it works for (None: no query)
    attrs: dict
    start: float                # time.perf_counter() seconds
    end: float = float("nan")
    counts: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)   # counted in this span itself
    total: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)   # and in the spans inside it

    @property
    def seconds(self) -> float:
        return self.end - self.start


_ring: collections.deque = collections.deque(maxlen=RING)
_ids = itertools.count()
_local = threading.local()
outside: collections.Counter = collections.Counter()


def _open() -> list:
    if not hasattr(_local, "stack"):
        _local.stack = []
    return _local.stack


@contextlib.contextmanager
def span(name: str, *, query: Any = None, **attrs):
    """Time the body as span ``name``; ``query`` defaults to the enclosing
    span's."""
    stack = _open()
    parent = stack[-1] if stack else None
    if query is None and parent is not None:
        query = parent.query
    with jax.profiler.TraceAnnotation(f"repro.{name}", **attrs):
        s = Span(name=name, id=next(_ids),
                 parent=None if parent is None else parent.id,
                 query=query, attrs=attrs, start=time.perf_counter())
        stack.append(s)
        try:
            yield s
        finally:
            s.end = time.perf_counter()
            stack.pop()
            _ring.append(s)


def count(name: str, value: float = 1) -> None:
    """Add ``value`` to counter ``name`` of the innermost open span."""
    stack = _open()
    if not stack:
        outside[name] += value
        return
    stack[-1].counts[name] += value
    for s in stack:
        s.total[name] += value


def records() -> list:
    """The closed spans in the ring, in the order they closed."""
    return list(_ring)


def clear() -> None:
    _ring.clear()
    outside.clear()


def _on_duration(event: str, seconds: float, **_) -> None:
    if event == _COMPILE:
        count("compile_s", seconds)
        count("programs")
    elif event == _CACHE_LOAD:
        count("cache_load_s", seconds)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
