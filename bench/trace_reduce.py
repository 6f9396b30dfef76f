"""From a profiler trace to device intervals, attributed by name stack.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
In it, each chip is a plane ``/device:TPU:<k>``, whose line ``XLA Ops``
holds one event per executed HLO instruction, named by the instruction's
text (``%fusion.194 = f32[...] fusion(...)``).  The events nest: a
``while`` spans the ops of its body.  An op's self time is its duration
less the time of the ops nested in it.  Host threads are planes too, and
the harness's spans are host events named ``bench.<span>``.  Host and
device events share one clock.

An op's name stack is the ``op_name`` metadata of its HLO instruction,
``jit(step_raw)/while/body/.../vmap(jit(bfs_sssp))/while/body/scatter``,
looked up by the instruction's name in the compiled program's HLO text
(the TPU trace does not carry it).
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Op:
    name: str           # the HLO instruction
    stack: str          # its name stack ('' where unknown)
    start_ns: float
    end_ns: float
    self_ns: float = 0.0  # duration less that of the ops nested in it


def self_times(ops: list) -> list:
    """Set each op's ``self_ns``; ``ops`` sorted by start."""
    stack: list = []
    for op in ops:
        op.self_ns = op.end_ns - op.start_ns
        while stack and stack[-1].end_ns <= op.start_ns:
            stack.pop()
        if stack:
            parent = stack[-1]
            parent.self_ns -= min(op.end_ns, parent.end_ns) - op.start_ns
        stack.append(op)
    return ops


@dataclasses.dataclass
class Trace:
    ops: dict           # device index -> [Op], by start
    spans: list         # (name, start_ns, end_ns) of the harness's spans

    def window(self) -> tuple[float, float]:
        """Start and end of the harness's span ``window``."""
        for n, a, b in self.spans:
            if n == "window":
                return a, b
        raise ValueError("no host span 'window' in the trace")


def xplane_file(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def hlo_op_names(hlo_text: str) -> dict:
    """``{instruction name: op_name}`` from compiled HLO text."""
    out = {}
    for m in re.finditer(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=.*?"
                         r'op_name="([^"]*)"', hlo_text, re.M):
        out[m.group(1).lstrip("%")] = m.group(2)
    return out


def instruction(event_name: str) -> str:
    """``fusion.194`` of ``%fusion.194 = f32[8] fusion(...)``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def load(data, op_names: dict | None = None) -> Trace:
    """Read a trace: an ``.xplane.pb`` file, the trace directory holding
    one, or its serialized bytes."""
    from jax.profiler import ProfileData

    if isinstance(data, bytes):
        data = ProfileData.from_serialized_xspace(data)
    else:
        path = Path(data)
        data = ProfileData.from_file(
            str(xplane_file(path) if path.is_dir() else path))
    op_names = op_names or {}
    ops, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                events = sorted(
                    (Op(name, op_names.get(name, ""), ev.start_ns, ev.end_ns)
                     for ev in line.events
                     for name in [instruction(ev.name)]),
                    key=lambda o: (o.start_ns, -o.end_ns))
                ops[int(m.group(1))] = self_times(events)
            elif not m:
                spans.extend((ev.name[len(SPAN_PREFIX):], ev.start_ns,
                              ev.end_ns) for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
    return Trace(ops=ops, spans=sorted(spans, key=lambda s: s[1]))


def clip(ops: list, lo: float, hi: float) -> list:
    """The ops' intervals inside [lo, hi], as ``(start, end, op)``."""
    out = []
    for op in ops:
        a, b = max(op.start_ns, lo), min(op.end_ns, hi)
        if b > a:
            out.append((a, b, op))
    return out


def busy_intervals(ops: list, lo: float, hi: float) -> list:
    """The union of the ops' intervals inside [lo, hi], merged."""
    merged = []
    for a, b, _ in sorted(clip(ops, lo, hi), key=lambda x: x[0]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_ns(ops: list, lo: float, hi: float) -> float:
    return float(sum(b - a for a, b in busy_intervals(ops, lo, hi)))


def idle_gaps(ops: list, lo: float, hi: float) -> list:
    """``(start, end)`` of the stretches of [lo, hi] with no op running."""
    gaps, cur = [], lo
    for a, b in busy_intervals(ops, lo, hi):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def span_at(spans: list, a: float, b: float) -> str:
    """The harness span inside the window that overlaps [a, b] the most,
    the innermost on a tie."""
    best, best_key = "none", (0.0, 0.0)
    for name, s, e in spans:
        if name == "window":
            continue
        overlap = min(b, e) - max(a, s)
        if overlap > 0 and (overlap, -(e - s)) > best_key:
            best, best_key = name, (overlap, -(e - s))
    return best


def _self_in(op: Op, a: float, b: float) -> float:
    """The part of the op's self time that falls in [a, b] (its share of
    the op's interval there)."""
    return op.self_ns * (b - a) / max(op.end_ns - op.start_ns, 1e-9)


def op_seconds(ops: list, lo: float, hi: float, where) -> float:
    """Device self time in seconds inside [lo, hi] of the ops for which
    ``where(op)``."""
    return float(sum(_self_in(op, a, b) for a, b, op in clip(ops, lo, hi)
                     if where(op))) * 1e-9


def in_stack(*names: str):
    def where(op: Op) -> bool:
        return any(f"jit({n})" in op.stack for n in names)
    return where


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10) -> list:
    """The k ops with the most device self time, averaged over the
    devices: ``[name @ stack, seconds]``."""
    total: dict = {}
    for ops in trace.ops.values():
        for a, b, op in clip(ops, lo, hi):
            key = f"{op.name} @ {op.stack}" if op.stack else op.name
            total[key] = total.get(key, 0.0) + _self_in(op, a, b) * 1e-9
    n = max(len(trace.ops), 1)
    return [[name, secs / n] for name, secs in
            sorted(total.items(), key=lambda kv: -kv[1])[:k]]


def top_gaps(trace: Trace, lo: float, hi: float, k: int = 10) -> list:
    """The k longest idle gaps of any device, each labelled with the
    harness span the host was in: ``[span, seconds]``."""
    gaps = [(b - a, a, b) for ops in trace.ops.values()
            for a, b in idle_gaps(ops, lo, hi)]
    gaps.sort(reverse=True)
    return [[span_at(trace.spans, a, b), float(d) * 1e-9]
            for d, a, b in gaps[:k]]


def mean(values) -> float:
    return float(np.mean(list(values)))
