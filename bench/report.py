"""The result line's metrics: the end-to-end metrics of a run, or the
per-layer metrics read from its trace by the readers in ``bench/metrics``."""

from __future__ import annotations

import dataclasses

from . import cell as cellmod
from . import trace_reduce as tr


def end_to_end(win) -> dict:
    values = {"samples_per_s": win.samples / win.seconds,
              "setup_s": win.setup["setup_s"]}
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in win.cell.end_to_end}


@dataclasses.dataclass(frozen=True)
class Context:
    """What a per-layer reader reads: the trace of the window, the
    window's bounds on the trace clock, the epochs and sampling rounds
    each worker ran in it, and the device's memory counter."""

    trace: tr.Trace
    lo: float
    hi: float
    epochs: float
    rounds: float
    memory_peak_bytes: int


def per_layer(win, trace_dir) -> tuple[dict, dict]:
    """(metrics, extra keys of the result line) of a traced run."""
    cfg = win.cell.config
    trace = tr.load(trace_dir, tr.hlo_op_names(win.hlo_text))
    lo, hi = trace.window()
    per_epoch = (int(cfg["batch"]) * win.instance.rounds_per_epoch
                 * win.cell.chips)   # one worker per chip
    epochs = win.samples / per_epoch
    ctx = Context(trace=trace, lo=lo, hi=hi, epochs=epochs,
                  rounds=epochs * win.instance.rounds_per_epoch,
                  memory_peak_bytes=win.memory_peak_bytes)
    metrics = {}
    for m in win.cell.per_layer:
        value = cellmod.load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    busy = tr.mean(tr.busy_ns(ops, lo, hi) for ops in trace.ops.values())
    extra = {"device": {"busy_s": busy * 1e-9, "window_s": (hi - lo) * 1e-9},
             "breakdown": {"device_ops": tr.top_ops(trace, lo, hi),
                           "idle_gaps": tr.top_gaps(trace, lo, hi)}}
    return metrics, extra
