"""The readers of the program's own spans and counters, on a run of the
tiny cell (``tiny.py``) on the CPU.  The CPU has no device trace, so the
readers of device ops get one laid out from the program's spans: each
epoch's op runs from the end of its dispatch to the end of its readback,
under the named scope ``bfs_level``."""

import sys

import pytest

from bench import cell as cellmod, harness, report, trace_reduce as tr
from bench.tests import tiny

NEW = ("bfs_levels_per_round", "bfs_ms_per_level", "path_live_step_share",
       "boundary_idle_ms_per_epoch", "csr_build_s", "preprocess_s",
       "program_load_s", "first_epoch_s")
STACK = ("jit(step_raw)/while/body/closed_call/vmap(jit(bfs_sssp))/while/"
         "body/bfs_level/scatter-add")
OFFSET = 3.5e12     # the trace's clock, ns, less the program's


def _read(ctx) -> dict:
    return {name: cellmod.load_module("metrics", name).read(ctx)
            for name in NEW}


@pytest.fixture(scope="module")
def run():
    """The tiny cell's window, the program's records after it, and a trace
    laid out from them."""
    from repro.runtime import spans

    wins = []
    mp = pytest.MonkeyPatch()
    real = harness.run
    mp.setattr(harness, "run",
               lambda *a, **k: wins.append(real(*a, **k)) or wins[-1])
    spans.clear()
    try:
        tiny.values(1)
    finally:
        mp.undo()
    win = wins[0]
    recs = spans.records()
    per_epoch = (int(win.cell.config["batch"])
                 * win.instance.rounds_per_epoch)
    epochs = win.samples / per_epoch
    steps = [r for r in recs if r.name == "session.step"][-round(epochs):]

    def child(step, name):
        return next(r for r in recs if r.parent == step.id and r.name == name)

    def ns(seconds):
        return seconds * 1e9 + OFFSET

    ops = [tr.Op(f"fusion.{i}", STACK, ns(child(s, "session.dispatch").end),
                 ns(child(s, "session.readback").end))
           for i, s in enumerate(steps)]
    outer = [("step", ns(s.start) - 2e3, ns(s.end) + 3e3) for s in steps]
    lo, hi = outer[0][1], outer[-1][2]
    ctx = report.Context(
        trace=tr.Trace(ops={0: tr.self_times(ops)},
                       spans=[("window", lo, hi), *outer]),
        lo=lo, hi=hi, epochs=epochs,
        rounds=epochs * win.instance.rounds_per_epoch,
        memory_peak_bytes=win.memory_peak_bytes)
    return win, steps, ops, ctx, child


def test_every_new_metric_has_its_reader():
    cell = cellmod.load("g500-bc.1chip")
    names = [m["name"] for m in cell.per_layer]
    assert names[-len(NEW):] == list(NEW)


def test_counter_readers(run):
    win, steps, _, ctx, _ = run
    values = _read(ctx)
    rounds = sum(s.total["rounds"] for s in steps)
    assert rounds == round(ctx.rounds)
    levels = values["bfs_levels_per_round"]
    assert levels == sum(s.total["bfs_levels"] for s in steps) / rounds
    assert 1 <= levels <= win.instance._graph()[1].diam_levels
    assert 0 < values["path_live_step_share"] <= 100


def test_set_up_readers_fit_inside_the_set_up(run):
    win, _, _, ctx, _ = run
    values, setup = _read(ctx), win.setup
    assert 0 < values["csr_build_s"] <= setup["graph"]
    assert 0 < values["preprocess_s"] <= setup["preprocess"]
    assert 0 < values["program_load_s"] <= setup["jax_compile_or_load"]
    assert 0 < values["first_epoch_s"] <= setup["warmup"]
    parts = ("csr_build_s", "preprocess_s", "program_load_s", "first_epoch_s")
    assert sum(values[k] for k in parts) < setup["setup_s"]


def test_device_readers_on_a_laid_out_trace(run, capsys):
    _, steps, ops, ctx, child = run
    values = _read(ctx)
    levels = sum(s.total["bfs_levels"] for s in steps)
    busy_ms = sum(op.end_ns - op.start_ns for op in ops) * 1e-6
    assert values["bfs_ms_per_level"] == pytest.approx(busy_ms / levels)
    # each boundary: the device waits from the end of a readback to the
    # end of the next dispatch
    gaps = [child(b, "session.dispatch").end - child(a, "session.readback").end
            for a, b in zip(steps, steps[1:])]
    assert values["boundary_idle_ms_per_epoch"] == pytest.approx(
        1e3 * sum(gaps) / len(gaps), rel=1e-6)
    residual = float(capsys.readouterr().err.split("residual ")[-1].split()[0])
    assert residual < 1.0     # us


def test_a_program_without_spans_reads_nothing(run, monkeypatch):
    """On a program that has no ``repro.runtime.spans`` every reader returns
    None, and none raises."""
    import repro.runtime

    ctx = run[3]
    monkeypatch.delattr(repro.runtime, "spans")
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    assert _read(ctx) == dict.fromkeys(NEW)


def test_device_readers_need_device_ops(run):
    ctx = run[3]
    bare = report.Context(
        trace=tr.Trace(ops={}, spans=ctx.trace.spans), lo=ctx.lo, hi=ctx.hi,
        epochs=ctx.epochs, rounds=ctx.rounds,
        memory_peak_bytes=ctx.memory_peak_bytes)
    values = _read(bare)
    assert values["bfs_ms_per_level"] is None
    assert values["boundary_idle_ms_per_epoch"] is None
