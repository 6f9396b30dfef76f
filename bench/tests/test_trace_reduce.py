"""The reduction from a profiler trace to device intervals and name-stack
attribution."""

from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def _ops(*spans, stack=""):
    return [tr.Op(f"op{i}", stack, a, b) for i, (a, b) in enumerate(spans)]


def test_busy_is_the_union_inside_the_window():
    ops = _ops((0, 10), (5, 20), (30, 40), (45, 60))
    assert tr.busy_intervals(ops, 2, 50) == [[2, 20], [30, 40], [45, 50]]
    assert tr.busy_ns(ops, 2, 50) == 18 + 10 + 5


def test_idle_gaps_cover_the_rest_of_the_window():
    ops = _ops((10, 20), (15, 25), (40, 50))
    assert tr.idle_gaps(ops, 0, 60) == [(0, 10), (25, 40), (50, 60)]
    gaps = sum(b - a for a, b in tr.idle_gaps(ops, 0, 60))
    assert gaps + tr.busy_ns(ops, 0, 60) == 60


def test_gap_takes_the_innermost_span_it_overlaps_most():
    spans = [("window", 0, 100), ("readback", 10, 30), ("dispatch", 30, 34),
             ("dispatch", 34, 90)]
    assert tr.span_at(spans, 28, 33) == "dispatch"
    assert tr.span_at(spans, 12, 20) == "readback"
    assert tr.span_at(spans, 95, 99) == "none"


def test_attribution_by_name_stack():
    bfs = "jit(step_raw)/while/body/closed_call/vmap(jit(bfs_sssp))/scatter"
    path = "jit(step_raw)/while/body/vmap(jit(sample_path))/gather"
    ops = tr.self_times([tr.Op("a", bfs, 0, 30), tr.Op("b", path, 30, 40),
                         tr.Op("c", "jit(step_raw)/add", 40, 45)])
    assert tr.op_seconds(ops, 0, 100, tr.in_stack("bfs_sssp")) == \
        pytest.approx(30e-9)
    assert tr.op_seconds(ops, 0, 100, tr.in_stack("sample_path")) == \
        pytest.approx(10e-9)
    assert tr.op_seconds(ops, 0, 100, lambda op: bool(op.stack)) == \
        pytest.approx(45e-9)


def test_self_time_leaves_out_nested_ops():
    loop = tr.Op("while.1", "jit(step_raw)/while", 0, 100)
    body = [tr.Op("f.1", "x", 10, 40), tr.Op("f.2", "x", 50, 101)]
    tr.self_times([loop, *body])
    assert loop.self_ns == 100 - 30 - 50
    assert [op.self_ns for op in body] == [30, 51]


def test_hlo_op_names_reads_the_metadata():
    text = ('  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
            'metadata={op_name="jit(step_raw)/vmap(jit(bfs_sssp))/add" '
            'source_file="x.py"}\n'
            '  ROOT scatter.7 = f32[8]{0} scatter(%a), '
            'metadata={op_name="jit(step_raw)/scatter"}\n')
    assert tr.hlo_op_names(text) == {
        "fusion.3": "jit(step_raw)/vmap(jit(bfs_sssp))/add",
        "scatter.7": "jit(step_raw)/scatter"}


@pytest.fixture(scope="module")
def chip_trace():
    """Four epochs of ``g500-bc.1chip`` traced on one TPU v5e, and the HLO
    text of the step program that ran them."""
    import gzip
    names = tr.hlo_op_names(gzip.decompress(
        (DATA / "g500-bc-1chip.hlo.txt.gz").read_bytes()).decode())
    return tr.load(gzip.decompress(
        (DATA / "g500-bc-1chip.xplane.pb.gz").read_bytes()), names)


def test_chip_trace_has_one_device_and_the_window(chip_trace):
    assert list(chip_trace.ops) == [0]
    lo, hi = chip_trace.window()
    assert 7.9e9 < hi - lo < 8.0e9
    assert len(chip_trace.ops[0]) == 23308


def test_chip_trace_self_times_add_up_to_busy_time(chip_trace):
    ops = chip_trace.ops[0]
    lo, hi = chip_trace.window()
    busy = tr.busy_ns(ops, lo, hi)
    assert 0.99 * (hi - lo) < busy <= hi - lo
    assert sum(op.self_ns for op in ops) == pytest.approx(busy, rel=1e-6)
    assert min(op.self_ns for op in ops) >= 0


def test_chip_trace_attribution(chip_trace):
    """Nearly all device time has a name stack, and the sampler's two
    functions take nearly all of it."""
    ops = chip_trace.ops[0]
    lo, hi = chip_trace.window()
    busy = tr.busy_ns(ops, lo, hi) * 1e-9
    known = tr.op_seconds(ops, lo, hi, lambda op: bool(op.stack))
    bfs = tr.op_seconds(ops, lo, hi, tr.in_stack("bfs_sssp"))
    path = tr.op_seconds(ops, lo, hi, tr.in_stack("sample_path"))
    assert known > 0.99 * busy
    assert bfs > 0.4 * busy and path > 0.4 * busy
    assert bfs + path > 0.99 * busy


def test_chip_trace_breakdown(chip_trace):
    lo, hi = chip_trace.window()
    ops = tr.top_ops(chip_trace, lo, hi)
    assert len(ops) == 10 and "scatter-add" in ops[0][0]
    assert ops == sorted(ops, key=lambda o: -o[1])
    gaps = tr.top_gaps(chip_trace, lo, hi)
    assert len(gaps) == 10
    assert {g[0] for g in gaps} <= {n for n, _, _ in chip_trace.spans}
