"""The trace reduction and the readers built on it, on a synthetic trace
with known intervals."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, peaks  # noqa: E402
from bench.trace_reduce import Span, leaves, merge, summarize, total  # noqa: E402

KERNEL = "^stream_collide"


def _trace():
    # window 10..20 s; device ops: two kernel calls overlapping a copy,
    # one boundary op, one op that starts before the window
    ops = {"/device:TPU:0": [
        Span("before", 8.0, 10.5),
        Span("stream_collide.1", 11.0, 13.0),
        Span("copy.2", 12.5, 13.5),
        Span("stream_collide.1", 14.0, 16.0),
        Span("nebb_fusion", 16.0, 17.0),
        Span("after", 21.0, 22.0),
    ]}
    host = [Span("bench.window", 10.0, 20.0),
            Span("bench.call", 10.0, 10.2),
            Span("bench.sync", 10.2, 17.2),
            Span("bench.call", 17.2, 17.3),
            Span("bench.sync", 17.3, 20.0),
            Span("bench.warm", 1.0, 5.0)]
    return summarize(ops, host)


def test_merge_and_total():
    m = merge([Span("a", 0, 2), Span("b", 1, 3), Span("c", 5, 6)])
    assert m == [(0, 3), (5, 6)]
    assert total(m) == 4


def test_leaves_drop_the_loops_around_ops():
    loop = Span("%while.56 = while(...)", 0.0, 10.0)
    inner = Span("%while.57 = while(...)", 1.0, 6.0)
    ops = [Span("k", 1.0, 3.0), Span("k", 3.5, 6.0), Span("copy", 7.0, 8.0)]
    assert leaves([loop, inner] + ops) == ops
    assert leaves([]) == []
    # touching spans are both leaves
    assert leaves([Span("a", 0, 1), Span("b", 1, 2)]) == [Span("a", 0, 1),
                                                          Span("b", 1, 2)]


def test_busy_kernel_and_idle():
    s = _trace()
    assert s.window_s == pytest.approx(10.0)
    # 10..10.5, 11..13.5, 14..17
    assert s.busy_s == pytest.approx(0.5 + 2.5 + 3.0)
    assert s.union_s(KERNEL) == pytest.approx(4.0)
    assert s.union_s("^nothing") == 0.0


def test_top_ops_and_gaps():
    s = _trace()
    top = dict(s.top_ops())
    assert top["stream_collide.1"] == pytest.approx(4.0)
    assert top["before"] == pytest.approx(0.5)
    assert "after" not in top and len(s.top_ops(2)) == 2
    gaps = s.idle_gaps()
    # 17..20 (host in sync), 10.5..11, 13.5..14 (host in the first sync)
    assert gaps[0][0] == "bench.sync" and gaps[0][1] == pytest.approx(3.0)
    assert sorted(g[1] for g in gaps) == pytest.approx([0.5, 0.5, 3.0])


def test_top_ops_cut_long_names_after_summing():
    long_a, long_b = "k" * 300 + "a", "k" * 300 + "b"
    ops = {"/device:TPU:0": [Span(long_a, 0.0, 1.0), Span(long_b, 1.0, 3.0)]}
    s = summarize(ops, [Span("bench.window", 0.0, 4.0)])
    assert s.top_ops() == [["k" * 200, pytest.approx(2.0)],
                           ["k" * 200, pytest.approx(1.0)]]
    assert s.union_s("b$") == pytest.approx(2.0)


def test_gap_outside_annotations_is_labelled():
    ops = {"/device:TPU:0": [Span("k", 0.0, 1.0)]}
    host = [Span("bench.window", 0.0, 3.0)]
    assert summarize(ops, host).idle_gaps() == [["host:outside", 2.0]]


def test_window_must_be_unique():
    with pytest.raises(ValueError):
        summarize({}, [])
    w = Span("bench.window", 0, 1)
    with pytest.raises(ValueError):
        summarize({}, [w, w])


def test_busy_averages_over_devices():
    ops = {"/device:TPU:0": [Span("k", 0.0, 1.0)],
           "/device:TPU:1": [Span("k", 0.0, 3.0)]}
    s = summarize(ops, [Span("bench.window", 0.0, 4.0)])
    assert s.busy_s == pytest.approx(2.0)
    assert s.top_ops() == [["k", pytest.approx(2.0)]]


def _run(trace, steps=10, n_fluid=1_000_000):
    e = [(0, 0, 0)] + [(1, 0, 0)] * 18
    return types.SimpleNamespace(
        trace=trace, steps=steps, n_fluid=n_fluid, q=19, e=e, itemsize=4,
        kernel=KERNEL, device={"kind": "TPU v5 lite"})


@pytest.mark.parametrize("name,expected", [
    ("device.idle_share", 40.0),
    ("backend.other_device_ms", 1e3 * 2.0 / 10),
])
def test_trace_readers(name, expected):
    value = harness.module(ROOT, "metrics", name).read(_run(_trace()))
    assert value == pytest.approx(expected)


def test_roofline_reader():
    run = _run(_trace())
    nbytes = 2 * 19 * run.n_fluid * 4 * run.steps
    bound = nbytes / 819e9           # bytes bound it at this size
    roof = harness.module(ROOT, "metrics", "stream_collide_roofline").read(run)
    assert roof == pytest.approx(100 * bound / 4.0)


@pytest.mark.parametrize("name", ["stream_collide_roofline",
                                  "backend.other_device_ms",
                                  "device.idle_share"])
def test_trace_readers_return_nothing_without_a_trace(name):
    assert harness.module(ROOT, "metrics", name).read(_run(None)) is None


@pytest.mark.parametrize("name", ["stream_collide_roofline",
                                  "backend.other_device_ms"])
def test_kernel_readers_return_nothing_without_the_kernel(name):
    run = _run(_trace())
    run.kernel = "^absent_kernel"
    assert harness.module(ROOT, "metrics", name).read(run) is None


def test_host_clock_readers():
    run = types.SimpleNamespace(steps=20, n_fluid=11_742_643, window_s=12.75,
                                setup_s=120.5, engine_build_s=110.25)
    read = lambda n: harness.module(ROOT, "metrics", n).read(run)  # noqa: E731
    assert read("mflups") == pytest.approx(20 * 11_742_643 / 12.75 / 1e6)
    assert read("setup_s") == 120.5
    assert read("setup.engine_build_s") == 110.25


def test_roofline_bound_is_the_larger():
    kind = "TPU v5 lite"
    assert peaks.roofline_seconds(819e9, 0, kind) == pytest.approx(1.0)
    assert peaks.roofline_seconds(0, 197e12, kind) == pytest.approx(1.0)
    assert peaks.roofline_seconds(819e9, 2 * 197e12, kind) == pytest.approx(2.0)
