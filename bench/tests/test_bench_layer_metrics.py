"""The reader of the fused kernel's device time per step, on a hand-built
trace: the named kernel, Pallas kernels of another name or none that it
must not count, and fusions inside and outside the named scopes."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.trace_reduce import Span, summarize  # noqa: E402

NAMED = ('%stream_collide.13 = f32[236018,19,64]{2,1,0:T(8,128)} custom-call('
         '%p.1), custom_call_target="tpu_custom_call"')
UNNAMED = ('%closed_call.11 = f32[236018,19,64]{2,1,0:T(8,128)} custom-call('
           '%p.2), custom_call_target="tpu_custom_call"')
RW = ('%stream_collide_rw.2 = f32[8,19,64]{2,1,0} custom-call(%p.3), '
      'custom_call_target="tpu_custom_call"')
NEBB = ('%fusion.99 = f32[9638016]{0} fusion(%p.4), kind=kLoop, metadata={'
        'op_name="jit(fn)/while/body/lbm.phase.boundary/jit(_take)/gather"}')
SCATTER = ('%fusion.106 = f32[236018,19,64]{2,1,0} fusion(%p.5), metadata={'
           'op_name="jit(fn)/while/body/lbm.phase.boundary/scatter"}')
PACK = ('%fusion.110 = f32[236018,19,64]{2,1,0} fusion(%p.6), metadata={'
        'op_name="jit(fn)/while/body/lbm.phase.pack/dynamic_update_slice"}')
COPY = '%copy.97 = f32[236018,19,64]{2,1,0:T(8,128)} copy(%p.7)'


def _trace():
    # window 0..10 s on each of two devices; device 1 runs the same ops
    # shifted by 0.5 s, so the readers average the same unions
    ops = [
        Span(NAMED, 1.0, 3.0), Span(COPY, 2.5, 3.5),   # overlaps the kernel
        Span(UNNAMED, 3.5, 4.5), Span(RW, 4.5, 5.0),
        Span(NEBB, 5.0, 6.5), Span(SCATTER, 6.25, 7.0),
        Span(PACK, 7.0, 7.25), Span(NAMED, 8.0, 9.0),
    ]
    shifted = [Span(o.name, o.start + 0.5, o.end + 0.5) for o in ops]
    return summarize({"/device:TPU:0": ops, "/device:TPU:1": shifted},
                     [Span("bench.window", 0.0, 10.5)])


def _run(trace, steps=20):
    return types.SimpleNamespace(trace=trace, steps=steps,
                                 kernel="tpu_custom_call",
                                 device={"kind": "TPU v5 lite"})


def _read(name, run):
    return harness.module(ROOT, "metrics", name).read(run)


def test_kernel_ms_counts_the_named_kernel_only():
    # 2 s + 1 s of %stream_collide; the unnamed kernel and the rw probe,
    # though tpu_custom_calls, are left out
    assert _read("kernel.stream_collide_ms", _run(_trace())) == \
        pytest.approx(1e3 * 3.0 / 20)


def test_kernel_ms_differs_from_the_tpu_custom_call_union():
    # what the older pattern reads on the same trace: every Pallas kernel
    s = _trace()
    assert s.union_s("tpu_custom_call") == pytest.approx(4.5)
    assert s.union_s(r"^%stream_collide\b") == pytest.approx(3.0)


@pytest.mark.parametrize("steps", [None, 0], ids=["no_trace", "no_steps"])
def test_kernel_ms_returns_nothing_without_a_trace_or_steps(steps):
    run = _run(None) if steps is None else _run(_trace(), steps=steps)
    assert _read("kernel.stream_collide_ms", run) is None


def test_kernel_ms_returns_nothing_on_an_unnamed_program():
    # the program before its kernel was named: the reader falls silent
    # instead of counting some other kernel
    ops = {"/device:TPU:0": [Span(UNNAMED, 1.0, 3.0), Span(NEBB, 3.0, 4.0)]}
    s = summarize(ops, [Span("bench.window", 0.0, 5.0)])
    assert _read("kernel.stream_collide_ms", _run(s)) is None


def test_kernel_ms_leaves_out_scoped_fusions():
    # the NEBB pass, the pack work and the copies around the kernel are
    # not the kernel, whatever scope they carry
    ops = {"/device:TPU:0": [Span(NAMED, 0.0, 1.0), Span(NEBB, 1.0, 2.0),
                             Span(SCATTER, 2.0, 3.0), Span(PACK, 3.0, 4.0),
                             Span(COPY, 4.0, 5.0)]}
    s = summarize(ops, [Span("bench.window", 0.0, 5.0)])
    assert _read("kernel.stream_collide_ms", _run(s, steps=10)) == \
        pytest.approx(1e3 * 1.0 / 10)
