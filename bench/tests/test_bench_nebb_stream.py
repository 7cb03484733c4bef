"""The reader of the NEBB pass's tile-list pull (``kernel.nebb_stream_ms``)
on hand-built traces: it counts the events named ``%nebb_stream`` and
nothing else, and falls silent on a program without the pull."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.trace_reduce import Span, summarize  # noqa: E402

KERNEL = ('%stream_collide.7 = f32[236018,19,64]{2,1,0:T(8,128)} custom-call('
          '%p.1), custom_call_target="tpu_custom_call"')
PULL = ('%nebb_stream.7 = f32[7926,19,64]{2,1,0:T(8,128)} custom-call('
        '%p.2), custom_call_target="tpu_custom_call"')
GATHER = ('%fusion.99 = f32[9638016]{0} fusion(%p.3), kind=kLoop, metadata={'
          'op_name="jit(fn)/while/body/lbm.phase.boundary/jit(_take)/gather"}')
SCATTER = ('%fusion.81 = f32[236018,19,64]{2,1,0} fusion(%p.4), metadata={'
           'op_name="jit(fn)/while/body/lbm.phase.boundary/scatter"}')


def _summary(ops):
    return summarize({"/device:TPU:0": ops}, [Span("bench.window", 0.0, 7.0)])


def _run(trace, steps=10):
    return types.SimpleNamespace(trace=trace, steps=steps,
                                 kernel="tpu_custom_call",
                                 device={"kind": "TPU v5 lite"})


def _read(name, run):
    return harness.module(ROOT, "metrics", name).read(run)


def test_nebb_stream_ms_counts_the_named_pull_only():
    # the pull beside the fused kernel, the old element gather and the
    # scatter: each kernel reader counts its own name, nothing else
    run = _run(_summary([Span(KERNEL, 0.0, 4.0), Span(PULL, 4.0, 4.5),
                         Span(GATHER, 4.5, 6.0), Span(SCATTER, 6.0, 6.25),
                         Span(PULL, 6.25, 6.5)]))
    assert _read("kernel.nebb_stream_ms", run) == \
        pytest.approx(1e3 * 0.75 / 10)
    assert _read("kernel.stream_collide_ms", run) == \
        pytest.approx(1e3 * 4.0 / 10)


@pytest.mark.parametrize("steps", [None, 0], ids=["no_trace", "no_steps"])
def test_nebb_stream_ms_returns_nothing_without_a_trace_or_steps(steps):
    trace = None if steps is None else _summary([Span(PULL, 0.0, 1.0)])
    assert _read("kernel.nebb_stream_ms", _run(trace, steps=steps)) is None


def test_nebb_stream_ms_returns_nothing_on_a_program_without_the_pull():
    # the parent program: its NEBB pass gathers in XLA, no event is named
    run = _run(_summary([Span(KERNEL, 0.0, 4.0), Span(GATHER, 4.0, 6.0)]))
    assert _read("kernel.nebb_stream_ms", run) is None
