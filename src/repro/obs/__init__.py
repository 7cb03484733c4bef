"""``repro.obs`` — unified observability for the sparse-LBM stack.

Public API (everything else is implementation detail):

* :func:`get_metrics` / :func:`get_tracer` — the process-global
  :class:`~repro.obs.metrics.MetricRegistry` and
  :class:`~repro.obs.trace.SpanRecorder`.  Both start **disabled**: every
  ``inc``/``set``/``observe`` call on a disabled registry is an
  early-return no-op, and a disabled recorder's ``span`` is only the
  profiler annotation of its name (a no-op unless a profiler runs).
* :func:`phase_scope` — ``jax.named_scope`` around a traced phase of a
  step; always on, since a scope is HLO metadata and changes no compiled
  instruction (``tests/test_obs.py`` pins the names in the lowered step).
* :func:`enable` / :func:`disable` — flip the two collectors.
* :func:`use` — context manager that swaps in caller-owned registry /
  recorder instances (and restores the previous ones on exit), so
  ``benchmarks.common.timed_mflups`` and tests can collect into private
  instances without touching global state.

Instrumented code reads the globals at *call* time::

    from repro import obs
    reg = obs.get_metrics()
    if reg.enabled:
        reg.counter("lbm.step_total").inc(steps)

Metric names are catalogued in :data:`repro.obs.metrics.CATALOGUE` and
documented in the README "Observability" section.
"""
from __future__ import annotations

import contextlib

from repro.obs.metrics import (CATALOGUE, Counter, Gauge, Histogram,
                               MetricRegistry)
from repro.obs.trace import Span, SpanRecorder, phase_scope

_metrics = MetricRegistry(enabled=False)
_tracer = SpanRecorder(enabled=False)


def get_metrics() -> MetricRegistry:
    return _metrics


def get_tracer() -> SpanRecorder:
    return _tracer


def enable(metrics: bool = True, trace: bool = True) -> None:
    """Turn the global collectors on (enable before building an engine to
    record its ``lbm.setup`` spans)."""
    _metrics.enabled = metrics
    _tracer.enabled = trace


def disable() -> None:
    _metrics.enabled = False
    _tracer.enabled = False


@contextlib.contextmanager
def use(metrics: MetricRegistry | None = None,
        trace: SpanRecorder | None = None):
    """Temporarily route global obs lookups to caller-owned instances::

        reg, rec = MetricRegistry(), SpanRecorder()
        with obs.use(metrics=reg, trace=rec):
            eng.run(100)          # instrumentation lands in reg/rec

    Only the arguments given are swapped; previous instances (and their
    enabled state) are restored on exit, even on exceptions.
    """
    global _metrics, _tracer
    prev_m, prev_t = _metrics, _tracer
    if metrics is not None:
        _metrics = metrics
    if trace is not None:
        _tracer = trace
    try:
        yield
    finally:
        _metrics, _tracer = prev_m, prev_t


__all__ = [
    "CATALOGUE", "Counter", "Gauge", "Histogram", "MetricRegistry",
    "Span", "SpanRecorder", "disable", "enable", "get_metrics",
    "get_tracer", "phase_scope", "use",
]
