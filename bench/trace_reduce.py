"""Reduce a profiler trace of the measured window to device time.

The benchmark marks its window and its calls with host annotations
(``bench.window``, ``bench.call``, ``bench.sync``).  On a TPU the device
plane's "XLA Ops" line nests the ops of a loop inside the loop's own event
(``%while.N``); only the innermost events count, so that a loop does not
hide the idle time between its ops.  The events are named by their HLO
instruction text, a Pallas kernel as a ``tpu_custom_call`` custom-call.
From these op events it takes, clipped to the window: the busy time (the union of op
intervals), the union of the ops a pattern matches (a kernel), the ops that
took most time, and the idle gaps, each labelled by the innermost benchmark
annotation the host was in at the gap's middle.  Times are seconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "bench.window"
HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
NAME_CHARS = 200     # of an op's HLO text in the breakdown


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


def merge(spans) -> list[tuple[float, float]]:
    """Union of intervals as sorted disjoint (start, end) pairs."""
    out: list[list[float]] = []
    for s, e in sorted((sp.start, sp.end) for sp in spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def leaves(spans) -> list[Span]:
    """The spans that hold no later-starting span of the same line."""
    ordered = sorted(spans, key=lambda sp: (sp.start, -sp.end))
    return [sp for sp, nxt in zip(ordered, ordered[1:] + [None])
            if nxt is None or nxt.start >= sp.end]


def total(merged) -> float:
    return sum(e - s for s, e in merged)


def _clip(spans, lo: float, hi: float) -> list[Span]:
    return [Span(s.name, max(s.start, lo), min(s.end, hi))
            for s in spans if s.end > lo and s.start < hi]


@dataclasses.dataclass
class TraceSummary:
    """Device ops (per device) and host annotations inside the window."""

    window: tuple[float, float]
    ops: dict[str, list[Span]]          # device plane name -> clipped ops
    host: list[Span]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _mean(self, fn) -> float:
        return sum(fn(v) for v in self.ops.values()) / max(1, len(self.ops))

    @property
    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the devices."""
        return self._mean(lambda ops: total(merge(ops)))

    def union_s(self, pattern: str) -> float:
        """Seconds in which an op whose name matches ``pattern`` (a regular
        expression, searched) ran, averaged over the devices."""
        rx = re.compile(pattern)
        return self._mean(
            lambda ops: total(merge(o for o in ops if rx.search(o.name))))

    def top_ops(self, n: int = 10) -> list[list]:
        """[name, seconds] of the ops that took most time, summed by name
        over the devices and divided by their number.  A name is the op's
        HLO text cut to its first ``NAME_CHARS`` characters (instruction,
        shape, op and the first operands); a kernel's whole text runs to
        kilobytes."""
        acc: dict[str, float] = {}
        for ops in self.ops.values():
            for o in ops:
                acc[o.name] = acc.get(o.name, 0.0) + (o.end - o.start)
        k = max(1, len(self.ops))
        return [[name[:NAME_CHARS], t / k] for name, t in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """[label, seconds] of the longest idle gaps of any device, labelled
        by the innermost benchmark annotation around the gap's middle."""
        gaps = []
        for ops in self.ops.values():
            t = self.window[0]
            for s, e in merge(ops) + [(self.window[1], self.window[1])]:
                if s > t:
                    gaps.append((t, s))
                t = max(t, e)
        gaps.sort(key=lambda g: -(g[1] - g[0]))
        out = []
        for s, e in gaps[:n]:
            mid = 0.5 * (s + e)
            around = [h for h in self.host if h.start <= mid <= h.end]
            label = (min(around, key=lambda h: h.end - h.start).name
                     if around else "host:outside")
            out.append([label, e - s])
        return out


def summarize(ops: dict[str, list[Span]], host: list[Span]) -> TraceSummary:
    """Clip to the (single) ``bench.window`` annotation."""
    windows = [h for h in host if h.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} annotation, "
                         f"found {len(windows)}")
    lo, hi = windows[0].start, windows[0].end
    return TraceSummary(
        (lo, hi), {d: _clip(v, lo, hi) for d, v in ops.items()},
        _clip([h for h in host if h.name != WINDOW], lo, hi))


def load(trace_dir: str):
    """Device op spans and benchmark host annotations of the one
    ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one trace under {trace_dir}: {paths}")
    ops: dict[str, list[Span]] = {}
    host: list[Span] = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = leaves(
                        Span(ev.name, ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Span(ev.name, ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9)
                            for ev in line.events
                            if ev.name.startswith(HOST_PREFIX))
    return ops, host
