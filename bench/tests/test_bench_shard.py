"""The sharded cell's readers on hand-built four-device traces, program
text and gauges, and its driver's refusal of a program without the
per-slab API."""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.trace_reduce import Span, summarize  # noqa: E402

SCOPE = 'metadata={op_name="jit(run)/while/body/shard_map/lbm.phase.halo/%s"}'
HLO = "\n".join([
    "%fused_computation.3 (param_0: f32[215001,19,64]) -> f32[8870,19,64] {",
    "  %gather.33 = f32[8870,19,64]{2,1,0} gather(%param_0), "
    + SCOPE % "gather",
    "}",
    "%body (p: (f32[215001,19,64])) -> (f32[215001,19,64]) {",
    "  %fusion.79 = f32[8870,19,64]{2,1,0} fusion(%gte.1), kind=kLoop, "
    "calls=%fused_computation.3, " + SCOPE % "gather",
    "  %collective-permute-start.1 = (f32[8870,19,64]{2,1,0}, "
    "f32[8870,19,64]{2,1,0}) collective-permute-start(%fusion.79), "
    + SCOPE % "ppermute",
    "  %fusion.80 = f32[8870,19,64]{2,1,0} fusion(%gte.1), "
    'metadata={op_name="jit(run)/while/body/shard_map/lbm.phase.boundary/x"}',
    "  %collective-permute-done.1 = f32[8870,19,64]{2,1,0} "
    "collective-permute-done(%collective-permute-start.1), "
    + SCOPE % "ppermute",
    "  %fusion.81 = f32[215001,19,64]{2,1,0} fusion(%gte.1, "
    "%collective-permute-done.1), kind=kLoop, " + SCOPE % "scatter",
    "  %stream_collide.7 = f32[215001,19,64]{2,1,0} custom-call(%fusion.81), "
    'custom_call_target="tpu_custom_call", '
    'metadata={op_name="jit(run)/while/body/lbm.phase.stream_collide/x"}',
    "}"])


def _event(line):
    """A trace event's name: the instruction's text without metadata."""
    return line.strip().split(", metadata=")[0]


EV = {name: _event(line) for line in HLO.splitlines()
      for name in ("fusion.79", "collective-permute-start.1", "fusion.80",
                   "collective-permute-done.1", "fusion.81",
                   "stream_collide.7")
      if line.strip().startswith(f"%{name} =")}


def _trace(n_dev=4, kernel_end=8.0):
    """Window 0..10 s on ``n_dev`` devices, each running the exchange once:
    the row gather 0.2..0.3 s, the collective's start 0.3..0.35 s, an op of
    another scope 0.35..0.6 s while the transfer runs, the done 0.6..0.65 s
    and the row scatter 0.65..0.7 s; then the kernel from 1 s."""
    ops = {}
    for d in range(n_dev):
        ops[f"/device:TPU:{d}"] = [
            Span(EV["fusion.79"], 0.2, 0.3),
            Span(EV["collective-permute-start.1"], 0.3, 0.35),
            Span(EV["fusion.80"], 0.35, 0.6),
            Span(EV["collective-permute-done.1"], 0.6, 0.65),
            Span(EV["fusion.81"], 0.65, 0.7),
            Span(EV["stream_collide.7"], 1.0, kernel_end)]
    return summarize(ops, [Span("bench.window", 0.0, 10.0)])


def _run(trace, steps=10, **kw):
    return types.SimpleNamespace(trace=trace, steps=steps, **kw)


def _read(name, run):
    return harness.module(ROOT, "metrics", name).read(run)


def test_halo_scope_names_the_top_level_and_fused_ops():
    halo = harness.module(ROOT, "metrics", "dist.halo_ms")
    assert halo.scoped(HLO) == {"gather.33", "fusion.79", "fusion.81",
                                "collective-permute-start.1",
                                "collective-permute-done.1"}


def test_halo_ms_is_the_union_of_the_exchange_ops_and_transfers():
    # gather, start, the transfer under fusion.80, done, scatter: 0.2..0.7 s
    run = _run(_trace(), hlo=HLO)
    assert _read("dist.halo_ms", run) == pytest.approx(1e3 * 0.5 / 10)


def test_halo_ms_without_its_start_counts_the_events_only():
    ops = {"/device:TPU:0": [
        Span(EV["collective-permute-done.1"], 0.6, 0.65),
        Span(EV["fusion.81"], 0.65, 0.7),
        Span(EV["fusion.80"], 0.7, 0.9)]}
    run = _run(summarize(ops, [Span("bench.window", 0.0, 1.0)]), hlo=HLO)
    assert _read("dist.halo_ms", run) == pytest.approx(1e3 * 0.1 / 10)


def test_halo_ms_absent_without_a_collective():
    ops = {"/device:TPU:0": [Span(EV["stream_collide.7"], 1.0, 2.0)]}
    run = _run(summarize(ops, [Span("bench.window", 0.0, 3.0)]), hlo=HLO)
    assert _read("dist.halo_ms", run) is None


def test_halo_ms_absent_without_the_program_text():
    assert _read("dist.halo_ms", _run(_trace())) is None
    assert _read("dist.halo_ms", _run(_trace(), hlo=None)) is None


@pytest.mark.parametrize("name", ["dist.halo_ms"])
def test_trace_readers_read_nothing_untraced(name):
    assert _read(name, _run(None, hlo=HLO)) is None


def test_slab_pad_share_reads_the_plan_gauges():
    gauges = {"dist.slab.own_tiles_mean": 197056.0, "dist.slab.t_pad": 212910}
    run = _run(None, gauges=gauges)
    assert _read("dist.slab_pad_share", run) == pytest.approx(
        100.0 * (1 - 197056 / 212910))
    even = _run(None, gauges={"dist.slab.own_tiles_mean": 99.0,
                              "dist.slab.t_pad": 100})
    assert _read("dist.slab_pad_share", even) == pytest.approx(1.0)


def test_slab_pad_share_absent_without_the_gauges():
    assert _read("dist.slab_pad_share", _run(None)) is None
    assert _read("dist.slab_pad_share",
                 _run(None, gauges={"dist.slab.t_pad": 100})) is None


def test_shard_build_s_reads_the_driver_field():
    assert _read("setup.shard_build_s", _run(None, shard_build_s=12.5)) == 12.5
    assert _read("setup.shard_build_s", _run(None)) is None


FAKE_SOLVER = '''
class ShardedLBM:
    """A sharded solver without the per-slab state API."""

    def run(self, steps):
        raise AssertionError("must not be reached")
'''

RUN_CELL = '''
import json, sys, time
sys.path.insert(0, {root!r})
from bench import harness
bench = harness.load_benchmark({root!r})
cell = harness.workload(bench, "spheres-p07-384.shard4")
result = harness.run_cell({root!r}, bench, cell, seed=2**33 + 7, seconds=1.0,
                          trace=False, t0=time.perf_counter())
print(json.dumps(result))
'''


def test_driver_refuses_a_solver_without_the_slab_api(tmp_path):
    """The harness's own path from the cell to its result line, over a
    checkout whose ``ShardedLBM`` lacks the API: a non-zero exit that names
    every missing method, before any geometry is built, and no result."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    pkg = root / "src" / "repro" / "dist"
    pkg.mkdir(parents=True)
    (root / "src" / "repro" / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    (pkg / "lbm.py").write_text(FAKE_SOLVER)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-c", RUN_CELL.format(root=str(root))],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    for name in ("owned_node_coords", "load_state", "read_owned"):
        assert name in p.stderr, p.stderr
    assert "geometry_s" not in p.stderr
    assert not p.stdout.strip()
    with pytest.raises(json.JSONDecodeError):
        json.loads(p.stdout or "")
