"""Tests for repro.obs — registry semantics, tracing, and the disabled-
mode invariants the instrumentation relies on:

* the registry is a correct Prometheus-style store (counter monotonicity,
  histogram bucketing, labelled series, reset-keeps-registrations),
* exports are deterministic (snapshot/JSONL byte-stable without
  intervening mutations),
* the span recorder nests correctly — including the serving chain
  ``sim.service.step > sim.group.step > lbm.ensemble.step`` and the
  engines' ``lbm.setup`` tree — and its Chrome-trace JSON round-trips with
  nesting intact,
* every span reaches a running profiler, recorder enabled or not,
* the step's named scopes are always in the lowered program, and the
  collectors' switches change nothing in it.
"""
import hashlib
import json

import jax
import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import _DEFAULT_BUCKETS, MetricRegistry
from repro.obs.trace import SpanRecorder


# --------------------------------------------------------------------------
# registry semantics
# --------------------------------------------------------------------------
def test_counter_accumulates_and_rejects_negative():
    reg = MetricRegistry()
    c = reg.counter("lbm.step_total")
    c.inc()
    c.inc(4)
    assert reg.value("lbm.step_total") == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert reg.value("lbm.step_total") == 5          # unchanged after raise


def test_gauge_last_write_wins():
    reg = MetricRegistry()
    reg.gauge("lbm.step.mflups").set(3.5)
    reg.gauge("lbm.step.mflups").set(2.0)
    assert reg.value("lbm.step.mflups") == 2.0


def test_instrument_identity_and_kind_mismatch():
    reg = MetricRegistry()
    assert reg.counter("x") is reg.counter("x")      # same series, same object
    assert reg.counter("x", sid="1") is not reg.counter("x", sid="2")
    with pytest.raises(TypeError):
        reg.gauge("x")                                # registered as counter


def test_labels_are_distinct_series():
    reg = MetricRegistry()
    reg.counter("sim.session.steps_total", sid="0").inc(6)
    reg.counter("sim.session.steps_total", sid="1").inc(9)
    assert reg.value("sim.session.steps_total", sid="0") == 6
    assert reg.value("sim.session.steps_total", sid="1") == 9
    assert reg.value("sim.session.steps_total") is None   # unlabelled: never
    per_label = reg.values("sim.session.steps_total")
    assert sorted(per_label.values()) == [6, 9]
    # label order in the call is irrelevant to series identity
    reg.counter("y", a="1", b="2").inc()
    reg.counter("y", b="2", a="1").inc()
    assert reg.value("y", b="2", a="1") == 2


def test_histogram_bucket_placement():
    reg = MetricRegistry()
    h = reg.histogram("sim.session.queue_wait_steps")
    assert h.buckets == tuple(float(b) for b in _DEFAULT_BUCKETS)
    for v in (0, 1, 2, 7, 1500):
        h.observe(v)
    # buckets are inclusive upper bounds; 1500 > 1000 -> +Inf bucket
    assert h.counts[0] == 2          # 0 and 1 into le=1
    assert h.counts[1] == 1          # 2 into le=2
    assert h.counts[3] == 1          # 7 into le=10
    assert h.counts[-1] == 1         # 1500 into +Inf
    assert h.count == 5 and h.sum == 1510
    # prometheus export: cumulative buckets, _sum/_count lines
    text = reg.prometheus_text()
    assert "# TYPE sim_session_queue_wait_steps histogram" in text
    assert 'sim_session_queue_wait_steps_bucket{le="+Inf"} 5' in text
    assert "sim_session_queue_wait_steps_count 5" in text


def test_reset_zeroes_but_keeps_registrations():
    reg = MetricRegistry()
    c = reg.counter("lbm.step_total")
    c.inc(10)
    reg.gauge("g").set(1.0)
    reg.histogram("h").observe(3)
    reg.event("sim.session.admit", sid=0)
    reg.reset()
    assert reg.value("lbm.step_total") == 0
    assert reg.value("g") == 0.0
    assert reg.histogram("h").count == 0
    assert reg.events == []
    c.inc(2)                          # held handle still lives on the registry
    assert reg.value("lbm.step_total") == 2


def test_disabled_registry_is_noop_but_readable():
    reg = MetricRegistry(enabled=False)
    reg.counter("c").inc(5)
    reg.gauge("g").set(1.0)
    reg.histogram("h").observe(3)
    reg.event("e")
    assert reg.value("c") == 0 and reg.value("g") == 0.0
    assert reg.histogram("h").count == 0 and reg.events == []
    reg.enabled = True
    reg.counter("c").inc(5)
    assert reg.value("c") == 5


# --------------------------------------------------------------------------
# export determinism
# --------------------------------------------------------------------------
def test_export_determinism(tmp_path):
    reg = MetricRegistry()
    # register in non-sorted order, with labels
    reg.gauge("z.last").set(1)
    reg.counter("a.first", sid="3").inc(2)
    reg.histogram("m.mid").observe(42)
    reg.event("ev", k="v")
    assert reg.snapshot() == reg.snapshot()
    p1, p2 = tmp_path / "m1.jsonl", tmp_path / "m2.jsonl"
    reg.write_jsonl(str(p1))
    reg.write_jsonl(str(p2))
    assert p1.read_bytes() == p2.read_bytes()        # byte-identical
    recs = [json.loads(line) for line in p1.read_text().splitlines()]
    assert [r["name"] for r in recs if r["type"] != "event"] == sorted(
        r["name"] for r in recs if r["type"] != "event")
    by_name = {r["name"]: r for r in recs}
    assert by_name["a.first"]["labels"] == {"sid": "3"}
    assert by_name["a.first"]["value"] == 2
    assert by_name["m.mid"]["count"] == 1 and by_name["m.mid"]["sum"] == 42
    assert by_name["ev"]["attrs"] == {"k": "v"}


# --------------------------------------------------------------------------
# span recorder + Chrome trace
# --------------------------------------------------------------------------
def test_span_nesting_and_aggregate():
    rec = SpanRecorder()
    with rec.span("outer", steps=2):
        with rec.span("inner"):
            pass
        with rec.span("inner"):
            pass
    outer, = rec.find("outer")
    inners = rec.find("inner")
    assert outer.parent == -1 and outer.attrs == {"steps": 2}
    assert all(s.parent == outer.sid for s in inners)
    agg = rec.aggregate()
    assert agg["inner"]["count"] == 2 and agg["outer"]["count"] == 1
    assert agg["outer"]["seconds"] >= agg["inner"]["seconds"] >= 0
    rec.reset()
    assert rec.spans == [] and rec.find("outer") == []


def test_disabled_recorder_records_nothing():
    rec = SpanRecorder(enabled=False)
    with rec.span("x"):
        pass
    assert rec.spans == []


def test_chrome_trace_schema_round_trip(tmp_path):
    rec = SpanRecorder()
    with rec.span("sim.service.step", steps=4):
        with rec.span("sim.group.step", group="abc"):
            pass
    path = str(tmp_path / "trace.json")
    assert rec.save(path) == path
    doc = json.loads(open(path).read())              # full JSON round-trip
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    assert meta and meta[0]["args"]["name"] == "repro"
    spans = [e for e in events if e["ph"] == "X"]
    assert len(spans) == 2
    by_name = {e["name"]: e for e in spans}
    svc, grp = by_name["sim.service.step"], by_name["sim.group.step"]
    # nesting survives via explicit sid/parent args AND by time containment
    assert grp["args"]["parent"] == svc["args"]["sid"]
    assert svc["ts"] <= grp["ts"]
    assert grp["ts"] + grp["dur"] <= svc["ts"] + svc["dur"] + 1e-3
    assert svc["args"]["steps"] == 4 and grp["args"]["group"] == "abc"
    assert svc["cat"] == "sim"
    for e in spans:                                   # schema fields present
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                "args"} <= set(e)


# --------------------------------------------------------------------------
# global switch / obs.use
# --------------------------------------------------------------------------
def test_globals_start_disabled_and_use_restores():
    assert not obs.get_metrics().enabled
    assert not obs.get_tracer().enabled
    reg, rec = MetricRegistry(), SpanRecorder()
    with obs.use(metrics=reg, trace=rec):
        assert obs.get_metrics() is reg and obs.get_tracer() is rec
        obs.get_metrics().counter("c").inc()
    assert obs.get_metrics() is not reg
    assert reg.value("c") == 1


def test_enable_disable_flip_only_the_collectors():
    eng = _tiny_duct("fused")
    program = _program_digest(eng)
    try:
        obs.enable(trace=True)
        assert obs.get_metrics().enabled and obs.get_tracer().enabled
        obs.enable(metrics=False, trace=False)
        assert not obs.get_metrics().enabled
        assert not obs.get_tracer().enabled
        obs.enable(metrics=True, trace=True)
        assert _program_digest(eng) == program
    finally:
        obs.disable()
    assert not obs.get_metrics().enabled and not obs.get_tracer().enabled
    assert not hasattr(obs, "set_device_annotations")


def test_span_reaches_the_profiler_when_disabled(tmp_path):
    from jax.profiler import ProfileData

    rec = SpanRecorder(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("lbm.probe.span", steps=1):
            jax.block_until_ready(jax.numpy.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("**/*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events}
    assert "lbm.probe.span" in names
    assert rec.spans == []


# --------------------------------------------------------------------------
# instrumented engine / serving stack
# --------------------------------------------------------------------------
def _tiny_engine(split_stream=False, backend="gather"):
    from repro.core.engine import LBMConfig, SparseTiledLBM

    geom = np.ones((6, 6, 6), np.uint8)
    cfg = LBMConfig(layout_scheme="xyz" if backend == "fused" else "paper",
                    periodic=(True, True, True), backend=backend,
                    split_stream=split_stream)
    return SparseTiledLBM(geom, cfg)


def _tiny_duct(backend):
    """A walled 8x8x16 duct with a velocity inlet and a pressure outlet,
    so the fused step runs its NEBB pass."""
    from repro.core.boundary import BoundarySpec
    from repro.core.engine import LBMConfig, SparseTiledLBM
    from repro.core.tiling import INLET, OUTLET
    from repro.data.geometry import duct

    bcs = ((INLET, BoundarySpec("velocity", (0, 0, 1),
                                velocity=(0.0, 0.0, 0.02))),
           (OUTLET, BoundarySpec("pressure", (0, 0, -1), rho=1.0)))
    cfg = LBMConfig(backend=backend, boundaries=bcs,
                    layout_scheme="xyz" if backend == "fused" else "paper")
    return SparseTiledLBM(duct(8, 8, 16), cfg)


def _lowered_step(eng, debug_info: bool = True) -> str:
    """The jitted step's StableHLO; with ``debug_info`` each op's location,
    which carries its named scopes (and the caller's source lines)."""
    return jax.jit(eng.backend.step).lower(
        eng.f, eng.backend.tables).as_text(debug_info=debug_info)


def _program_digest(eng) -> str:
    return hashlib.sha256(
        _lowered_step(eng, debug_info=False).encode()).hexdigest()


def test_fused_step_lowers_with_phase_scopes():
    """With obs disabled the fused step still names its phases: the kernel,
    the NEBB pass and the buffer work around the kernel."""
    obs.disable()
    text = _lowered_step(_tiny_duct("fused"))
    for scope in ("lbm.phase.stream_collide", "lbm.phase.boundary",
                  "lbm.phase.pack"):
        assert scope in text, scope


SETUP_CHILDREN = ["lbm.setup.tiling", "lbm.setup.stream_tables",
                  "lbm.setup.backend_tables", "lbm.setup.place",
                  "lbm.setup.initial_state"]


@pytest.mark.parametrize("backend", ["gather", "fused"])
def test_setup_spans_nest_under_lbm_setup(backend):
    rec = SpanRecorder()
    with obs.use(trace=rec):
        _tiny_duct(backend)
    root, = rec.find("lbm.setup")
    assert root.parent == -1 and root.attrs == {"backend": backend}
    children = sorted((s for s in rec.spans if s.parent == root.sid),
                      key=lambda s: s.ts_ns)
    assert [s.name for s in children] == SETUP_CHILDREN
    assert sum(s.dur_ns for s in children) <= root.dur_ns
    assert len(rec.spans) == 1 + len(SETUP_CHILDREN)


def test_disabled_recorder_records_no_setup_span():
    rec = SpanRecorder(enabled=False)
    with obs.use(trace=rec):
        _tiny_duct("fused")
    assert rec.spans == []


def test_sharded_setup_spans_share_the_engine_names():
    """The sharded engine's set-up spans are the engine's; its fused
    backend reads no stream tables, so it builds none and has no
    ``lbm.setup.stream_tables`` span."""
    from repro.core.engine import LBMConfig
    from repro.dist.lbm import ShardedLBM

    mesh = jax.make_mesh((1,), ("data",))
    cfg = LBMConfig(layout_scheme="xyz", periodic=(True, True, True),
                    backend="fused")
    rec = SpanRecorder()
    with obs.use(trace=rec):
        ShardedLBM(np.ones((8, 8, 8), np.uint8), cfg, mesh)
    root, = rec.find("lbm.setup")
    assert root.attrs == {"backend": "fused", "sharded": True}
    children = sorted((s for s in rec.spans if s.parent == root.sid),
                      key=lambda s: s.ts_ns)
    assert [s.name for s in children] == [
        name for name in SETUP_CHILDREN if name != "lbm.setup.stream_tables"]


SLAB_GAUGES = ("dist.slab.count", "dist.slab.own_tiles_max",
               "dist.slab.own_tiles_min", "dist.slab.own_tiles_mean",
               "dist.slab.t_pad", "dist.halo.tiles")


def _sharded_duct(slabs: int = 4):
    """A fused ``ShardedLBM`` of the walled duct with open ends in
    ``slabs`` z slabs, built as for a dry run (nothing placed) over a mesh
    that repeats the one CPU device."""
    from jax.sharding import Mesh

    from repro.core.boundary import BoundarySpec
    from repro.core.engine import LBMConfig
    from repro.core.tiling import INLET, OUTLET
    from repro.data.geometry import duct
    from repro.dist.lbm import ShardedLBM

    bcs = ((INLET, BoundarySpec("velocity", (0, 0, 1),
                                velocity=(0.0, 0.0, 0.02))),
           (OUTLET, BoundarySpec("pressure", (0, 0, -1), rho=1.0)))
    mesh = Mesh(np.array([jax.devices()[0]] * slabs), ("data",))
    return ShardedLBM(duct(8, 8, 32), LBMConfig(backend="fused",
                                                boundaries=bcs),
                      mesh, dryrun=True)


def test_slab_gauges_set_at_construction_when_enabled():
    from repro.obs.metrics import CATALOGUE

    assert all(name in CATALOGUE for name in SLAB_GAUGES)
    reg = MetricRegistry()
    with obs.use(metrics=reg):
        eng = _sharded_duct()
    own = eng.plan.own.sum(axis=1)
    h = eng.table_shapes()["su"].shape[1]
    assert reg.value("dist.slab.count") == 4
    assert reg.value("dist.slab.own_tiles_max") == own.max()
    assert reg.value("dist.slab.own_tiles_min") == own.min()
    assert reg.value("dist.slab.own_tiles_mean") == own.mean()
    assert reg.value("dist.slab.t_pad") == eng.plan.t_pad
    # the inner slabs send one layer up and one down
    assert reg.value("dist.halo.tiles") == 2 * h
    off = MetricRegistry(enabled=False)
    with obs.use(metrics=off):
        _sharded_duct()
    assert all(off.value(name) is None for name in SLAB_GAUGES)


def test_sharded_run_program_unchanged_by_the_registry():
    programs = []
    for enabled in (False, True):
        with obs.use(metrics=MetricRegistry(enabled=enabled)):
            eng = _sharded_duct()
        programs.append(str(jax.make_jaxpr(eng.run_fn(10))(
            eng.state_shape(), eng.table_shapes())))
    assert "ppermute" in programs[0]
    assert programs[0] == programs[1]


def test_engine_counters_only_when_enabled():
    eng = _tiny_engine()
    reg, rec = MetricRegistry(), SpanRecorder()
    with obs.use(metrics=reg, trace=rec):
        eng.step(2)
        eng.run(3)
    assert reg.value("lbm.step_total") == 5
    run_span, = rec.find("lbm.run")
    assert run_span.attrs["steps"] == 3
    eng.step(1)                                       # globals disabled again
    assert reg.value("lbm.step_total") == 5


def test_nebb_gauges_count_the_boundary_tiles():
    """The fused backend reports, at construction, the tiles its NEBB pass
    re-streams each step and their share of all tiles; a disabled
    registry gets nothing."""
    from repro.core.tiling import INLET, OUTLET

    reg = MetricRegistry()
    with obs.use(metrics=reg):
        eng = _tiny_duct("fused")
    types = eng.tiling.node_types
    b = int(np.isin(types, (INLET, OUTLET)).any(axis=1).sum())
    assert 0 < b < eng.tiling.num_tiles
    assert b == len(eng.backend.tables["bc"]["tiles"])
    assert reg.value("lbm.nebb.tiles") == b
    assert reg.value("lbm.nebb.tile_share") == b / eng.tiling.num_tiles
    off = MetricRegistry(enabled=False)
    with obs.use(metrics=off):
        _tiny_duct("fused")
    assert off.value("lbm.nebb.tiles") is None


def test_nebb_gauges_absent_without_boundaries():
    from repro.core.engine import LBMConfig, SparseTiledLBM

    reg = MetricRegistry()
    with obs.use(metrics=reg):
        eng = SparseTiledLBM(np.ones((8, 8, 8), np.uint8), LBMConfig(
            layout_scheme="xyz", periodic=(True, True, True),
            backend="fused"))
    assert "bc" not in eng.backend.tables
    assert reg.value("lbm.nebb.tiles") is None
    assert reg.value("lbm.nebb.tile_share") is None


def test_kernel_blocks_per_tile_gauge():
    """The fused backend reports, at construction, the per-tile input
    blocks each grid step of its kernel DMAs: the own f and bounce-mask
    blocks and the 18 linked neighbours' f blocks (D3Q19); the pull that
    gathered node types took 38.  The gather backend and a disabled
    registry get nothing."""
    reg = MetricRegistry()
    with obs.use(metrics=reg):
        _tiny_duct("fused")
    assert reg.value("lbm.kernel.blocks_per_tile") == 20
    gather = MetricRegistry()
    with obs.use(metrics=gather):
        _tiny_duct("gather")
    assert gather.value("lbm.kernel.blocks_per_tile") is None
    off = MetricRegistry(enabled=False)
    with obs.use(metrics=off):
        _tiny_duct("fused")
    assert off.value("lbm.kernel.blocks_per_tile") is None


def test_model_metrics_names_and_sanity():
    eng = _tiny_engine(split_stream=True)
    m = eng.model_metrics()
    assert 0 < m["lbm.bw.eqn10_fraction"] <= 1
    assert m["lbm.bw.eqn10_min_bytes"] == 2 * 19 * eng.n_fluid_nodes * 4
    fracs = (m["lbm.stream.interior_frac"] + m["lbm.stream.frontier_frac"]
             + m["lbm.stream.bounce_frac"])
    assert fracs == pytest.approx(1.0)
    assert 0 < m["lbm.tiles.utilisation"] <= 1
    assert m["lbm.index.bytes_per_node"] > 0


def test_sim_service_span_nesting_and_counters():
    """The serving chain must nest: sim.service.step > sim.group.step >
    lbm.ensemble.step, with per-tenant counters and a queue-wait histogram."""
    from repro.core.engine import LBMConfig
    from repro.sim.service import SimService

    geom = np.ones((6, 6, 6), np.uint8)
    cfg = LBMConfig(layout_scheme="paper", periodic=(True, True, True),
                    backend="gather")
    reg, rec = MetricRegistry(), SpanRecorder()
    with obs.use(metrics=reg, trace=rec):
        svc = SimService(slots=2)
        svc.submit(geom, cfg, steps=2)
        svc.submit(geom, cfg, steps=3)
        svc.submit(geom, cfg, steps=2)               # 3rd waits in queue
        svc.run()
    assert reg.value("sim.session.submitted_total") == 3
    assert reg.value("sim.session.admitted_total") == 3
    assert reg.value("sim.session.finished_total") == 3
    assert reg.value("sim.session.steps_total", sid="1") == 3
    hist = reg.histogram("sim.session.queue_wait_steps")
    assert hist.count == 3
    assert hist.counts[0] == 2                       # two seated immediately
    assert reg.value("sim.node_updates_total") > 0
    assert len(reg.values("lbm.mass.drift")) == 3    # one gauge per sid
    ev_names = {e["name"] for e in reg.events}
    assert {"sim.session.submit", "sim.session.admit",
            "sim.session.finish"} <= ev_names
    # span chain
    svc_spans = rec.find("sim.service.step")
    grp_spans = rec.find("sim.group.step")
    ens_spans = rec.find("lbm.ensemble.step")
    assert svc_spans and grp_spans and ens_spans
    svc_sids = {s.sid for s in svc_spans}
    grp_sids = {s.sid for s in grp_spans}
    assert all(s.parent in svc_sids for s in grp_spans)
    assert all(s.parent in grp_sids for s in ens_spans)


def test_watchdog_metrics():
    from repro.dist.ft import StepWatchdog

    reg = MetricRegistry()
    wd = StepWatchdog(window=3, threshold=2.0, metrics=reg)
    for step, dt in enumerate((0.1, 0.1, 0.1, 0.5)):
        wd.observe(step, dt)
    assert reg.value("dist.watchdog.step_seconds") == 0.5
    assert reg.value("dist.watchdog.straggler_total") == 1
    trip, = [e for e in reg.events if e["name"] == "dist.watchdog.straggler"]
    assert trip["attrs"]["seconds"] == 0.5


def test_timed_mflups_sources_from_obs():
    from benchmarks.common import timed_mflups

    geom = np.ones((6, 6, 6), np.uint8)
    res = timed_mflups(geom, steps=2, warmup=1, periodic=(True,) * 3,
                       dispatch=False)
    assert res.mflups > 0 and res.metrics is not None
    assert res.metrics.value("lbm.step.mflups") == res.mflups
    assert res.metrics.value("lbm.bw.eqn10_fraction") > 0
    assert res.phases["lbm.bench.run"]["count"] == 1
    assert "lbm.run" in res.phases                   # engine span nested in
    mf, eng = res                                    # tuple compat preserved
    assert mf == res.mflups and eng is res.eng
    assert not obs.get_metrics().enabled             # globals untouched
