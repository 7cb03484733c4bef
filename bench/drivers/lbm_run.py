"""Driver of closed ``run(k)`` traffic: one caller advances one domain.

Set-up builds the configuration's geometry, the solver
(``SparseTiledLBM`` on the mix's backend), the seeded initial flow (packed
with the backend's ``initial_state``) and warms the one ``run(k)`` program.
The window then calls ``run(k)`` and waits for it (``block_until_ready``)
until ``seconds`` have passed.  Afterwards the state the window produced is
read back, the solver is released, and the dense reference
(``bench/reference.py``) advances the same initial flow through the same
number of steps; the populations of every fluid node are compared.

``bench/control.py`` drives the same function with the parts a benchmark
run never uses: an engine built once for many seeds (``built``), a fixed
number of window calls (``calls``) and a stand-in that advances the state in
the solver's place (``stand_in``), so that the control and the planted
faults are judged by this comparison and no other.
"""
from __future__ import annotations

import gc
import os
import shutil
import sys
import tempfile
import time
import traceback
import types

import numpy as np

from bench import geometry as geo
from bench import reference, trace_reduce

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _solver():
    """The system under test, from the checkout's ``src``."""
    src = os.path.join(CHECKOUT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.core import collision
    from repro.core.boundary import BoundarySpec
    from repro.core.engine import LBMConfig, SparseTiledLBM

    return collision, BoundarySpec, LBMConfig, SparseTiledLBM


def engine(geometry: np.ndarray, config: dict, traffic: dict):
    collision, BoundarySpec, LBMConfig, SparseTiledLBM = _solver()
    bcs = tuple(
        (b["node_type"],
         BoundarySpec(b["kind"], tuple(b["normal"]),
                      velocity=tuple(b.get("velocity", (0.0, 0.0, 0.0))),
                      rho=b.get("rho", 1.0)))
        for b in config["boundaries"])
    col = config["collision"]
    cfg = LBMConfig(
        lattice=config["lattice"],
        collision=collision.CollisionConfig(model=col["model"],
                                            fluid=col["fluid"],
                                            tau=col["tau"]),
        a=config["tile_edge"], layout_scheme="xyz", dtype=config["dtype"],
        boundaries=bcs, backend=traffic["backend"])
    return SparseTiledLBM(geometry, cfg)


def node_set_mismatch(geometry: np.ndarray, coords: np.ndarray) -> int:
    """Non-solid nodes the solver does not hold once, plus nodes it holds
    that are solid in the geometry: 0 when it covers the fluid exactly."""
    seen = np.bincount(np.ravel_multi_index(tuple(coords.T), geometry.shape),
                       minlength=geometry.size)
    fluid = (geometry != geo.SOLID).ravel()
    return int((seen[fluid] != 1).sum() + seen[~fluid].sum())


def readout(eng):
    """The solver's answer as the reference judges it, read through its
    public ``tiling.node_coords()`` and ``backend.canonical``:
    ``(answer, corners, fluid, fluid_coords)`` with ``answer`` (T, Q * a^3)
    on the device, each tile's nodes x fastest, then y, then z."""
    import jax
    import jax.numpy as jnp

    til = eng.tiling
    a = til.a
    coords = til.node_coords()                        # (T, n, 3)
    corners = coords.min(axis=1)
    local = coords - corners[:, None, :]
    offset = local[..., 0] + a * local[..., 1] + a * a * local[..., 2]
    if not (offset == offset[:1]).all():
        raise ValueError("tiles order their nodes differently")
    order = np.argsort(offset[0])
    fluid = til.node_types != geo.SOLID
    answer = jax.jit(lambda f: jnp.moveaxis(
        eng.backend.canonical(f)[:, :, order], 0, 1).reshape(
            til.num_tiles, -1))(eng.f)
    return answer, corners, fluid[:, order], coords[fluid]


def initial_state(eng, config: dict, params: dict) -> None:
    """Give the solver the seeded flow, packed by its backend."""
    coords = eng.tiling.node_coords()
    eng.f = eng.backend.initial_state(reference.initial_f(
        params, coords[..., 0], coords[..., 1], coords[..., 2],
        eng.tiling.node_types == geo.SOLID, lat=config["lattice"],
        dtype=config["dtype"]))


def _log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def build(config: dict, traffic: dict):
    """``(geometry, engine, engine_build_s)``; raises if the geometry's
    counts differ from those the configuration states."""
    import jax

    t = time.perf_counter()
    g = geo.build(CHECKOUT, config["geometry"])
    _log(f"geometry_s={time.perf_counter() - t:.3f}")
    t = time.perf_counter()
    eng = engine(g, config, traffic)
    jax.block_until_ready((eng.f, eng.backend.tables))
    engine_build_s = time.perf_counter() - t
    _log(f"engine_build_s={engine_build_s:.3f}")
    til = eng.tiling
    got = {"tiles": til.num_tiles, "fluid_nodes": til.n_fluid_nodes}
    for key, want in config.get("expected", {}).items():
        if got[key] != want:
            raise ValueError(f"geometry has {key}={got[key]}, "
                             f"configuration states {want}")
    return g, eng, engine_build_s


def run(config: dict, traffic: dict, *, seed: int, seconds: float,
        trace: bool, t0: float, built=None, stand_in=None, calls=None):
    """One run of the cell.  ``built``: the result of :func:`build`, reused
    (default: built here); ``stand_in(eng, k)``: advances ``eng.f`` by ``k``
    steps in place of ``eng.run(k)``; ``calls``: the window ends after this
    many calls instead of after ``seconds``."""
    import jax

    g, eng, engine_build_s = built or build(config, traffic)
    del built
    til = eng.tiling
    advance = eng.run if stand_in is None else (
        lambda k: stand_in(eng, k))

    t = time.perf_counter()
    params = reference.draw_initial(seed, traffic["initial"], g.shape)
    initial_state(eng, config, params)
    jax.block_until_ready(eng.f)
    _log(f"initial_state_s={time.perf_counter() - t:.3f}")
    t = time.perf_counter()
    k = int(traffic["steps_per_call"])
    with jax.profiler.TraceAnnotation("bench.warm"):
        advance(k)
        jax.block_until_ready(eng.f)
    _log(f"warm_s={time.perf_counter() - t:.3f}")

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(tdir)
    done = failed = 0
    t_start = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                with jax.profiler.TraceAnnotation("bench.call"):
                    advance(k)
                with jax.profiler.TraceAnnotation("bench.sync"):
                    jax.block_until_ready(eng.f)
                done += 1
                if (done >= calls if calls is not None
                        else time.perf_counter() - t_start >= seconds):
                    break
    except Exception:                    # a call that raised fails its steps
        traceback.print_exc()
        failed = k
    t_end = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    dev = jax.devices()
    stats = dev[0].memory_stats() or {}
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    summary = None
    if trace:
        summary = trace_reduce.summarize(*trace_reduce.load(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)

    steps = done * k
    attempted = steps + failed
    t = time.perf_counter()
    n_fluid = til.n_fluid_nodes
    if not failed:
        answer, corners, fluid, coords = readout(eng)
        jax.block_until_ready(answer)
    del eng, til, advance
    gc.collect()
    _log(f"readout_s={time.perf_counter() - t:.3f}")

    limit = config["check"]["max_abs_df"]
    checks = {}
    if not failed:
        t = time.perf_counter()
        checks["node_set_mismatch"] = {
            "value": node_set_mismatch(g, coords), "limit": 0}
        f_ref, origin = reference.run(g, config, params, k + steps,
                                      config["dtype"])
        jax.block_until_ready(f_ref)
        _log(f"reference_s={time.perf_counter() - t:.3f}")
        t = time.perf_counter()
        diff = reference.max_abs_diff(f_ref, origin, corners, answer, fluid,
                                      config["tile_edge"])
        del f_ref, answer
        _log(f"compare_s={time.perf_counter() - t:.3f}")
        if diff != diff:                 # a non-finite value in the state
            failed = attempted
        checks["max_abs_df"] = {"value": diff, "limit": limit}
    correct = (not failed and limit is not None
               and all(c["value"] <= c["limit"] for c in checks.values()))

    e, _, _ = reference.lattice(config["lattice"])
    return types.SimpleNamespace(
        correct=correct, attempted=attempted, failed=failed, device=device,
        checks=checks, steps=steps, window_s=t_end - t_start,
        setup_s=t_start - t0, engine_build_s=engine_build_s,
        n_fluid=n_fluid, q=len(e), e=e,
        itemsize=np.dtype(config["dtype"]).itemsize,
        kernel=traffic["kernel"], trace=summary,
        breakdown=({"device_ops": summary.top_ops(),
                    "idle_gaps": summary.idle_gaps()} if summary else None))
