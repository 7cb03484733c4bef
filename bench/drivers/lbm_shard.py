"""Driver of closed ``run(k)`` traffic over one domain cut into z slabs, one
slab per chip of the host (``ShardedLBM``).

Set-up builds the configuration's geometry, a one-axis mesh of the
traffic's ``slabs`` chips ordered so that slab d and slab d+1 sit on
neighbouring chips, the solver (``ShardedLBM`` on the mix's backend), the
seeded initial flow (computed and loaded slab by slab on each slab's chip)
and warms the one ``run(k)`` program.  The window then calls ``run(k)`` and
waits for it (``block_until_ready``) until ``seconds`` have passed.
Afterwards each slab's owned tiles are read back on its own chip, the solver
is released, and the dense reference (``bench/reference.py``) advances the
same initial flow on one chip; the slabs are judged against it one at a
time, so that the reference box and one slab's answer are all that chip
holds.

The solver's state is read through its public per-slab API only
(``owned_node_coords``, ``load_state``, ``read_owned``).  A program without
it cannot run this cell: the driver stops before any set-up work, naming
what is missing.  The result also carries the gauges the solver sets while
it is built (``gauges``) and, in a traced run, the optimised text of the
``run(k)`` program (``hlo``, from ``run_fn``), whose op metadata names the
scopes that the trace's events lack.  ``run`` takes ``built``, ``stand_in``
and ``calls`` as ``lbm_run.run`` does.
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
from bench import harness, reference, trace_reduce

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
lbm_run = harness.module(CHECKOUT, "drivers", "lbm_run")

API = ("owned_node_coords", "load_state", "read_owned")


def _solver():
    """``ShardedLBM`` from the checkout's ``src``; exits (non-zero, no
    result) when it lacks the per-slab API this driver reads it through."""
    src = os.path.join(CHECKOUT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.dist.lbm import ShardedLBM

    missing = [m for m in API if not callable(getattr(ShardedLBM, m, None))]
    if missing:
        raise SystemExit("bench: ShardedLBM lacks " + ", ".join(missing)
                         + "; this program cannot run a sharded cell")
    return ShardedLBM


def _log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def slab_devices(n: int) -> list:
    """``n`` devices, ordered so that consecutive ones are neighbours on the
    chip interconnect where the devices report their coordinates (a snake
    over the host's x-y grid); otherwise in ``jax.devices()`` order."""
    import jax

    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(f"{n} slabs need {n} devices; found {len(devs)}")
    devs = devs[:n]
    if all(getattr(d, "coords", None) is not None for d in devs):
        devs = sorted(devs, key=lambda d: (
            d.coords[2], d.coords[1],
            d.coords[0] if d.coords[1] % 2 == 0 else -d.coords[0]))
        hops = [int(np.abs(np.subtract(a.coords, b.coords)).sum())
                for a, b in zip(devs, devs[1:])]
        _log("slab devices: " + "; ".join(
            f"slab {i} id {d.id} coords {list(d.coords)}"
            for i, d in enumerate(devs)) + f"; hops {hops}")
    return devs


def _lbm_config(config: dict, traffic: dict):
    collision, BoundarySpec, LBMConfig, _ = lbm_run._solver()
    bcs = tuple(
        (b["node_type"],
         BoundarySpec(b["kind"], tuple(b["normal"]),
                      velocity=tuple(b.get("velocity", (0.0, 0.0, 0.0))),
                      rho=b.get("rho", 1.0)))
        for b in config["boundaries"])
    col = config["collision"]
    return LBMConfig(
        lattice=config["lattice"],
        collision=collision.CollisionConfig(model=col["model"],
                                            fluid=col["fluid"],
                                            tau=col["tau"]),
        a=config["tile_edge"], layout_scheme="xyz", dtype=config["dtype"],
        boundaries=bcs, backend=traffic["backend"])


def build(config: dict, traffic: dict):
    """``(geometry, solver, shard_build_s, gauges)``, ``gauges`` the
    solver's unlabelled gauges ({name: value}) as it set them while it was
    built; raises if the geometry's counts differ from those the
    configuration states."""
    import jax
    from jax.sharding import Mesh

    ShardedLBM = _solver()                    # before any set-up work
    from repro import obs
    from repro.obs.metrics import MetricRegistry

    cfg = _lbm_config(config, traffic)
    t = time.perf_counter()
    g = geo.build(CHECKOUT, config["geometry"])
    _log(f"geometry_s={time.perf_counter() - t:.3f}")
    mesh = Mesh(np.array(slab_devices(int(traffic["slabs"]))), ("data",))
    reg = MetricRegistry()
    t = time.perf_counter()
    with obs.use(metrics=reg):
        eng = ShardedLBM(g, cfg, mesh)
    jax.block_until_ready((eng.f, eng.tables))
    shard_build_s = time.perf_counter() - t
    gauges = {r["name"]: r["value"] for r in reg.snapshot()
              if r["type"] == "gauge" and not r["labels"]}
    own = eng.plan.own.sum(axis=1)
    _log(f"shard_build_s={shard_build_s:.3f} own_tiles={own.tolist()} "
         f"t_pad={eng.plan.t_pad}")
    got = {"tiles": int(own.sum()), "fluid_nodes": eng.n_fluid_nodes}
    for key, want in config.get("expected", {}).items():
        if got[key] != want:
            raise ValueError(f"geometry has {key}={got[key]}, "
                             f"configuration states {want}")
    return g, eng, shard_build_s, gauges


def _solid(geometry: np.ndarray, coords: np.ndarray) -> np.ndarray:
    """Nodes the geometry makes solid, or that lie outside it (tiles pad
    the box to whole tiles)."""
    shape = np.asarray(geometry.shape)
    inside = (coords < shape).all(axis=-1)
    c = np.minimum(coords, shape - 1)
    return ~inside | (geometry[c[..., 0], c[..., 1], c[..., 2]] == geo.SOLID)


def initial_state(eng, geometry, config: dict, params: dict,
                  coords: list) -> None:
    """Give the solver the seeded flow, each slab's owned tiles computed on
    the slab's own chip."""
    import jax

    owned = []
    for d, c in enumerate(coords):
        dev = eng.mesh.devices[d, 0]
        x, y, z = (jax.device_put(c[..., i], dev) for i in range(3))
        owned.append(reference.initial_f(
            params, x, y, z, jax.device_put(_solid(geometry, c), dev),
            lat=config["lattice"], dtype=config["dtype"]))
    eng.load_state(owned)


def readout(eng, coords: list, a: int):
    """Per slab ``(answer, corners, order, held)``: ``answer``
    (T_own, Q * a^3) on the slab's chip, each tile's nodes x fastest, then
    y, then z (as ``reference.max_abs_diff`` reads them), ``order`` taking
    a tile's node slots to that order; ``held`` (host) the coordinates of
    the nodes whose populations are not all zero, the fluid nodes as the
    solver holds them (it keeps solid nodes at zero)."""
    import jax
    import jax.numpy as jnp

    out = []
    for canon, c in zip(eng.read_owned(), coords):
        corners = c.min(axis=1)
        local = c - corners[:, None, :]
        offset = local[..., 0] + a * local[..., 1] + a * a * local[..., 2]
        if not (offset == offset[:1]).all():
            raise ValueError("tiles order their nodes differently")
        order = np.argsort(offset[0])
        answer, nonzero = jax.jit(lambda f: (
            jnp.moveaxis(f[:, :, order], 0, 1).reshape(f.shape[1], -1),
            jnp.any(f != 0, axis=0)))(canon)
        out.append((answer, corners, order, c[np.asarray(nonzero)]))
    return out


def run(config: dict, traffic: dict, *, seed: int, seconds: float,
        trace: bool, t0: float, built=None, stand_in=None, calls=None):
    """One run of the cell.  ``built``: the result of :func:`build`, reused
    (default: built here); ``stand_in(eng, k)``: advances ``eng.f`` by ``k``
    steps in place of ``eng.run(k)``; ``calls``: the window ends after this
    many calls instead of after ``seconds``."""
    import jax

    g, eng, shard_build_s, gauges = built or build(config, traffic)
    del built
    advance = eng.run if stand_in is None else (
        lambda k: stand_in(eng, k))
    a = config["tile_edge"]

    t = time.perf_counter()
    params = reference.draw_initial(seed, traffic["initial"], g.shape)
    coords = eng.owned_node_coords()
    initial_state(eng, g, config, params, coords)
    jax.block_until_ready(eng.f)
    _log(f"initial_state_s={time.perf_counter() - t:.3f}")
    t = time.perf_counter()
    k = int(traffic["steps_per_call"])
    with jax.profiler.TraceAnnotation("bench.warm"):
        advance(k)
        jax.block_until_ready(eng.f)
    _log(f"warm_s={time.perf_counter() - t:.3f}")
    hlo = None
    if trace and stand_in is None:
        # the optimised text of the program the window runs: it names each
        # op's scope, which the trace's events do not carry
        t = time.perf_counter()
        hlo = eng.run_fn(k).lower(eng.f, eng.tables).compile().as_text()
        _log(f"program_text_s={time.perf_counter() - t:.3f}")

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
    devs = list(eng.mesh.devices[:, 0])
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    _log(f"memory_peak_bytes per slab chip: {peaks}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": max(peaks)}
    summary = None
    if trace:
        summary = trace_reduce.summarize(*trace_reduce.load(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)

    steps = done * k
    attempted = steps + failed
    n_fluid = eng.n_fluid_nodes
    t = time.perf_counter()
    slabs = []
    if not failed:
        slabs = readout(eng, coords, a)
        jax.block_until_ready([s[0] for s in slabs])
    del eng, advance
    gc.collect()
    _log(f"readout_s={time.perf_counter() - t:.3f}")

    limit = config["check"]["max_abs_df"]
    checks = {}
    if not failed:
        t = time.perf_counter()
        checks["node_set_mismatch"] = {
            "value": lbm_run.node_set_mismatch(
                g, np.concatenate([s[3] for s in slabs])),
            "limit": 0}
        f_ref, origin = reference.run(g, config, params, k + steps,
                                      config["dtype"])
        jax.block_until_ready(f_ref)
        _log(f"reference_s={time.perf_counter() - t:.3f}")
        t = time.perf_counter()
        ref_dev = next(iter(f_ref.devices()))
        diffs = []
        while slabs:                     # one slab's answer at a time
            answer, corners, order, _ = slabs.pop(0)
            c = coords.pop(0)
            judged = ~_solid(g, c)[:, order]
            diffs.append(reference.max_abs_diff(
                f_ref, origin, corners, jax.device_put(answer, ref_dev),
                judged, a))
            del answer
        del f_ref
        _log(f"compare_s={time.perf_counter() - t:.3f} "
             f"max_abs_df per slab: {diffs}")
        diff = float("nan") if any(v != v for v in diffs) else max(diffs)
        if diff != diff:                 # a non-finite value in the state
            failed = attempted
        checks["max_abs_df"] = {"value": diff, "limit": limit}
    correct = (not failed and limit is not None
               and all(c["value"] <= c["limit"] for c in checks.values()))

    e, _, _ = reference.lattice(config["lattice"])
    return types.SimpleNamespace(
        correct=correct, attempted=attempted, failed=failed, device=device,
        checks=checks, steps=steps, window_s=t_end - t_start,
        setup_s=t_start - t0, shard_build_s=shard_build_s, gauges=gauges,
        hlo=hlo,
        n_fluid=n_fluid, q=len(e), e=e,
        itemsize=np.dtype(config["dtype"]).itemsize,
        kernel=traffic["kernel"], trace=summary,
        breakdown=({"device_ops": summary.top_ops(),
                    "idle_gaps": summary.idle_gaps()} if summary else None))
