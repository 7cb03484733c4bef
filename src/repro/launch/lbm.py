import os
import sys
if "--dryrun" in sys.argv:  # BEFORE any jax import (device count locks)
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")
"""LBM launcher: run the paper's solver, or dry-run it on the production
meshes (the paper's own technique under the same multi-pod regime as the
assigned LM architectures).

    # small real run on local devices
    PYTHONPATH=src python -m repro.launch.lbm --case duct --steps 100

    # multi-pod dry-run: slab decomposition over pod x data (32 slabs),
    # 16x16 and 2x16x16 meshes
    PYTHONPATH=src python -m repro.launch.lbm --dryrun --mesh both
"""
import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import collision as C
from repro.core.boundary import BoundarySpec
from repro.core.engine import LBMConfig, SparseTiledLBM
from repro.core.tiling import INLET, NODE_ORDERS, OUTLET, TILE_ORDERS
from repro.data import geometry as geo
from repro.dist.lbm import ShardedLBM
from repro.launch.cache import init_compile_cache
from repro.launch.mesh import make_production_mesh, mesh_chip_count
from repro.roofline.analysis import HBM_BW, ICI_BW, PEAK_FLOPS
from repro.roofline.hlo_cost import analyze_hlo


@dataclasses.dataclass
class Case:
    """A runnable scenario: geometry + boundary conditions + engine knobs."""

    geometry: np.ndarray
    boundaries: tuple = ()
    periodic: tuple = (False, False, False)
    lattice: str = "D3Q19"
    force: tuple | None = None


_Z_FLOW = ((INLET, BoundarySpec("velocity", (0, 0, 1),
                                velocity=(0, 0, 0.02))),
           (OUTLET, BoundarySpec("pressure", (0, 0, -1), rho=1.0)))
_X_FLOW = ((INLET, BoundarySpec("velocity", (1, 0, 0),
                                velocity=(0.02, 0, 0))),
           (OUTLET, BoundarySpec("pressure", (-1, 0, 0), rho=1.0)))

CASES = ("cavity", "duct", "spheres", "vessel", "aorta", "channel2d")


def make_case(name: str, scale: int = 1) -> Case:
    """Every geometry generator in ``repro.data.geometry`` is reachable here
    (and therefore from the CLI and benchmarks/geometry_suite.py)."""
    if name == "cavity":
        return Case(
            geo.cavity3d(48 * scale),
            ((geo.LID, BoundarySpec("velocity", (0, 0, -1),
                                    velocity=(0.05, 0.0, 0.0))),))
    if name == "duct":
        g = geo.duct(24 * scale, 24 * scale, 96 * scale)
        bcs = ((INLET, BoundarySpec("velocity", (0, 0, 1),
                                    velocity=(0, 0, 0.05))),
               (OUTLET, BoundarySpec("pressure", (0, 0, -1), rho=1.0)))
        return Case(g, bcs)
    if name == "spheres":
        return Case(geo.duct_wrap(
            geo.random_spheres(box=64 * scale, porosity=0.7, diameter=16)),
            _Z_FLOW)
    if name == "vessel":
        # aneurysm-like curved vessel, inlet/outlet on the x faces; the
        # radius must reach the x=1 plane (tube centreline starts at x=8)
        return Case(geo.vessel_aneurysm(
            (64 * scale, 48 * scale, 48 * scale),
            radius=8.0 * scale, bulge=12.0 * scale), _X_FLOW)
    if name == "aorta":
        # arched tube with a coarctation pinch, inlet/outlet on the z faces
        return Case(geo.aorta_coarctation(
            (48 * scale, 64 * scale, 96 * scale), radius=9.0 * scale),
            _Z_FLOW)
    if name == "channel2d":
        # body-force-driven D2Q9 Poiseuille channel, periodic along x
        return Case(geo.channel2d(32 * scale, 32 * scale),
                    periodic=(True, False, True), lattice="D2Q9",
                    force=(1e-5, 0.0, 0.0))
    raise ValueError(f"unknown case {name!r}; expected one of {CASES}")


def dryrun(multi_pod: bool, collision: str = "lbgk",
           fluid: str = "incompressible", verbose: bool = True,
           node_order: str = "canonical", split_stream: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_chip_count(mesh)
    axis = ("pod", "data") if multi_pod else ("data",)
    slabs = 2 * 16 if multi_pod else 16        # slab axis = pod x data
    # production-scale geometry: a long duct with >= `slabs` z tile-layers;
    # the "model" axis is left for a second-level decomposition (future
    # work: 2-D slab grid); slab count 16/32 matches pod x data.
    case = make_case("duct", scale=1)
    # deepen z so every slab holds >= 2 tile layers
    reps = max(1, (slabs * 2 * 4) // case.geometry.shape[2] + 1)
    g = np.concatenate([case.geometry] * reps, axis=2)
    cfg = LBMConfig(
        collision=C.CollisionConfig(model=collision, fluid=fluid, tau=0.6),
        layout_scheme="paper", dtype="float32", boundaries=case.boundaries,
        periodic=case.periodic, node_order=node_order,
        split_stream=split_stream)
    eng = ShardedLBM(g, cfg, mesh, axis=axis, dryrun=True)
    t0 = time.time()
    lowered = eng.lower_step()
    compiled = lowered.compile()
    dt = time.time() - t0
    mem = compiled.memory_analysis()
    hc = analyze_hlo(compiled.as_text())
    n_own = eng.plan.n_fluid_own
    q = eng.lat.q
    nd = jnp.dtype(cfg.dtype).itemsize
    # paper Eqn (10): minimum bytes per node per step = 2 q n_d
    min_bytes_global = 2 * q * nd * n_own
    terms = {
        "t_compute": hc.flops / PEAK_FLOPS,
        "t_memory": hc.bytes / HBM_BW,
        "t_collective": hc.collective_bytes / ICI_BW,
    }
    dominant = max(terms, key=terms.get)
    out = {
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips, "slabs": eng.plan.n_dev,
        "geometry": list(g.shape),
        "fluid_nodes": n_own,
        "tile_utilisation": round(eng.plan.tile_utilisation, 4),
        # split-phase streaming budget (fluid links): interior links use the
        # static (Q, n) table, frontier links cross tiles, the rest bounce
        "interior_frac": round(eng.stream_fracs["interior_frac"], 4),
        "frontier_frac": round(eng.stream_fracs["frontier_frac"], 4),
        "bounce_frac": round(eng.stream_fracs["bounce_frac"], 4),
        "node_order": node_order,
        "split_stream": split_stream,
        "flops_per_device": hc.flops,
        "bytes_per_device": hc.bytes,
        "coll_bytes_per_device": hc.collective_bytes,
        "coll_by_op": hc.coll_by_op,
        "min_bytes_per_device": min_bytes_global / eng.plan.n_dev,
        "bw_efficiency_model": (min_bytes_global / eng.plan.n_dev)
        / max(hc.bytes, 1.0),
        **terms,
        "dominant": dominant,
        "compile_s": round(dt, 1),
        "ok": True,
    }
    # the SAME canonical metric names the measured runtime emits
    # (repro.obs.metrics.CATALOGUE), so modelled-vs-measured comparison is
    # a single key join — plus the HLO-derived dry-run-only figures
    out["metrics"] = {
        **eng.model_metrics(),
        "lbm.bw.eqn10_fraction_hlo": out["bw_efficiency_model"],
        "lbm.bytes.hlo_per_device": float(hc.bytes),
    }
    reg = obs.get_metrics()
    if reg.enabled:
        for name, v in out["metrics"].items():
            reg.gauge(name, mesh=out["mesh"]).set(v)
    if verbose:
        print(f"[LBM x {out['mesh']}] OK slabs={out['slabs']} "
              f"geom={out['geometry']} fluid={n_own:,}")
        print(f"  eta_t={out['tile_utilisation']} "
              f"interior={out['interior_frac']} "
              f"frontier={out['frontier_frac']} "
              f"bounce={out['bounce_frac']}")
        print(f"  memory_analysis: {mem}")
        print(f"  terms: compute={terms['t_compute']*1e6:.1f}us "
              f"memory={terms['t_memory']*1e6:.1f}us "
              f"collective={terms['t_collective']*1e6:.1f}us "
              f"-> dominant={dominant}; "
              f"Eqn10-min/HLO-bytes={out['bw_efficiency_model']:.3f}")
    return out


def run_local(args):
    case = make_case(args.case, args.scale)
    cfg = LBMConfig(
        lattice=case.lattice,
        collision=C.CollisionConfig(model=args.collision, fluid=args.fluid,
                                    tau=args.tau),
        layout_scheme="xyz" if args.backend == "fused" else "paper",
        dtype=args.dtype, boundaries=case.boundaries, periodic=case.periodic,
        force=case.force, backend=args.backend, tile_order=args.order,
        node_order=args.node_order, split_stream=args.split_stream)
    n_dev = len(jax.devices())
    # a case is slab-decomposable only if every device can own >= 1 z
    # tile-layer (2 with a wrapped periodic-z halo) — channel2d, for one,
    # is a single tile layer thick and must run single-device
    tz = -(-case.geometry.shape[2] // cfg.a)
    sharded = n_dev > 1 and tz >= n_dev * (2 if case.periodic[2] else 1)
    if n_dev > 1 and not sharded:
        print(f"case={args.case}: {tz} z tile-layer(s) cannot feed "
              f"{n_dev} slabs; running single-device")
    if sharded:
        mesh = jax.make_mesh((n_dev,), ("data",))
        eng = ShardedLBM(case.geometry, cfg, mesh)
        n_fluid = eng.plan.n_fluid_own
        util = eng.plan.tile_utilisation
    else:
        eng = SparseTiledLBM(case.geometry, cfg)
        n_fluid = eng.n_fluid_nodes
        util = eng.tiling.tile_utilisation
    eng.run(args.steps)  # compile the fori_loop + warm
    jax.block_until_ready(eng.f)
    eng.reset()          # back to t=0: the timed run IS the reported physics
    t0 = time.time()
    eng.run(args.steps)  # timed: one dispatch for the whole loop
    jax.block_until_ready(eng.f)
    dt = time.time() - t0
    mflups = n_fluid * args.steps / dt / 1e6
    reg = obs.get_metrics()
    if reg.enabled:
        model = eng.model_metrics()
        for name, v in model.items():
            reg.gauge(name, case=args.case).set(v)
        reg.gauge("lbm.step.mflups", case=args.case).set(mflups)
        reg.gauge("lbm.step.seconds", case=args.case).set(dt / args.steps)
        reg.gauge("lbm.bw.achieved_gbs", case=args.case).set(
            model["lbm.bw.eqn10_min_bytes"] / (dt / args.steps) / 1e9)
        reg.gauge("lbm.mass.total", case=args.case).set(eng.total_mass())
    stream = "split" if args.split_stream else "mono"
    print(f"case={args.case} backend={args.backend} order={args.order} "
          f"node_order={args.node_order} stream={stream} "
          f"devices={n_dev if sharded else 1} fluid={n_fluid:,} "
          f"eta_t={util:.3f} "
          f"steps={args.steps} {dt:.2f}s -> {mflups:.2f} MFLUPS")
    print(f"mass = {eng.total_mass():.6f} after {args.steps} steps")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--case", default="duct", choices=list(CASES))
    ap.add_argument("--scale", type=int, default=1)
    ap.add_argument("--order", default="zmajor", choices=list(TILE_ORDERS),
                    help="tile traversal policy (data placement)")
    ap.add_argument("--node-order", default="canonical",
                    choices=list(NODE_ORDERS), dest="node_order",
                    help="within-tile node enumeration (data placement)")
    ap.add_argument("--split-stream", action="store_true",
                    dest="split_stream",
                    help="split-phase streaming: static interior "
                         "permutation + compact frontier tables "
                         "(gather backend only)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--tau", type=float, default=0.6)
    ap.add_argument("--collision", default="lbgk", choices=["lbgk", "lbmrt"])
    ap.add_argument("--fluid", default="incompressible",
                    choices=["incompressible", "quasi_compressible"])
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--backend", default="gather",
                    choices=["gather", "fused"])
    ap.add_argument("--out", default=None)
    ap.add_argument("--metrics-out", default=None, dest="metrics_out",
                    help="write the obs metric registry as JSONL here")
    ap.add_argument("--trace", default=None,
                    help="write a Chrome-trace JSON (perfetto-loadable) "
                         "here: the set-up spans, the warm-up (compiling) "
                         "run and the timed run")
    args = ap.parse_args(argv)
    init_compile_cache()

    if args.metrics_out or args.trace:
        # enable BEFORE any engine is built so its set-up spans are
        # recorded
        obs.enable(metrics=True, trace=bool(args.trace))

    if not args.dryrun:
        rc = run_local(args) or 0
        write_obs_outputs(args)
        return rc
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    results = [dryrun(mp, args.collision, args.fluid,
                      node_order=args.node_order,
                      split_stream=args.split_stream) for mp in meshes]
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    write_obs_outputs(args)
    return 0


def write_obs_outputs(args) -> None:
    """Export the global obs collectors per the CLI flags (shared with
    ``repro.launch.sim_serve``)."""
    if getattr(args, "metrics_out", None):
        print(f"metrics -> {obs.get_metrics().write_jsonl(args.metrics_out)}")
    if getattr(args, "trace", None):
        print(f"trace -> {obs.get_tracer().save(args.trace)}")


if __name__ == "__main__":
    sys.exit(main())
