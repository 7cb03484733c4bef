#!/usr/bin/env python3
"""On-chip smoke run of the sparse tiled LBM through its user entry points.

    python chip_smoke.py                # one TPU chip: single-domain + serving
    python chip_smoke.py --four-chips   # ShardedLBM on 4 chips vs one device

Phases (one process; every check raises, so any failure exits non-zero):

* single-domain — the porous sphere pack ``make_case("spheres", 4)``
  (256^3 box, porosity 0.7, 236,017 tiles, 11.7 M fluid nodes, 1.15 GB of
  float32 populations) through ``SparseTiledLBM.run()`` on the gather
  backend and on the fused Pallas kernel, compiled.  The two backends'
  macroscopics must agree within ``TOL`` and every value must be finite.
* serving — ``SimService`` seats 3 fused-backend sessions on 2 lid-driven
  cavities in 2 slots and runs them to their budgets through
  submit/step/collect; each session's mass drift is bounded.
* ``--four-chips`` (alone) — ``ShardedLBM`` slabs over a 4-device mesh vs
  ``SparseTiledLBM`` on one device, compared on owned tiles.

Set-up, compile and per-step times are printed for information only.
The last stdout line is ``{"ok": true, "device": {...}}``; without a TPU the
script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# the 256^3 pack on one chip; 128^3 for --four-chips, where one device also
# holds the whole-domain reference next to its slab
SCALE, FOUR_CHIP_SCALE = 4, 2
STEPS = 10                       # per timed run; each engine runs twice

# float32 agreement bound for rho and u (absolute; rho ~ 1, |u| <= ~0.1):
# the backends differ only in rounding order, about 1e-7 per step
TOL = 2e-5
# relative mass drift allowed: with open inlet/outlet faces the physical
# in/outflow over 20 steps of the 256^3 pack is about 2e-3; the closed
# cavities of the serving phase conserve mass to about 1e-4
MASS_DRIFT_OPEN = 1e-2
MASS_DRIFT_CLOSED = 1e-3


def _log(msg: str) -> None:
    print(msg, flush=True)


def _config(case, backend: str):
    from repro.core import collision as C
    from repro.core.engine import LBMConfig

    return LBMConfig(
        lattice=case.lattice, collision=C.CollisionConfig(tau=0.6),
        layout_scheme="xyz", dtype="float32", boundaries=case.boundaries,
        periodic=case.periodic, force=case.force, backend=backend)


def _check_finite(name: str, *arrays) -> None:
    for a in arrays:
        assert np.isfinite(a).all(), f"{name}: non-finite values"


def _timed_engine(case, backend: str, steps: int):
    """Build, compile and run one engine; returns (engine, mass0)."""
    import jax

    from repro.core.engine import SparseTiledLBM

    t0 = time.perf_counter()
    eng = SparseTiledLBM(case.geometry, _config(case, backend))
    jax.block_until_ready(eng.f)
    setup = time.perf_counter() - t0
    if backend == "fused":
        assert eng.kernel_interpret is False, "fused kernel is interpreted"
    mass0 = eng.total_mass()
    t0 = time.perf_counter()
    eng.run(steps)                       # compiles the fori_loop, then runs
    jax.block_until_ready(eng.f)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.run(steps)                       # steady state
    jax.block_until_ready(eng.f)
    per_step = (time.perf_counter() - t0) / steps
    _log(f"  {backend}: tiles={eng.tiling.num_tiles} "
         f"fluid={eng.n_fluid_nodes} setup_s={setup:.1f} "
         f"compile_s~={first - per_step * steps:.1f} "
         f"s_per_step={per_step:.4f}")
    return eng, mass0


def phase_single(scale: int, steps: int) -> None:
    """Gather vs compiled fused kernel on the sphere pack at ``scale``."""
    import jax

    from repro.launch.lbm import make_case

    t0 = time.perf_counter()
    case = make_case("spheres", scale)
    _log(f"[single] spheres scale={scale} grid={case.geometry.shape} "
         f"geometry_s={time.perf_counter() - t0:.1f} steps={2 * steps}")
    out = {}
    for backend in ("gather", "fused"):
        eng, mass0 = _timed_engine(case, backend, steps)
        rho, u = eng.macroscopics()
        out[backend] = (np.asarray(rho), np.asarray(u), mass0,
                        eng.total_mass())
        del eng                          # one engine on the device at a time;
        gc.collect()                     # its jit cache closes over it
    (rho_g, u_g, m0, m_g), (rho_f, u_f, _, m_f) = out["gather"], out["fused"]
    _check_finite("single", rho_g, u_g, rho_f, u_f)
    d_rho = float(np.abs(rho_f - rho_g).max())
    d_u = float(np.abs(u_f - u_g).max())
    drift = abs(m_f - m0) / m0
    _log(f"[single] max|d rho|={d_rho:.3e} max|d u|={d_u:.3e} (tol {TOL}) "
         f"mass drift gather={abs(m_g - m0) / m0:.3e} fused={drift:.3e} "
         f"(bound {MASS_DRIFT_OPEN})")
    assert d_rho < TOL and d_u < TOL, (d_rho, d_u)
    assert max(drift, abs(m_g - m0) / m0) < MASS_DRIFT_OPEN
    stats = jax.devices()[0].memory_stats() or {}
    _log(f"[single] process peak device bytes="
         f"{stats.get('peak_bytes_in_use', 'not reported')}")


def phase_serving(steps: int,
                  sessions=(("cavity", 1, 2), ("cavity", 2, 1))) -> None:
    """3 sessions on 2 lid-driven cavities (48^3 and 96^3) in 2 fused-backend
    slots via SimService; ``sessions`` holds (case, scale, count)."""
    from repro.launch.lbm import make_case
    from repro.sim.service import SimService

    svc = SimService(slots=2)
    sids = []
    for name, scale, n in sessions:
        case = make_case(name, scale)
        for i in range(n):
            sids.append(svc.submit(case.geometry, _config(case, "fused"),
                                   steps=steps + 5 * i))
    t0 = time.perf_counter()
    svc.run()
    wall = time.perf_counter() - t0
    for sid in sids:
        r = svc.collect(sid)
        assert r is not None, f"session {sid} did not finish"
        assert np.isfinite([r["mass"], r["mean_speed"], r["max_speed"]]).all()
        assert r["mass_drift"] < MASS_DRIFT_CLOSED, r
        _log(f"[serving] sid={sid} steps={r['steps']} "
             f"drift={r['mass_drift']:.3e} mean|u|={r['mean_speed']:.3e}")
    _log(f"[serving] sessions={len(sids)} "
         f"engines={svc.registry.compiled_count} wall_s={wall:.1f}")
    assert svc.registry.compiled_count == len(sessions)


def phase_four_chips(scale: int, steps: int) -> None:
    """ShardedLBM over every device vs SparseTiledLBM on device 0."""
    import jax

    from repro.core.engine import SparseTiledLBM
    from repro.core.tiling import SOLID
    from repro.dist.lbm import ShardedLBM
    from repro.launch.lbm import make_case

    n_dev = len(jax.devices())
    case = make_case("spheres", scale)
    cfg = _config(case, "fused")
    t0 = time.perf_counter()
    sh = ShardedLBM(case.geometry, cfg, jax.make_mesh((n_dev,), ("data",)))
    _log(f"[four-chips] spheres scale={scale} slabs={sh.plan.n_dev} "
         f"setup_s={time.perf_counter() - t0:.1f}")
    # state and slab tables must really be spread over the mesh
    for name, arr in [("f", sh.f)] + sorted(sh._tbl.items()):
        devs = {s.device for s in arr.addressable_shards}
        assert len(devs) == n_dev, (name, devs)
    t0 = time.perf_counter()
    sh.run(steps)
    jax.block_until_ready(sh.f)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    sh.run(steps)
    jax.block_until_ready(sh.f)
    per_step = (time.perf_counter() - t0) / steps
    _log(f"[four-chips] sharded compile_s~={first - per_step * steps:.1f} "
         f"s_per_step={per_step:.4f}")

    ref = SparseTiledLBM(case.geometry, cfg)
    ref.run(steps)
    ref.run(steps)
    rho_r, u_r = ref.fields_dense()
    rho_s, u_s, _, own = sh.macroscopics_own()
    _check_finite("four-chips", rho_s, u_s)
    a = cfg.a
    d_rho = d_u = 0.0
    for d, lt in enumerate(sh.plan.local_tilings):
        z_base = sh.plan.layer_of_dev[d][0] - sh.plan.own_z0[d]
        o = own[d, :lt.num_tiles]
        fluid = lt.node_types[o] != SOLID
        xyz = (lt.node_coords()[o] + np.array([0, 0, z_base * a]))[fluid]
        x, y, z = xyz[:, 0], xyz[:, 1], xyz[:, 2]
        d_rho = max(d_rho, float(np.abs(
            rho_s[d, :lt.num_tiles][o][fluid] - rho_r[x, y, z]).max()))
        d_u = max(d_u, float(np.abs(
            u_s[:, d, :lt.num_tiles][:, o][:, fluid] - u_r[:, x, y, z]).max()))
    _log(f"[four-chips] owned-tile max|d rho|={d_rho:.3e} "
         f"max|d u|={d_u:.3e} (tol {TOL})")
    assert d_rho < TOL and d_u < TOL, (d_rho, d_u)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true", dest="four_chips",
                    help="run only the 4-chip ShardedLBM phase")
    args = ap.parse_args(argv)

    from repro.launch.cache import init_compile_cache

    cache = init_compile_cache()
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    _log(f"device: platform={device['platform']} kind={device['kind']} "
         f"count={device['count']} compile_cache={cache}")
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU found; refusing to run on "
              f"{device['platform']!r}", file=sys.stderr)
        return 1
    if args.four_chips:
        assert device["count"] == 4, f"--four-chips needs 4 devices: {devs}"
        phase_four_chips(FOUR_CHIP_SCALE, STEPS)
    else:
        phase_single(SCALE, STEPS)
        phase_serving(4 * STEPS)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
