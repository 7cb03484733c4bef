"""Geometry benchmark suite — the paper's Tables 6-9 analogues x tile
ordering x node ordering x backend x streaming mode.

The paper's headline claim is that a uniform mesh of small tiles PLUS
careful data placement recovers most of peak bandwidth; this suite finally
measures the placement half.  Every row pairs performance (MFLUPS,
kernel-only and dispatch-included, plus the achieved-bandwidth estimate
against the Eqn-10 minimum traffic — the paper's >70%-of-peak metric) with
the structural quantities that explain it: tile utilisation eta_t (Eqn
14), porosity, the split-phase link budget (interior / frontier / bounce
fractions), the per-step indirection-table sizes (monolithic Q*T*n gather
vs the split interior+frontier tables, and their ratio), a modelled
bytes-per-node-update column, and the locality metrics introduced with
``LBMConfig.tile_order`` — mean neighbour index distance, cross-tile link
fraction, and the cross-tile link distance histogram in tile-index space.

Cases: lid-driven cavity (dense reference), duct, random sphere packs at
two porosities (Table 6), and the body-like vessel / aorta geometries
(Tables 8/9) that previously existed in ``repro.data.geometry`` but were
reachable from no benchmark.

    PYTHONPATH=src python -m benchmarks.geometry_suite --quick     # CI-sized
    PYTHONPATH=src python -m benchmarks.geometry_suite             # paper-sized

Emits ``BENCH_geometry_suite.json``.  CPU numbers (Pallas interpret mode
for the fused backend) are labelled as such in the meta block and are for
trajectory tracking, not GPU/TPU comparison — see benchmarks/common.py.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax

from benchmarks.common import timed_mflups
from repro.core.boundary import BoundarySpec
from repro.core.tiling import NODE_ORDERS, TILE_ORDERS
from repro.data import geometry as geo
from repro.launch.lbm import _X_FLOW, _Z_FLOW, Case, make_case

BACKENDS = ("gather", "fused")


def suite_cases(quick: bool) -> dict:
    """name -> Case.  Quick sizes keep every geometry under ~100 non-empty
    tiles so the fused backend stays CI-affordable in interpret mode."""
    if quick:
        lid = ((geo.LID, BoundarySpec("velocity", (0, 0, -1),
                                      velocity=(0.05, 0.0, 0.0))),)
        return {
            "cavity": Case(geo.cavity3d(12), lid),
            "duct": Case(geo.duct(12, 12, 24), _Z_FLOW),
            "spheres_p0.7": Case(geo.duct_wrap(geo.random_spheres(
                box=12, porosity=0.7, diameter=6, seed=0), wall=2), _Z_FLOW),
            "spheres_p0.5": Case(geo.duct_wrap(geo.random_spheres(
                box=12, porosity=0.5, diameter=6, seed=1), wall=2), _Z_FLOW),
            "vessel": Case(geo.vessel_aneurysm((32, 24, 24), radius=7.0,
                                               bulge=8.0), _X_FLOW),
            "aorta": Case(geo.aorta_coarctation((24, 32, 48), radius=6.0),
                          _Z_FLOW),
        }
    cases = {n: make_case(n) for n in
             ("cavity", "duct", "spheres", "vessel", "aorta")}
    cases["spheres_p0.7"] = cases.pop("spheres")
    cases["spheres_p0.5"] = Case(geo.duct_wrap(geo.random_spheres(
        box=64, porosity=0.5, diameter=16, seed=1)), _Z_FLOW)
    return cases


def suite_variants(backends, node_orders, split_modes) -> list:
    """(backend, node_order, split) triples: the gather backend sweeps
    split-vs-monolithic streaming, the fused kernel has no split knob."""
    out = []
    for backend in backends:
        for node_order in node_orders:
            for split in (split_modes if backend == "gather" else (False,)):
                out.append((backend, node_order, split))
    return out


def run_suite(cases: dict, orders, variants, steps: int, warmup: int,
              dtype: str, dispatch: bool = True) -> list:
    rows = []
    total = len(cases) * len(orders) * len(variants)
    print("geometry,tile_order,backend,node_order,stream,MFLUPS,BW_GBps,"
          "eta_t,interior_frac,frontier_frac,index_ratio")
    for gname, case in cases.items():
        for order in orders:
            for backend, node_order, split in variants:
                t0 = time.time()
                res = timed_mflups(
                    case.geometry, steps=steps, warmup=warmup, dtype=dtype,
                    boundaries=case.boundaries, periodic=case.periodic,
                    backend=backend, tile_order=order, lattice=case.lattice,
                    force=case.force, dispatch=dispatch,
                    node_order=node_order, split_stream=split)
                eng = res.eng
                loc = eng.tiling.locality_metrics()
                loc.pop("tile_order")
                tabs = eng.tables
                # per-step indirection-table sizes: the acceptance metric of
                # the split-phase restructuring ((Q*n + frontier tables) vs
                # the monolithic Q*T*n gather table)
                mono_entries = tabs.index_entries_mono
                split_entries = (tabs.split.index_entries
                                 if tabs.split is not None else None)
                row = {
                    "geometry": gname,
                    "tile_order": order,
                    "node_order": node_order,
                    "backend": backend,
                    "stream": "split" if split else "mono",
                    "mflups": round(res.mflups, 4),
                    "mflups_dispatch": (None if res.mflups_dispatch is None
                                        else round(res.mflups_dispatch, 4)),
                    "seconds_per_step": res.seconds_per_step,
                    # 6 decimals: interpret-mode CI rows can sit well below
                    # 1e-4 GB/s — must never round to 0 (guards assert > 0)
                    "bandwidth_gbs": round(res.bandwidth_gbs, 6),
                    "model_bytes_per_node":
                        round(res.model_bytes_per_node, 2),
                    "n_fluid_nodes": eng.n_fluid_nodes,
                    "num_tiles": eng.tiling.num_tiles,
                    "tile_utilisation": round(eng.tiling.tile_utilisation, 4),
                    "porosity": round(eng.tiling.porosity, 4),
                    **loc,
                    # within-tile locality (node_order knob): slot distance
                    # of the intra-tile links under the engine's lattice
                    "mean_intra_tile_link_distance": round(
                        eng.tiling.mean_intra_tile_link_distance(eng.lat.e),
                        2),
                    "interior_frac": round(tabs.interior_frac, 4),
                    "frontier_frac": round(tabs.frontier_frac, 4),
                    "bounce_frac": round(tabs.bounce_frac, 4),
                    "cross_tile_frac": round(tabs.cross_tile_frac, 4),
                    "mean_link_distance":
                        round(tabs.mean_link_distance, 2),
                    "link_distance_hist": tabs.link_distance_hist,
                    "index_entries_mono": mono_entries,
                    "index_entries_split": split_entries,
                    "index_bytes_per_step": eng.index_bytes_per_step(),
                    "index_ratio": (None if split_entries is None
                                    else round(mono_entries / split_entries,
                                               2)),
                }
                rows.append(row)
                print(f"{gname},{order},{backend},{node_order},"
                      f"{row['stream']},{row['mflups']},"
                      f"{row['bandwidth_gbs']},{row['tile_utilisation']},"
                      f"{row['interior_frac']},{row['frontier_frac']},"
                      f"{row['index_ratio']}"
                      f"  [{len(rows)}/{total} {time.time() - t0:.1f}s]")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized geometries / step counts")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--orders", default=None,
                    help="comma-separated subset of TILE_ORDERS "
                         "(default: zmajor,morton_slab quick; all otherwise)")
    ap.add_argument("--node-orders", default=None, dest="node_orders",
                    help="comma-separated subset of NODE_ORDERS "
                         "(default: canonical,frontier_last)")
    ap.add_argument("--backends", default=",".join(BACKENDS))
    ap.add_argument("--streams", default="mono,split",
                    help="gather-backend streaming modes to sweep "
                         "(subset of mono,split)")
    ap.add_argument("--out", default="BENCH_geometry_suite.json")
    args = ap.parse_args(argv)

    orders = (args.orders.split(",") if args.orders
              else ["zmajor", "morton_slab"] if args.quick
              else list(TILE_ORDERS))
    assert all(o in TILE_ORDERS for o in orders), orders
    node_orders = (args.node_orders.split(",") if args.node_orders
                   else ["canonical", "frontier_last"])
    assert all(o in NODE_ORDERS for o in node_orders), node_orders
    backends = args.backends.split(",")
    streams = args.streams.split(",")
    assert streams and set(streams) <= {"mono", "split"}, streams
    split_modes = tuple(s == "split" for s in ("mono", "split")
                        if s in streams)
    steps = args.steps or (2 if args.quick else 20)

    cases = suite_cases(args.quick)
    variants = suite_variants(backends, node_orders, split_modes)
    # quick mode skips the dispatch-included timing: it would compile a
    # second program per row, which dominates interpret-mode CI runs
    rows = run_suite(cases, orders, variants, steps, args.warmup, args.dtype,
                     dispatch=not args.quick)

    # structural guards so CI catches config drift, not just crashes
    # (guards relax when the user deliberately narrowed the sweep via flags)
    assert len({r["geometry"] for r in rows}) >= 5
    assert len({r["tile_order"] for r in rows}) >= min(2, len(orders))
    assert {r["backend"] for r in rows} >= {"gather", "fused"} or \
        set(backends) != set(BACKENDS)
    assert all(r["mflups"] > 0 for r in rows)
    assert all(r["bandwidth_gbs"] > 0 for r in rows)
    for r in rows:          # the split budget must account for every link
        assert abs(r["interior_frac"] + r["frontier_frac"]
                   + r["bounce_frac"] - 1.0) < 5e-4, r
    split_rows = [r for r in rows if r["stream"] == "split"]
    assert all(r["index_ratio"] > 1 for r in split_rows)

    out = {
        "meta": {
            "jax_backend": jax.default_backend(),
            "interpreted_fused": jax.default_backend() not in ("tpu",),
            "quick": args.quick,
            "steps": steps,
            "dtype": args.dtype,
            "orders": orders,
            "node_orders": node_orders,
            "backends": backends,
            "streams": sorted({"split" if s else "mono"
                               for s in split_modes}),
        },
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"# geometry suite OK: {len(rows)} rows -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
