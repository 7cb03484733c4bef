"""Ensemble amortisation benchmark: throughput and indirection-table
traffic vs batch width B.

The follow-up paper ("Sparse geometries handling...", arXiv:1703.08015)
shows the sparse engine's indirection tables dominate bandwidth as the
geometry gets sparser.  ``repro.sim.ensemble`` batches B independent flow
states over ONE set of tables: on the gather backend every index table is
shared across the batch, so the index bytes **per node update** fall
exactly as 1/B (the f traffic per update stays constant); on the fused
backend the per-replica neighbour table is replicated and only the static
pull tables amortise, so the figure falls sub-1/B towards that floor.
This benchmark reports both columns per backend/streaming mode:

* ``aggregate_mflups`` — million fluid-node updates/s across all replicas
  (one jitted fori_loop dispatch for the whole measurement window),
* ``index_bytes_per_node_update`` — indirection-table bytes loaded per
  fluid-node update (exact, from the engine's table accounting),

plus the per-replica MFLUPS and the modelled total bytes per update.  CPU
numbers track the trajectory only (see benchmarks/common.py); the 1/B
index-traffic column is hardware-independent.

    PYTHONPATH=src python -m benchmarks.ensemble_scaling --quick   # CI-sized
    PYTHONPATH=src python -m benchmarks.ensemble_scaling           # bigger

Emits ``BENCH_ensemble_scaling.json``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax

from repro.core import collision as C
from repro.core.engine import LBMConfig, SparseTiledLBM
from repro.data import geometry as geo
from repro.launch.lbm import _Z_FLOW


def bench_cases(quick: bool) -> dict:
    """Sparse geometries where the index tables actually bite."""
    if quick:
        return {
            "spheres_p0.7": geo.duct_wrap(geo.random_spheres(
                box=12, porosity=0.7, diameter=6, seed=0), wall=2),
        }
    return {
        "spheres_p0.7": geo.duct_wrap(geo.random_spheres(
            box=48, porosity=0.7, diameter=12, seed=0)),
        "spheres_p0.5": geo.duct_wrap(geo.random_spheres(
            box=48, porosity=0.5, diameter=12, seed=1)),
    }


VARIANTS = (("gather", False), ("gather", True), ("fused", False))


def run_bench(cases: dict, batches, steps: int, dtype: str,
              boundaries=_Z_FLOW, periodic=(False, False, True)) -> list:
    rows = []
    print("geometry,backend,stream,B,agg_MFLUPS,per_replica_MFLUPS,"
          "index_B_per_update")
    for gname, g in cases.items():
        for backend, split in VARIANTS:
            cfg = LBMConfig(
                collision=C.CollisionConfig(tau=0.6),
                layout_scheme="xyz" if backend == "fused" else "paper",
                dtype=dtype, boundaries=boundaries, periodic=periodic,
                backend=backend, split_stream=split)
            eng = SparseTiledLBM(g, cfg)
            for b in batches:
                ens = eng.ensemble(b)
                ens.run(steps)                  # compile + warm
                jax.block_until_ready(ens.f)
                ens.reset()
                t0 = time.perf_counter()
                ens.run(steps)
                jax.block_until_ready(ens.f)
                dt = (time.perf_counter() - t0) / steps
                agg = ens.aggregate_mflups(dt)
                row = {
                    "geometry": gname,
                    "backend": backend,
                    "stream": "split" if split else "mono",
                    "batch": b,
                    "aggregate_mflups": round(agg, 4),
                    "per_replica_mflups": round(agg / b, 4),
                    "seconds_per_step": dt,
                    "n_fluid_nodes": ens.n_fluid_nodes,
                    "index_bytes_per_step": ens.index_bytes_per_step(),
                    "index_bytes_per_node_update":
                        round(ens.index_bytes_per_node_update(), 3),
                    "f_bytes_per_node_update":
                        round(eng.bytes_per_step()
                              / max(1, eng.n_fluid_nodes), 3),
                }
                rows.append(row)
                print(f"{gname},{backend},{row['stream']},{b},"
                      f"{row['aggregate_mflups']},"
                      f"{row['per_replica_mflups']},"
                      f"{row['index_bytes_per_node_update']}")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized geometry / step counts")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batches", default=None,
                    help="comma-separated batch widths (default 1,2,4 quick;"
                         " 1,2,4,8 otherwise)")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--out", default="BENCH_ensemble_scaling.json")
    args = ap.parse_args(argv)

    batches = ([int(b) for b in args.batches.split(",")] if args.batches
               else [1, 2, 4] if args.quick else [1, 2, 4, 8])
    steps = args.steps or (2 if args.quick else 20)
    rows = run_bench(bench_cases(args.quick), batches, steps, args.dtype)

    # the amortisation claim, asserted per backend: on gather every index
    # table is shared across the batch, so B doubled -> index bytes per
    # node update exactly halved; on fused the neighbour table is
    # replicated per replica, so the per-update figure still falls (the
    # static pull tables amortise) but strictly less than 1/B, towards
    # the replicated-neighbour-table floor
    by_key = {}
    for r in rows:
        by_key.setdefault((r["geometry"], r["backend"], r["stream"]),
                          []).append(r)
    for key, rs in by_key.items():
        rs = sorted(rs, key=lambda r: r["batch"])
        for lo, hi in zip(rs, rs[1:]):
            ratio = (lo["index_bytes_per_node_update"]
                     / hi["index_bytes_per_node_update"])
            full = hi["batch"] / lo["batch"]
            if key[1] == "gather":
                assert abs(ratio - full) < 0.01, (key, ratio, full)
            else:
                assert 1.0 < ratio < full, (key, ratio, full)
        assert all(r["aggregate_mflups"] > 0 for r in rs), key

    out = {
        "meta": {
            "jax_backend": jax.default_backend(),
            "interpreted_fused": jax.default_backend() not in ("tpu",),
            "quick": args.quick,
            "steps": steps,
            "dtype": args.dtype,
            "batches": batches,
        },
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"# ensemble scaling OK: {len(rows)} rows -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
