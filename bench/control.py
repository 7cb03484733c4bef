#!/usr/bin/env python3
"""Readings that the limit of ``max_abs_df`` is set from, each judged by a
whole run of the cell's driver and printed as the harness's result line.

    python3 bench/control.py --workload <cell> --calls <window calls> \
        [--seeds 1,2,...] [--control-seeds 1,2,3] [--fault-seeds 1,2,3] \
        [--out <file.jsonl>]

One solver of the cell's configuration is built once and reused by every
run; each run gives it its seed's initial flow, makes the warm call and
``--calls`` window calls, and judges the state against the float32
reference exactly as a benchmark run does (the driver's own readout,
``max_abs_diff`` and ``correct``).

- ``program`` (``--seeds``): the solver itself.
- ``control`` (``--control-seeds``): the dense reference computed one
  precision below the configuration's (bfloat16 for float32), put in the
  solver's place: each call reads the solver's state through its backend,
  advances it and writes it back.
- faults (``--fault-seeds``), planted in the float32 reference put in the
  solver's place: ``unchanged`` (each call returns the state it was given),
  ``half_tiles`` (the second half of the tiles keeps its old state) and
  ``altered`` (one tile's populations, produced right, then scaled by 1.01).

The benchmark's own runs never run this.  Each run is one JSON line on
standard output (and in ``--out``): the harness's result line with the
``kind`` and ``seed`` of the run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import geometry as geo  # noqa: E402
from bench import harness, reference  # noqa: E402

LOWER = {"float64": "float32", "float32": "bfloat16"}


def _unchanged(old, new, fluid_tile):
    return old


def _half_tiles(old, new, fluid_tile):
    half = old.shape[1] // 2
    return new.at[:, half:].set(old[:, half:])


def _altered(old, new, fluid_tile):
    return new.at[:, fluid_tile].multiply(1.01)


FAULTS = {"unchanged": _unchanged, "half_tiles": _half_tiles,
          "altered": _altered}


class ReferenceInPlace:
    """``stand_in`` for the driver: the dense reference in ``dtype``
    advances the solver's state.  Each call scatters the solver's canonical
    state (``backend.canonical``) into the reference's padded box, makes
    ``k`` reference steps, gathers the tiles' nodes back and packs them
    (``backend.initial_state``); ``fault(old, new, fluid_tile)`` may alter
    the canonical (Q, T, n) result before it is packed."""

    def __init__(self, geometry: np.ndarray, config: dict, dtype: str,
                 fault=None):
        self.geometry, self.config, self.dtype = geometry, config, dtype
        self.fault = fault
        self._eng = None

    def _prepare(self, eng):
        types, origin = reference.padded_box(self.geometry,
                                             self.config["tile_edge"])
        idx = eng.tiling.node_coords() - np.asarray(origin)
        self._idx = tuple(jnp.asarray(idx[..., ax]) for ax in range(3))
        self._types = jnp.asarray(types)
        fluid = (eng.tiling.node_types != geo.SOLID).sum(axis=1)
        self._fluid_tile = int(np.argmax(fluid))
        self._step = reference.make_step(self.config)
        q = len(reference.lattice(self.config["lattice"])[0])
        shape, dt = (q,) + types.shape, jnp.dtype(self.dtype)
        self._to_box = jax.jit(lambda c, ix, iy, iz: jnp.zeros(shape, dt)
                               .at[:, ix, iy, iz].set(c.astype(dt)))
        self._from_box = jax.jit(lambda b, ix, iy, iz, like: b[:, ix, iy, iz]
                                 .astype(like.dtype))
        self._eng = eng

    def __call__(self, eng, k: int) -> None:
        if self._eng is not eng:
            self._prepare(eng)
        old = eng.backend.canonical(eng.f)
        box = self._to_box(old, *self._idx)
        for _ in range(k):
            box = self._step(box, self._types)
        new = self._from_box(box, *self._idx, old)
        del box
        if self.fault is not None:
            new = self.fault(old, new, self._fluid_tile)
        eng.f = eng.backend.initial_state(new)


def stand_ins(geometry: np.ndarray, config: dict) -> dict:
    """``{kind: stand_in}``: the control and every planted fault."""
    out = {"control": ReferenceInPlace(geometry, config,
                                       LOWER[config["dtype"]])}
    for name, fault in FAULTS.items():
        out[name] = ReferenceInPlace(geometry, config, config["dtype"], fault)
    return out


def readings(config: dict, traffic: dict, runs, calls: int):
    """Yield ``(kind, seed, run)`` for each ``(kind, seed)`` in ``runs``:
    the driver's whole run, one solver built for all of them."""
    driver = harness.module(CHECKOUT, "drivers", traffic["driver"])
    built = driver.build(config, traffic)
    kinds = stand_ins(built[0], config)
    kinds["program"] = None
    for kind, seed in runs:
        yield kind, seed, driver.run(
            config, traffic, seed=seed, seconds=0.0, trace=False,
            t0=time.perf_counter(), built=built, stand_in=kinds[kind],
            calls=calls)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--calls", type=int, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(CHECKOUT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = harness.load_benchmark(CHECKOUT)
    cell = harness.workload(bench, args.workload)
    parse = lambda s: [int(v) for v in s.split(",") if v]  # noqa: E731
    runs = [("program", s) for s in parse(args.seeds)]
    runs += [("control", s) for s in parse(args.control_seeds)]
    runs += [(f, s) for s in parse(args.fault_seeds) for f in FAULTS]
    config = harness.config(CHECKOUT, bench, cell["config"])
    traffic = harness.traffic(CHECKOUT, cell["traffic"])
    for kind, seed, run in readings(config, traffic, runs, args.calls):
        line = harness.result_line(CHECKOUT, bench, cell, run, False)
        text = json.dumps(dict(kind=kind, seed=seed, **line))
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as fh:
                fh.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
