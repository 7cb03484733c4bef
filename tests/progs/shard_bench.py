"""Multi-device prog: the sharded benchmark cell's driver
(``bench/drivers/lbm_shard.py``) run end to end on 4 host devices at a small
size (a duct-wrapped sphere pack of 6 tile layers in 4 slabs, float32, the
fused kernel interpreted), judged against the dense reference exactly as on
the chip:

* the sound run covers every fluid node once and agrees to 1e-5, and the
  halo reader finds every collective of the exchange under the exchange's
  scope in the compiled program;
* running both steps without the halo exchange leaves the answer bitwise
  the same: a one-tile halo holds a-1 = 3 more correct node layers than
  the next step needs, so the owned tiles cannot see a missed exchange
  until a = 4 steps in a row have gone without one;
* an exchange that delivers zeros (its send lists name the dummy tile)
  makes the run not correct;
* the control, the dense reference one precision below the
  configuration's (bfloat16) put in the solver's place through the
  solver's public per-slab API, is not correct either.
"""
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, reference  # noqa: E402

BENCH = harness.load_benchmark(ROOT)
CELL = harness.workload(BENCH, "spheres-p07-384.shard4")
CONFIG = dict(harness.config(ROOT, BENCH, CELL["config"]),
              geometry=[{"op": "random_spheres", "box": 24, "porosity": 0.7,
                         "diameter": 8, "seed": 0},
                        {"op": "duct_wrap", "wall": 1}])
CONFIG.pop("expected")
LIMIT = CONFIG["check"]["max_abs_df"]
SEED = 2**33 + 4242
driver = harness.module(ROOT, "drivers", "lbm_shard")


def with_tables(edit):
    """A stand-in that makes each step with the step tables as
    ``edit(eng, tables)`` leaves them."""
    def stand_in(eng, k):
        tbl = eng.tables
        eng.tables = edit(eng, tbl)
        try:
            eng.step(k)
        finally:
            eng.tables = tbl
    return stand_in


def no_exchange(eng, tbl):
    """Receive masks cleared: every halo row keeps what its slab computed
    for it in the step before."""
    return dict(tbl, rum=jnp.zeros_like(tbl["rum"]),
                rdm=jnp.zeros_like(tbl["rdm"]))


def empty_exchange(eng, tbl):
    """Send lists on the dummy tile: every halo row receives zeros."""
    dummy = jnp.full_like(tbl["su"], eng.plan.t_pad - 1)
    return dict(tbl, su=dummy, sd=dummy)


def lower_precision_reference(geometry):
    """A stand-in that advances the owned tiles with the dense reference
    in bfloat16: each call scatters them into the reference's box, makes
    ``k`` reference steps and loads the result back."""
    types, origin = reference.padded_box(geometry, CONFIG["tile_edge"])
    step = reference.make_step(CONFIG)
    q = len(reference.lattice(CONFIG["lattice"])[0])

    def stand_in(eng, k):
        idx = [tuple(np.moveaxis(c - np.asarray(origin), -1, 0))
               for c in eng.owned_node_coords()]
        box = jnp.zeros((q,) + types.shape, jnp.bfloat16)
        for ix, f in zip(idx, eng.read_owned()):
            box = box.at[(slice(None),) + ix].set(
                np.asarray(f).astype(jnp.bfloat16))
        for _ in range(k):
            box = step(box, jnp.asarray(types))
        eng.load_state([box[(slice(None),) + ix].astype(jnp.float32)
                        for ix in idx])
    return stand_in


def one(mix, built, stand_in=None):
    t = time.perf_counter()
    run = driver.run(CONFIG, mix, seed=SEED, seconds=0.0, trace=False,
                     t0=t, built=built, stand_in=stand_in, calls=1)
    print(f"  {time.perf_counter() - t:.1f}s checks {run.checks}", flush=True)
    return run


assert len(jax.devices()) == 4, jax.devices()
mix = dict(harness.traffic(ROOT, CELL["traffic"]), steps_per_call=1)
built = driver.build(CONFIG, mix)
assert built[1].plan.n_dev == 4 and built[1].plan.tile_layers == 6
# the halo reader finds the exchange by its scope in the compiled program
halo = harness.module(ROOT, "metrics", "dist.halo_ms")
text = built[1].run_fn(1).lower(built[1].f, built[1].tables).compile().as_text()
permutes = {m.group(1) for m in re.finditer(
    r"%([\w.\-]+) = [^\n]*? collective-permute(?:-start|-done)?\(", text)}
assert permutes and permutes <= halo.scoped(text), permutes
gauges = built[3]
assert gauges["dist.slab.count"] == 4, gauges
assert gauges["dist.slab.t_pad"] == built[1].plan.t_pad, gauges

sound = one(mix, built)
assert sound.correct and sound.attempted == 1, sound.checks
pad = harness.module(ROOT, "metrics", "dist.slab_pad_share").read(sound)
assert 0 < pad < 100, pad
assert sound.checks["node_set_mismatch"]["value"] == 0
assert sound.checks["max_abs_df"]["value"] <= 1e-5, sound.checks

skipped = one(mix, built, with_tables(no_exchange))
assert skipped.checks == sound.checks, (skipped.checks, sound.checks)

empty = one(mix, built, with_tables(empty_exchange))
assert not empty.correct, empty.checks
assert empty.checks["max_abs_df"]["value"] > LIMIT, empty.checks

control = one(mix, built, lower_precision_reference(built[0]))
assert not control.correct, control.checks
assert control.checks["max_abs_df"]["value"] > LIMIT, control.checks
print("SHARD_BENCH_OK")
