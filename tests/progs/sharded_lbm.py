"""Multi-device prog: sharded LBM == single-device engine (8 fake devices)."""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.core.engine import SparseTiledLBM, LBMConfig
from repro.core import collision as C
from repro.core.tiling import SOLID, INLET, OUTLET, tile_geometry
from repro.data.geometry import duct
from repro.core.boundary import BoundarySpec
from repro.dist.lbm import ShardedLBM

g = duct(16, 16, 64, open_ends=True)
cfg = LBMConfig(
    collision=C.CollisionConfig(model="lbgk", fluid="incompressible", tau=0.8),
    layout_scheme="paper", dtype="float64",
    boundaries=((INLET, BoundarySpec("velocity", (0, 0, 1), velocity=(0, 0, 0.05))),
                (OUTLET, BoundarySpec("pressure", (0, 0, -1), rho=1.0))))
ref = SparseTiledLBM(g, cfg); ref.step(15)
rho_r, _ = ref.fields_dense()
mesh = jax.make_mesh((8,), ("data",))


def step_synced(sh, steps):
    """One step at a time, each finished before the next is dispatched:
    with several 8-device programs in flight, the CPU backend's in-process
    collectives can deadlock (a rendezvous that never fills) when the host
    is busy."""
    for _ in range(steps):
        sh.step()
        jax.block_until_ready(sh.f)


sh = ShardedLBM(g, cfg, mesh); step_synced(sh, 15)
rho_s, _, types, own = sh.macroscopics_own()
a = cfg.a
dense_s = np.full(ref.tiling.shape, np.nan)
for d in range(sh.plan.n_dev):
    zl, zh = sh.plan.layer_of_dev[d]
    g_lo = max(0, zl - 1)
    g_hi = min(ref.tiling.tile_grid[2], zh + 1)
    sub_geo = np.full((g.shape[0], g.shape[1], (g_hi - g_lo) * a), SOLID, np.uint8)
    src = g[:, :, g_lo * a: min(g.shape[2], g_hi * a)]
    sub_geo[:, :, :src.shape[2]] = src
    sub_t = tile_geometry(sub_geo, a)
    for t in range(sub_t.num_tiles):
        if not own[d, t]:
            continue
        cx, cy, cz = sub_t.tile_coords[t]
        blk = rho_s[d, t].reshape(a, a, a).transpose(2, 1, 0)
        dense_s[cx*a:(cx+1)*a, cy*a:(cy+1)*a, (cz+g_lo)*a:(cz+g_lo+1)*a] = blk
fluid = np.zeros(ref.tiling.shape, bool)
fluid[:g.shape[0], :g.shape[1], :g.shape[2]] = g != SOLID
err = np.nanmax(np.abs(np.where(fluid, dense_s - rho_r, 0.0)))
assert err < 1e-12, err
assert abs(ref.total_mass() - sh.total_mass()) / ref.total_mass() < 1e-10

# split-phase streaming + frontier_last node order: same oracle (the
# gather step is policy-neutral), same 1e-12 parity on owned tiles
import dataclasses
cfg2 = dataclasses.replace(cfg, split_stream=True, node_order="frontier_last")
sh2 = ShardedLBM(g, cfg2, mesh); step_synced(sh2, 15)
rho_s2, _, _, own2 = sh2.macroscopics_own()
dense_s2 = np.full(ref.tiling.shape, np.nan)
for d, lt in enumerate(sh2.plan.local_tilings):
    z_base = sh2.plan.layer_of_dev[d][0] - sh2.plan.own_z0[d]
    o = own2[d, :lt.num_tiles]
    coords = lt.node_coords()[o] + np.array([0, 0, z_base * a])
    dense_s2[coords[..., 0], coords[..., 1], coords[..., 2]] = \
        rho_s2[d, :lt.num_tiles][o]
err2 = np.nanmax(np.abs(np.where(fluid, dense_s2 - rho_r, 0.0)))
assert err2 < 1e-12, err2
fr = sh2.stream_fracs
assert abs(fr["interior_frac"] + fr["frontier_frac"]
           + fr["bounce_frac"] - 1.0) < 1e-9, fr
print("SHARDED_OK")
