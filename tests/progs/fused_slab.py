"""Multi-device prog: ShardedLBM with backend='fused' == backend='gather'
on the same 8-slab mesh (owned tiles, float64, 1e-12), and mass parity with
the single-device fused engine — for BOTH slab-compatible tile orderings
('zmajor' and 'morton_slab', the locality ordering that keeps slabs
contiguous).  Chained with progs/sharded_lbm.py (gather sharded ==
single-device reference), this pins the fused slab step to the reference
physics under reordering."""

import jax

jax.config.update("jax_enable_x64", True)
import numpy as np

from repro.core import collision as C
from repro.core.boundary import BoundarySpec
from repro.core.engine import LBMConfig, SparseTiledLBM
from repro.core.tiling import INLET, OUTLET, SOLID
from repro.data.geometry import duct
from repro.dist.lbm import ShardedLBM


g = duct(12, 12, 32, open_ends=True)
mesh = jax.make_mesh((8,), ("data",))
for order in ("zmajor", "morton_slab"):
    base = dict(
        collision=C.CollisionConfig(model="lbgk", fluid="incompressible",
                                    tau=0.8),
        dtype="float64", tile_order=order,
        boundaries=((INLET, BoundarySpec("velocity", (0, 0, 1),
                                         velocity=(0, 0, 0.05))),
                    (OUTLET, BoundarySpec("pressure", (0, 0, -1), rho=1.0))))

    sh_f = ShardedLBM(g, LBMConfig(backend="fused", **base), mesh)
    sh_g = ShardedLBM(g, LBMConfig(backend="gather", **base), mesh)
    # exercise both the per-step jit path and the fori_loop run path
    sh_f.step(8); sh_f.run(4)
    sh_g.step(8); sh_g.run(4)

    rho_f, u_f, types, own = sh_f.macroscopics_own()
    rho_g, u_g, _, _ = sh_g.macroscopics_own()
    err_r = err_u = 0.0
    for d in range(sh_f.plan.n_dev):
        m = own[d][:, None] & (types[d] != SOLID)
        err_r = max(err_r, float(np.max(np.abs(
            np.where(m, rho_f[d] - rho_g[d], 0.0)))))
        err_u = max(err_u, float(np.max(np.abs(
            np.where(m[None], u_f[:, d] - u_g[:, d], 0.0)))))
    assert err_r < 1e-12, (order, err_r)
    assert err_u < 1e-12, (order, err_u)

    ref = SparseTiledLBM(g, LBMConfig(backend="fused", **base))
    ref.step(8); ref.run(4)
    assert abs(ref.total_mass() - sh_f.total_mass()) / ref.total_mass() \
        < 1e-10, order
    print(f"FUSED_SLAB_OK[{order}]")
print("FUSED_SLAB_OK")
