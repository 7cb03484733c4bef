"""Physics validation: Poiseuille analytic profile, mass conservation,
sparse-vs-dense engine equivalence, collision model cross-checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import collision as C
from repro.core.boundary import BoundarySpec
from repro.core.engine import LBMConfig, SparseTiledLBM
from repro.core.dense import DenseLBM
from repro.core.tiling import INLET, OUTLET, SOLID
from repro.data.geometry import cavity3d, channel2d, duct, random_spheres

pytestmark = pytest.mark.usefixtures("x64")

LID = 4


def test_poiseuille_2d_analytic():
    """Body-force-driven D2Q9 channel flow converges to the parabolic
    profile u(y) = g/(2 nu) * y (H - y) (half-way bounce-back walls)."""
    ny = 21
    g_force = 1e-6
    tau = 0.8
    nu = (tau - 0.5) / 3.0
    geom = channel2d(4, ny)
    cfg = LBMConfig(
        lattice="D2Q9", a=4, layout_scheme="xyz", dtype="float32",
        collision=C.CollisionConfig(model="lbgk", fluid="incompressible",
                                    tau=tau),
        periodic=(True, False, True),
        force=(g_force, 0.0, 0.0),
    )
    eng = SparseTiledLBM(geom, cfg)
    eng.run(4000)
    rho, u = eng.fields_dense()
    ux = u[0, 1, 1:ny-1, 0]         # profile across fluid rows (padded grid)
    y = np.arange(1, ny - 1) - 0.5  # half-way walls at y=0.5, ny-1.5
    h = ny - 2.0
    u_exact = g_force / (2 * nu) * y * (h - y)
    err = np.abs(ux - u_exact).max() / u_exact.max()
    assert err < 0.02, f"Poiseuille profile error {err:.3%}"


@pytest.mark.parametrize("model", ["lbgk", "lbmrt"])
@pytest.mark.parametrize("fluid", ["incompressible", "quasi_compressible"])
def test_mass_conservation_closed_box(model, fluid):
    """Periodic all-fluid box conserves total mass for all 4 kernel
    variants (the paper's four collision x fluid combinations)."""
    g = np.ones((8, 8, 8), np.uint8)
    cfg = LBMConfig(
        collision=C.CollisionConfig(model=model, fluid=fluid, tau=0.7),
        layout_scheme="paper", dtype="float64",
        periodic=(True, True, True),
        u0=(0.02, 0.01, -0.015),
    )
    eng = SparseTiledLBM(g, cfg)
    m0 = eng.total_mass()
    eng.step(50)
    assert abs(eng.total_mass() - m0) / m0 < 1e-12


@pytest.mark.parametrize("layout", ["xyz", "paper"])
def test_sparse_matches_dense_engine(layout):
    """The tiled engine must agree with the classic dense (roll-based)
    engine — the paper's correctness oracle — on a sparse geometry."""
    rng = np.random.default_rng(3)
    g = (rng.random((12, 12, 12)) < 0.8).astype(np.uint8)
    g[5:7, 5:7, 5:7] = 1
    cfg = LBMConfig(
        collision=C.CollisionConfig(model="lbgk", fluid="incompressible",
                                    tau=0.65),
        layout_scheme=layout, dtype="float64",
        periodic=(True, True, True), u0=(0.01, 0.0, 0.02),
    )
    sp = SparseTiledLBM(g, cfg)
    de = DenseLBM(np.pad(g, [(0, sp.tiling.shape[i] - g.shape[i])
                             for i in range(3)]), cfg)
    sp.step(10)
    de.step(10)
    rho_s, u_s = sp.fields_dense()
    rho_d, u_d = de.macroscopics()
    fluid = np.asarray(de.node_type != SOLID)
    assert np.nanmax(np.abs(np.where(fluid, rho_s - np.asarray(rho_d), 0))) < 1e-12
    assert np.max(np.abs(np.where(fluid[None], u_s - np.asarray(u_d), 0))) < 1e-12


def test_mrt_equal_rates_matches_lbgk_dynamics():
    g = cavity3d(12)
    base = dict(layout_scheme="xyz", dtype="float64",
                boundaries=((LID, BoundarySpec("velocity", (0, 0, -1),
                                               velocity=(0.05, 0, 0))),))
    cfg_bgk = LBMConfig(collision=C.CollisionConfig("lbgk", tau=0.6), **base)
    eng = SparseTiledLBM(g, cfg_bgk)
    eng.step(20)
    rho1, u1 = eng.fields_dense()
    # equal-rate MRT == LBGK exactly (see lattice.d3q19_mrt_collision_matrix);
    # heterogeneous-rate MRT differs but stays stable and conserves mass.
    cfg_mrt = LBMConfig(collision=C.CollisionConfig("lbmrt", tau=0.6), **base)
    eng2 = SparseTiledLBM(g, cfg_mrt)
    eng2.step(20)
    rho2, u2 = eng2.fields_dense()
    assert np.isfinite(np.asarray(u2)).all()
    assert np.nanmax(np.abs(rho2 - 1.0)) < 0.1
    assert not np.allclose(u1, u2)    # different relaxation spectra


def test_duct_flow_develops():
    """Inlet/outlet duct: velocity BC drives flow; outlet pressure holds."""
    g = duct(12, 12, 32)
    cfg = LBMConfig(
        collision=C.CollisionConfig(tau=0.8), layout_scheme="paper",
        dtype="float32",
        boundaries=((INLET, BoundarySpec("velocity", (0, 0, 1),
                                         velocity=(0, 0, 0.05))),
                    (OUTLET, BoundarySpec("pressure", (0, 0, -1), rho=1.0))),
    )
    eng = SparseTiledLBM(g, cfg)
    eng.run(300)
    rho, u = eng.fields_dense()
    uz_mid = u[2, 6, 6, 16]
    assert 0.01 < uz_mid < 0.12
    assert np.isfinite(np.asarray(u)).all()


def test_random_spheres_stable():
    g = random_spheres(box=48, porosity=0.7, diameter=12, seed=1)
    cfg = LBMConfig(
        collision=C.CollisionConfig(tau=0.7), layout_scheme="paper",
        dtype="float64", periodic=(True, True, True),
        force=(0.0, 0.0, 1e-5),
    )
    eng = SparseTiledLBM(g, cfg)
    m0 = eng.total_mass()
    eng.run(100)
    assert abs(eng.total_mass() - m0) / m0 < 1e-9
    t = eng.tiling
    assert 0.3 < t.tile_utilisation <= 1.0
