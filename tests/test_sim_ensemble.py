"""Ensemble stepping (repro.sim.ensemble): B batched states over one
geometry's tables == B independent engines.

Acceptance pins (ISSUE 5): for B in {1, 3}, every replica of the batched
step equals an independent SparseTiledLBM run BITWISE on the gather
backend and to 1e-12 (float64) on the fused backend, across split_stream
on/off and two tile/node orders, with open boundaries exercised (the
replicated NEBB pass), plus the 1/B indirection-traffic accounting.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import collision as C
from repro.core.boundary import BoundarySpec
from repro.core.engine import LBMConfig, SparseTiledLBM
from repro.core.tiling import INLET, OUTLET
from repro.data.geometry import channel2d, duct_wrap, random_spheres


pytestmark = pytest.mark.usefixtures("x64")


TOL = 1e-12

BCS = ((INLET, BoundarySpec("velocity", (0, 0, 1), velocity=(0, 0, 0.03))),
       (OUTLET, BoundarySpec("pressure", (0, 0, -1), rho=1.0)))

# two genuinely different placement policies (acceptance: >= 2 orders)
ORDERS = (("zmajor", "canonical"), ("morton", "frontier_last"))


def _spheres():
    return duct_wrap(random_spheres(box=12, porosity=0.6, diameter=6,
                                    seed=1), wall=2)


def _perturbed_canonical(eng: SparseTiledLBM, b: int) -> np.ndarray:
    """Replica-distinct initial state (so parity is not vacuous)."""
    return np.asarray(eng._initial_feq()) * (1.0 + 0.01 * (b + 1))


def _ensemble_vs_independent(cfg, geometry, batch, steps=4):
    """Build one ensemble + `batch` independent engines from identical
    per-replica states; step both; return list of (canonical_ensemble,
    canonical_independent) pairs."""
    eng = SparseTiledLBM(geometry, cfg)
    ens = eng.ensemble(batch)
    singles = []
    for b in range(batch):
        e2 = SparseTiledLBM(geometry, cfg)
        f_canon = _perturbed_canonical(e2, b)
        e2.f = e2.backend.initial_state(jnp.asarray(f_canon))
        ens.set_replica(b, f_canon)
        singles.append(e2)
    ens.step(steps)
    for e2 in singles:
        e2.step(steps)
    return [(ens.replica_canonical(b),
             singles[b].backend.canonical(singles[b].f))
            for b in range(batch)], ens


@pytest.mark.parametrize("batch", [1, 3])
@pytest.mark.parametrize("split", [False, True])
@pytest.mark.parametrize("tile_order,node_order", ORDERS)
def test_gather_ensemble_bitwise(batch, split, tile_order, node_order):
    """Gather backend: each vmapped replica is BITWISE an independent run
    (boundaries + bounce-back + split/mono streaming included)."""
    cfg = LBMConfig(collision=C.CollisionConfig(model="lbgk"),
                    layout_scheme="paper", dtype="float64", boundaries=BCS,
                    backend="gather", split_stream=split,
                    tile_order=tile_order, node_order=node_order)
    pairs, _ = _ensemble_vs_independent(cfg, _spheres(), batch)
    for b, (c_e, c_s) in enumerate(pairs):
        assert bool(jnp.all(c_e == c_s)), f"replica {b} not bitwise"


@pytest.mark.parametrize("batch", [1, 3])
@pytest.mark.parametrize("tile_order,node_order", ORDERS)
def test_fused_ensemble_parity(batch, tile_order, node_order):
    """Fused backend: the B-replicated packed state (one pallas_call over
    a B*T grid, replicated NEBB pass) matches independent engines to
    1e-12 in float64."""
    cfg = LBMConfig(collision=C.CollisionConfig(model="lbgk"),
                    layout_scheme="xyz", dtype="float64", boundaries=BCS,
                    backend="fused", tile_order=tile_order,
                    node_order=node_order)
    pairs, _ = _ensemble_vs_independent(cfg, _spheres(), batch, steps=3)
    for b, (c_e, c_s) in enumerate(pairs):
        assert float(jnp.max(jnp.abs(c_e - c_s))) < TOL, f"replica {b}"


def test_fused_ensemble_periodic_no_boundaries():
    """Fused ensemble without the NEBB pass: periodic wrap through the
    replicated neighbour table."""
    g = np.ones((8, 8, 8), np.uint8)
    cfg = LBMConfig(collision=C.CollisionConfig(model="lbmrt"),
                    layout_scheme="xyz", dtype="float64",
                    periodic=(True, True, True), backend="fused")
    pairs, _ = _ensemble_vs_independent(cfg, g, batch=2, steps=3)
    for b, (c_e, c_s) in enumerate(pairs):
        assert float(jnp.max(jnp.abs(c_e - c_s))) < TOL, f"replica {b}"


def test_replica_roundtrip_and_reset():
    """set_replica / replica_canonical round-trip exactly; reset(b)
    restores equilibrium for that slot only."""
    cfg = LBMConfig(layout_scheme="paper", dtype="float64", boundaries=BCS,
                    backend="gather")
    eng = SparseTiledLBM(_spheres(), cfg)
    ens = eng.ensemble(3)
    f1 = _perturbed_canonical(eng, 1)
    ens.set_replica(1, f1)
    np.testing.assert_array_equal(np.asarray(ens.replica_canonical(1)), f1)
    ens.reset(1)
    feq = np.asarray(eng._initial_feq())
    np.testing.assert_array_equal(np.asarray(ens.replica_canonical(1)), feq)
    # slot 0 untouched throughout
    np.testing.assert_array_equal(np.asarray(ens.replica_canonical(0)), feq)


def test_ensemble_run_matches_step():
    """run(k) (one fori_loop dispatch) == k x step(1)."""
    cfg = LBMConfig(layout_scheme="paper", dtype="float64",
                    periodic=(True, False, True), lattice="D2Q9",
                    force=(1e-5, 0.0, 0.0), backend="gather")
    g = channel2d(8, 8)
    eng = SparseTiledLBM(g, cfg)
    a = eng.ensemble(2)
    b = eng.ensemble(2)
    a.run(5)
    b.step(5)
    np.testing.assert_array_equal(np.asarray(a.f), np.asarray(b.f))


def test_mass_conserved_per_replica():
    """Closed geometry: every replica conserves its own (distinct) mass."""
    cfg = LBMConfig(layout_scheme="paper", dtype="float64",
                    periodic=(True, True, True), backend="gather")
    eng = SparseTiledLBM(np.ones((8, 8, 8), np.uint8), cfg)
    ens = eng.ensemble(3)
    for b in range(3):
        ens.set_replica(b, _perturbed_canonical(eng, b))
    m0 = ens.total_mass()
    assert len(set(np.round(m0, 6))) == 3          # genuinely distinct
    ens.step(5)
    m1 = ens.total_mass()
    np.testing.assert_allclose(m1, m0, rtol=1e-12)


def test_index_traffic_amortisation():
    """gather: every index table is shared across the batch, so bytes per
    node update fall exactly as 1/B.  fused: the neighbour table is
    materialised per replica, so the figure falls sub-1/B and the
    per-step bytes grow by exactly the replicated neighbour-table term.
    Aggregate MFLUPS accounting scales with B."""
    g = _spheres()
    cfg = LBMConfig(layout_scheme="paper", split_stream=True,
                    backend="gather")
    eng = SparseTiledLBM(g, cfg)
    e1, e4 = eng.ensemble(1), eng.ensemble(4)
    assert e1.index_bytes_per_step() == e4.index_bytes_per_step()
    assert e1.index_bytes_per_node_update() == pytest.approx(
        4 * e4.index_bytes_per_node_update())
    assert e4.aggregate_mflups(1.0) == pytest.approx(
        4 * e1.aggregate_mflups(1.0))

    engf = SparseTiledLBM(g, LBMConfig(layout_scheme="xyz",
                                       backend="fused"))
    f1, f4 = engf.ensemble(1), engf.ensemble(4)
    t = engf.tiling.num_tiles
    assert (f4.index_bytes_per_step() - f1.index_bytes_per_step()
            == 27 * 3 * t * 4)                  # 3 extra replicas' nbr rows
    ratio = (f1.index_bytes_per_node_update()
             / f4.index_bytes_per_node_update())
    assert 1.0 < ratio < 4.0                    # amortises, but sub-1/B
    assert f1.index_bytes_per_step() == engf.index_bytes_per_step()


def test_gather_use_kernel_rejected():
    cfg = LBMConfig(layout_scheme="paper", backend="gather", use_kernel=True)
    eng = SparseTiledLBM(_spheres(), cfg)
    with pytest.raises(ValueError, match="use_kernel"):
        eng.ensemble(2)
