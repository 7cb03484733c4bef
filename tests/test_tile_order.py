"""Tile traversal orders (LBMConfig.tile_order) — the data-placement knob.

Pins the tentpole invariants:
* every ordering is a pure permutation of the z-major tiling (same tiles,
  consistent tile_map / neighbour table / streaming tables),
* the Hilbert curve really is a Hilbert curve (consecutive tiles
  face-adjacent on a full grid),
* physics is ORDER-NEUTRAL: bitwise-identical dense fields on the gather
  backend, 1e-12 float64 parity on the fused backend, for a sparse
  (spheres) and a body-like (vessel) geometry,
* only slab-compatible orderings are accepted by the slab decomposition,
  and morton_slab halo tile-rows line up between neighbouring devices.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import collision as C
from repro.core.boundary import BoundarySpec
from repro.core.engine import LBMConfig, SparseTiledLBM
from repro.core.lattice import get_lattice
from repro.core.streaming import build_stream_tables
from repro.core.tiling import (INLET, OUTLET, SLAB_COMPATIBLE_ORDERS, SOLID,
                               TILE_ORDERS, hilbert_key_3d, tile_field,
                               tile_geometry, untile)
from repro.data.geometry import duct_wrap, random_spheres, vessel_aneurysm

BCS = ((INLET, BoundarySpec("velocity", (0, 0, 1), velocity=(0, 0, 0.03))),
       (OUTLET, BoundarySpec("pressure", (0, 0, -1), rho=1.0)))


def _spheres():
    return random_spheres(box=16, porosity=0.6, diameter=8, seed=1)


def _vessel():
    return vessel_aneurysm((48, 32, 32), radius=8.0, bulge=10.0)


# ---------------------------------------------------------------- structure
@pytest.mark.parametrize("order", TILE_ORDERS)
@pytest.mark.parametrize("geom", ["spheres", "vessel"])
def test_order_is_pure_permutation(order, geom):
    g = _spheres() if geom == "spheres" else _vessel()
    ref = tile_geometry(g, 4)
    t = tile_geometry(g, 4, order=order)
    assert t.order == order
    assert t.num_tiles == ref.num_tiles
    # same tile SET, possibly different enumeration
    assert (np.sort(t.tile_coords.view([("", t.tile_coords.dtype)] * 3),
                    axis=0)
            == np.sort(ref.tile_coords.view(
                [("", ref.tile_coords.dtype)] * 3), axis=0)).all()
    # tile_map is the inverse of tile_coords
    for i in range(0, t.num_tiles, max(1, t.num_tiles // 17)):
        x, y, z = t.tile_coords[i]
        assert t.tile_map[x, y, z] == i
    # neighbour table routes through tile_map: re-derive one entry per tile
    own = t.tile_coords.astype(int)
    east = own + (1, 0, 0)
    inside = east[:, 0] < t.tile_grid[0]
    expect = np.full(t.num_tiles, -1, np.int64)
    cl = np.clip(east, 0, np.array(t.tile_grid) - 1)
    expect[inside] = t.tile_map[cl[inside, 0], cl[inside, 1], cl[inside, 2]]
    from repro.core.tiling import neighbor_offset_index
    got = t.tile_neighbors[:, neighbor_offset_index(1, 0, 0)].astype(np.int64)
    assert (np.where(inside, expect, -1) == got).all()


def test_hilbert_is_a_hilbert_curve():
    """On a full cube the Hilbert traversal visits face-adjacent tiles."""
    t = tile_geometry(np.ones((32, 32, 32), np.uint8), 4, order="hilbert")
    step = np.abs(np.diff(t.tile_coords.astype(int), axis=0)).sum(axis=1)
    assert (step == 1).all()
    # and it is a bijection over the 8^3 grid
    assert t.num_tiles == 512


def test_morton_slab_keeps_layers_contiguous():
    g = duct_wrap(_spheres(), wall=4)
    t = tile_geometry(g, 4, order="morton_slab")
    z = t.tile_coords[:, 2].astype(int)
    assert (np.diff(z) >= 0).all()          # z tile-layers stay contiguous
    # within a layer the order depends only on (x, y): two layers with the
    # same non-empty (x, y) footprint enumerate it identically
    by_layer = {}
    for layer in np.unique(z):
        ids = np.nonzero(z == layer)[0]
        by_layer[layer] = [tuple(c) for c in t.tile_coords[ids, :2]]
    footprints = {}
    for layer, seq in by_layer.items():
        key = frozenset(seq)
        if key in footprints:
            assert footprints[key] == seq, f"layer {layer} enumeration drifts"
        footprints[key] = seq


def test_locality_metrics_exposed():
    t = tile_geometry(_vessel(), 4, order="hilbert")
    m = t.locality_metrics()
    assert m["tile_order"] == "hilbert"
    assert m["mean_neighbor_index_distance"] > 0
    assert sum(m["neighbor_index_distance_hist"].values()) == \
        len(t.neighbor_index_distances())
    tabs = build_stream_tables(t, get_lattice("D3Q19"))
    assert tabs.mean_link_distance > 0
    assert 0 < tabs.cross_tile_frac < 1
    assert sum(tabs.link_distance_hist.values()) > 0


@pytest.mark.parametrize("order", TILE_ORDERS)
def test_tile_untile_roundtrip_all_orders(order):
    rng = np.random.default_rng(3)
    g = (rng.random((19, 13, 27)) < 0.3).astype(np.uint8)
    t = tile_geometry(g, 4, order=order)
    dense = rng.random((19, 13, 27))
    back = untile(t, tile_field(t, dense), fill=np.nan)
    fluid = np.zeros(t.shape, bool)
    fluid[:19, :13, :27] = g != SOLID
    pad = np.pad(dense, [(0, t.shape[i] - dense.shape[i]) for i in range(3)])
    assert np.array_equal(back[fluid], pad[fluid])


def test_streaming_tables_follow_tile_map():
    """Decode gather_idx under a reordered tiling: every pulled value must
    come from the geometric source node x - e (periodic box, no bounce)."""
    g = np.ones((8, 8, 8), np.uint8)
    lat = get_lattice("D3Q19")
    t = tile_geometry(g, 4, order="morton")
    tabs = build_stream_tables(t, lat, "xyz", periodic=(True, True, True))
    coords = t.node_coords().astype(np.int64)           # (T, n, 3)
    n = t.nodes_per_tile
    m = t.num_tiles * n
    flat_of = np.full(t.shape, -1, np.int64)
    flat_of[coords[..., 0], coords[..., 1], coords[..., 2]] = (
        np.arange(t.num_tiles)[:, None] * n + np.arange(n)[None, :])
    for q in (1, 7, 14):
        src = (coords - lat.e[q].astype(np.int64)) % 8
        want = q * m + flat_of[src[..., 0], src[..., 1], src[..., 2]]
        assert np.array_equal(tabs.gather_idx[q].astype(np.int64), want)


# ------------------------------------------------------------------ physics
def _dense_fields(eng):
    rho, u = eng.macroscopics()
    return (untile(eng.tiling, np.asarray(rho), fill=0.0),
            untile(eng.tiling, np.asarray(u), fill=0.0))


@pytest.mark.parametrize("geom", ["spheres", "vessel"])
def test_gather_bitwise_identical_across_orders(geom):
    """Acceptance: every ordering produces BITWISE-identical dense physics
    to zmajor on the gather backend."""
    if geom == "spheres":
        g, kw = duct_wrap(_spheres(), wall=4), dict(boundaries=BCS)
    else:
        g = _vessel()
        kw = dict(boundaries=(
            (INLET, BoundarySpec("velocity", (1, 0, 0),
                                 velocity=(0.02, 0, 0))),
            (OUTLET, BoundarySpec("pressure", (-1, 0, 0), rho=1.0))))
    ref = None
    for order in TILE_ORDERS:
        eng = SparseTiledLBM(g, LBMConfig(
            collision=C.CollisionConfig(tau=0.8), dtype="float32",
            layout_scheme="paper", tile_order=order, **kw))
        eng.run(6)
        rho, u = _dense_fields(eng)
        if ref is None:
            ref = (rho, u)
        else:
            assert np.array_equal(ref[0], rho), order
            assert np.array_equal(ref[1], u), order


@pytest.mark.parametrize("geom,order", [
    ("spheres", "morton"),
    ("spheres", "hilbert"),
    ("spheres", "morton_slab"),
    ("vessel", "hilbert"),           # body-like geometry, NEBB boundaries
])
@pytest.mark.usefixtures("x64")
def test_fused_parity_across_orders(geom, order):
    """Fused backend under reordering matches zmajor gather to 1e-12, on a
    sparse (spheres) and a body-like (vessel) geometry."""
    if geom == "spheres":
        g = _spheres()
        base = dict(collision=C.CollisionConfig(tau=0.7),
                    dtype="float64", periodic=(True, True, True),
                    u0=(0.01, 0.0, 0.02))
    else:
        g = vessel_aneurysm((32, 24, 24), radius=7.0, bulge=8.0)
        base = dict(collision=C.CollisionConfig(tau=0.8),
                    dtype="float64", boundaries=(
                        (INLET, BoundarySpec("velocity", (1, 0, 0),
                                             velocity=(0.02, 0, 0))),
                        (OUTLET, BoundarySpec("pressure", (-1, 0, 0),
                                             rho=1.0))))
    ref = SparseTiledLBM(g, LBMConfig(backend="gather", **base))
    eng = SparseTiledLBM(g, LBMConfig(backend="fused", tile_order=order,
                                      **base))
    ref.run(4)
    eng.run(4)
    r0, u0 = _dense_fields(ref)
    r1, u1 = _dense_fields(eng)
    assert float(np.abs(r0 - r1).max()) < 1e-12
    assert float(np.abs(u0 - u1).max()) < 1e-12


# ----------------------------------------------------------------- sharding
def test_slab_plan_rejects_global_curves():
    from repro.dist.lbm import make_slab_plan

    g = duct_wrap(_spheres(), wall=4)
    for order in ("morton", "hilbert"):
        with pytest.raises(ValueError, match="slab-compatible"):
            make_slab_plan(g, 4, 2, tile_order=order)
    assert set(SLAB_COMPATIBLE_ORDERS) == {"zmajor", "morton_slab"}


@pytest.mark.parametrize("order", SLAB_COMPATIBLE_ORDERS)
def test_slab_plan_halo_rows_align(order):
    """Adjacent devices enumerate a shared halo tile-layer identically, so
    ppermute payloads line up element-wise (the invariant _tiles_at_layer
    relies on for every slab-compatible ordering)."""
    from repro.dist.lbm import _tiles_at_layer, make_slab_plan

    g = duct_wrap(_spheres(), wall=4)
    plan = make_slab_plan(g, 4, 2, tile_order=order)
    assert plan.tile_order == order
    assert plan.n_fluid_own == tile_geometry(g, 4).n_fluid_nodes
    assert 0 < plan.tile_utilisation <= 1
    for d in range(plan.n_dev - 1):
        lt, nxt = plan.local_tilings[d], plan.local_tilings[d + 1]
        top = plan.owned_layer_range_local(d)[1] - 1
        send = _tiles_at_layer(lt, top)                  # d's top owned row
        recv = _tiles_at_layer(nxt, 0)                   # d+1's bottom halo
        assert len(send) == len(recv)
        assert np.array_equal(lt.tile_coords[send][:, :2],
                              nxt.tile_coords[recv][:, :2])
