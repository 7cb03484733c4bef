"""Split-phase streaming (LBMConfig.split_stream) + within-tile node
orders (LBMConfig.node_order) — the PR-4 tentpole invariants.

* the compact split tables (static interior permutation + neighbour-table
  cross links + bounce/irregular lists) reconstruct the monolithic
  ``gather_idx`` BITWISE at every fluid destination, across all
  tile_order x node_order x periodic combinations on a sparse (spheres)
  and a body-like (vessel) geometry,
* the link budget is exhaustive: interior + frontier + bounce == 1,
* the split-phase engine step is bitwise identical to the monolithic
  gather step ('full' mode), and identical at fluid slots in
  'propagation_only' mode,
* the indirection tables shrink >= 10x on the paper-sized spheres case,
* every node order is a pure within-tile permutation; 'frontier_last'
  really sorts all cross-link destinations into the tile suffix,
* the fused backend keeps 1e-12 float64 parity under every node_order,
* a declared-but-absent boundary type skips the fused NEBB pass instead
  of scattering over empty tables.
"""
import numpy as np
import pytest

from repro.core import collision as C
from repro.core.backends import boundary_pass_tables
from repro.core.boundary import BoundarySpec
from repro.core.engine import LBMConfig, SparseTiledLBM
from repro.core.lattice import get_lattice
from repro.core.streaming import build_stream_tables
from repro.core.tiling import (INLET, NODE_ORDERS, OUTLET, SOLID, TILE_ORDERS,
                               node_order_permutation, static_frontier_mask,
                               tile_geometry, untile)
from repro.data.geometry import duct_wrap, random_spheres, vessel_aneurysm
from repro.kernels.stream_collide import build_neighbor_table

BCS = ((INLET, BoundarySpec("velocity", (0, 0, 1), velocity=(0, 0, 0.03))),
       (OUTLET, BoundarySpec("pressure", (0, 0, -1), rho=1.0)))


def _spheres():
    return random_spheres(box=12, porosity=0.6, diameter=6, seed=1)


def _vessel():
    return vessel_aneurysm((32, 24, 24), radius=7.0, bulge=8.0)


def _reconstruct(tabs, tiling, q_cnt):
    """Expand the split tables back into a monolithic flat index array."""
    sp = tabs.split
    t_cnt, n = tiling.num_tiles, tiling.nodes_per_tile
    m = t_cnt * n
    src_tile = np.moveaxis(sp.nbr[:, sp.case.astype(np.int64)], 0, 1)
    full = (np.arange(q_cnt, dtype=np.int64)[:, None, None] * m
            + src_tile.astype(np.int64) * n
            + sp.intra_idx.astype(np.int64)[:, None, :]).reshape(-1)
    bd = sp.bounce_dst.astype(np.int64)
    qq, rem = np.divmod(bd, m)
    tt, ss = np.divmod(rem, n)
    full[bd] = (sp.opp[qq].astype(np.int64) * m + tt * n
                + tabs.perms[sp.opp[qq], ss])
    full[sp.irregular_dst] = sp.irregular_src
    return full


# ------------------------------------------------------- table properties
@pytest.mark.parametrize("tile_order", TILE_ORDERS)
@pytest.mark.parametrize("node_order", NODE_ORDERS)
@pytest.mark.parametrize("periodic", [(False,) * 3, (True, True, True)])
@pytest.mark.parametrize("geom", ["spheres", "vessel"])
def test_split_reconstructs_monolithic_bitwise(tile_order, node_order,
                                               periodic, geom):
    """The property test of the tentpole: split tables == monolithic
    gather_idx at every fluid destination, over the full policy grid."""
    g = _spheres() if geom == "spheres" else _vessel()
    lat = get_lattice("D3Q19")
    t = tile_geometry(g, 4, order=tile_order, node_order=node_order)
    tabs = build_stream_tables(t, lat, "paper", periodic, split=True)
    full = _reconstruct(tabs, t, lat.q)
    fluid = np.broadcast_to((t.node_types != SOLID)[None],
                            tabs.gather_idx.shape).reshape(-1)
    assert np.array_equal(full[fluid], tabs.gather_idx.reshape(-1)[fluid])


@pytest.mark.parametrize("geom", ["spheres", "vessel"])
def test_link_budget_accounts_for_every_link(geom):
    g = _spheres() if geom == "spheres" else _vessel()
    lat = get_lattice("D3Q19")
    for node_order in NODE_ORDERS:
        t = tile_geometry(g, 4, node_order=node_order)
        tabs = build_stream_tables(t, lat, "xyz", split=True)
        total = tabs.interior_frac + tabs.frontier_frac + tabs.bounce_frac
        assert abs(total - 1.0) < 1e-12
        assert tabs.frontier_frac == tabs.cross_tile_frac
        assert 0 < tabs.interior_frac < 1


def test_split_handles_non_tile_aligned_periodic_wrap():
    """Periodic extent % a != 0: the tile-level neighbour table cannot
    express the wrap, so those links must land in the irregular list —
    and the reconstruction must still be exact."""
    rng = np.random.default_rng(7)
    g = (rng.random((10, 8, 8)) < 0.8).astype(np.uint8)
    lat = get_lattice("D3Q19")
    t = tile_geometry(g, 4)
    tabs = build_stream_tables(t, lat, "xyz", (True, False, False),
                               split=True)
    assert tabs.split.irregular_dst.size > 0
    full = _reconstruct(tabs, t, lat.q)
    fluid = np.broadcast_to((t.node_types != SOLID)[None],
                            tabs.gather_idx.shape).reshape(-1)
    assert np.array_equal(full[fluid], tabs.gather_idx.reshape(-1)[fluid])


def test_split_index_tables_shrink_10x_on_paper_spheres():
    """Acceptance: >= 10x fewer indirection-table bytes on the spheres
    benchmark geometry ((Q*n + frontier tables) vs the (Q, T, n) gather)."""
    g = duct_wrap(random_spheres(box=64, porosity=0.7, diameter=16))
    lat = get_lattice("D3Q19")
    t = tile_geometry(g, 4)
    tabs = build_stream_tables(t, lat, "xyz", split=True)
    assert tabs.index_entries_mono / tabs.split.index_entries >= 10
    assert tabs.index_bytes_mono / tabs.split.index_bytes >= 10


# ------------------------------------------------------------ node orders
@pytest.mark.parametrize("order", NODE_ORDERS)
@pytest.mark.parametrize("a", [2, 4, 8])
def test_node_order_is_a_permutation(order, a):
    sigma = node_order_permutation(order, a)
    assert sorted(sigma.tolist()) == list(range(a ** 3))


def test_frontier_last_sorts_face_nodes_to_suffix():
    a = 4
    sigma = node_order_permutation("frontier_last", a)
    face = static_frontier_mask(a)
    interior = (a - 2) ** 3
    assert (sigma[~face] < interior).all()       # interior nodes first
    assert (sigma[face] >= interior).all()       # face nodes = suffix
    # every cross-tile link destination sits in the suffix
    lat = get_lattice("D3Q19")
    t = tile_geometry(_spheres(), a, node_order="frontier_last")
    tabs = build_stream_tables(t, lat, "xyz", split=True)
    cross_slots = np.nonzero(tabs.split.is_cross.any(axis=0))[0]
    assert cross_slots.min() >= interior


@pytest.mark.parametrize("order", NODE_ORDERS)
def test_tile_untile_roundtrip_node_orders(order):
    rng = np.random.default_rng(5)
    g = (rng.random((19, 13, 27)) < 0.4).astype(np.uint8)
    from repro.core.tiling import tile_field

    t = tile_geometry(g, 4, node_order=order)
    dense = rng.random((19, 13, 27))
    back = untile(t, tile_field(t, dense), fill=np.nan)
    fluid = np.zeros(t.shape, bool)
    fluid[:19, :13, :27] = g != SOLID
    pad = np.pad(dense, [(0, t.shape[i] - dense.shape[i]) for i in range(3)])
    assert np.array_equal(back[fluid], pad[fluid])


# --------------------------------------------------------- engine parity
def _pair(g, split_kw, steps=5, **kw):
    base = dict(collision=C.CollisionConfig(tau=0.8), dtype="float32",
                layout_scheme="paper", **kw)
    e0 = SparseTiledLBM(g, LBMConfig(**base))
    e1 = SparseTiledLBM(g, LBMConfig(split_stream=True, **split_kw, **base))
    e0.run(steps)
    e1.run(steps)
    return e0, e1


@pytest.mark.parametrize("tile_order,node_order", [
    ("zmajor", "canonical"),
    ("hilbert", "sfc"),
    ("morton_slab", "frontier_last"),
])
def test_split_engine_bitwise_identical_spheres(tile_order, node_order):
    g = duct_wrap(_spheres(), wall=2)
    e0, e1 = _pair(g, dict(tile_order=tile_order, node_order=node_order),
                   boundaries=BCS)
    c0 = np.asarray(e0.backend.canonical(e0.f))
    # monolithic reference runs zmajor/canonical; both are bitwise
    # order-neutral (test_tile_order), so compare DENSE fields bitwise
    r0, u0 = e0.macroscopics()
    r1, u1 = e1.macroscopics()
    d0 = untile(e0.tiling, np.asarray(r0), fill=0.0)
    d1 = untile(e1.tiling, np.asarray(r1), fill=0.0)
    assert np.array_equal(d0, d1)
    assert np.array_equal(untile(e0.tiling, np.asarray(u0), fill=0.0),
                          untile(e1.tiling, np.asarray(u1), fill=0.0))
    assert np.isfinite(c0).all()


def test_split_engine_bitwise_identical_same_layout():
    """Same tile/node order on both sides: the full packed state must be
    bitwise identical (not just the dense fields)."""
    g = duct_wrap(_spheres(), wall=2)
    for node_order in NODE_ORDERS:
        base = dict(collision=C.CollisionConfig(tau=0.8), dtype="float32",
                    layout_scheme="paper", boundaries=BCS,
                    node_order=node_order)
        e0 = SparseTiledLBM(g, LBMConfig(**base))
        e1 = SparseTiledLBM(g, LBMConfig(split_stream=True, **base))
        e0.run(5)
        e1.run(5)
        assert np.array_equal(np.asarray(e0.f), np.asarray(e1.f)), node_order


def test_split_streaming_op_bitwise_identical():
    """The backend-level bitwise pin: on the SAME state, the split-phase
    streaming op returns exactly the monolithic gather's values at every
    fluid slot (and zero at solid slots), under jit, for every node order
    and a periodic box.  (Full steps additionally run collision, where XLA
    may fuse the arithmetic differently between the two programs — a 1-ULP
    compiler effect unrelated to streaming, bounded by the tests below.)"""
    import jax
    import jax.numpy as jnp

    from repro.core.backends import apply_split_stream

    g = _spheres()
    lat = get_lattice("D3Q19")
    rng = np.random.default_rng(11)
    for node_order in NODE_ORDERS:
        t = tile_geometry(g, 4, node_order=node_order)
        tabs = build_stream_tables(t, lat, "xyz", (True, True, True),
                                   split=True)
        sp = tabs.split
        shape = (lat.q, t.num_tiles, t.nodes_per_tile)
        f = jnp.asarray(rng.random(shape, dtype=np.float32))
        mono = jnp.take(f.reshape(-1),
                        jnp.asarray(tabs.gather_idx.reshape(lat.q, -1)),
                        axis=0).reshape(shape)
        solid = jnp.asarray(t.node_types == SOLID)
        split = jax.jit(apply_split_stream, static_argnames=())(
            f, solid,
            intra=jnp.asarray(sp.intra_idx),
            case=jnp.asarray(sp.case.astype(np.int32)),
            is_cross=jnp.asarray(sp.is_cross),
            nbr=jnp.asarray(sp.nbr),
            bounce_dst=jnp.asarray(sp.bounce_dst),
            irregular_dst=jnp.asarray(sp.irregular_dst),
            irregular_src=jnp.asarray(sp.irregular_src),
            opp=jnp.asarray(sp.opp), perms=jnp.asarray(tabs.perms))
        fluid = ~np.asarray(solid)
        assert np.array_equal(np.asarray(split)[:, fluid],
                              np.asarray(mono)[:, fluid]), node_order
        assert (np.asarray(split)[:, ~fluid] == 0).all()


def test_split_engine_periodic_full_step_parity():
    """Full steps over a periodic box: streaming is bitwise (pinned
    above); collision fusion may differ by 1 ULP per step between the two
    compiled programs, so the bound here is a few float32 ULPs."""
    g = _spheres()
    base = dict(collision=C.CollisionConfig(tau=0.7), dtype="float32",
                periodic=(True, True, True), u0=(0.01, 0.0, 0.02))
    e0 = SparseTiledLBM(g, LBMConfig(**base))
    e1 = SparseTiledLBM(g, LBMConfig(split_stream=True,
                                     node_order="frontier_last", **base))
    e0.run(5)
    e1.run(5)
    r0, _ = e0.macroscopics()
    r1, _ = e1.macroscopics()
    d0 = untile(e0.tiling, np.asarray(r0), fill=0.0)
    d1 = untile(e1.tiling, np.asarray(r1), fill=0.0)
    assert float(np.abs(d0 - d1).max()) < 5e-6


@pytest.mark.parametrize("periodic", [(False,) * 3, (True, True, True)])
def test_split_propagation_only_matches_at_fluid_slots(periodic):
    """propagation_only: split zeroes solid slots (documented difference);
    every NON-solid slot must match the monolithic path bitwise — the
    end-to-end pin that multi-step streaming alone never diverges."""
    g = duct_wrap(_spheres(), wall=2)
    base = dict(dtype="float32", kernel_mode="propagation_only",
                layout_scheme="xyz", periodic=periodic)
    e0 = SparseTiledLBM(g, LBMConfig(**base))
    e1 = SparseTiledLBM(g, LBMConfig(split_stream=True, **base))
    e0.run(3)
    e1.run(3)
    fluid = ~np.asarray(e0.backend._solid)
    f0 = np.asarray(e0.backend.canonical(e0.f))
    f1 = np.asarray(e1.backend.canonical(e1.f))
    assert np.array_equal(f0[:, fluid], f1[:, fluid])


def test_split_requires_gather_backend():
    with pytest.raises(ValueError, match="gather"):
        SparseTiledLBM(_spheres(), LBMConfig(backend="fused",
                                             split_stream=True))


# ------------------------------------------------- fused x node_order
@pytest.mark.parametrize("node_order", NODE_ORDERS)
@pytest.mark.usefixtures("x64")
def test_fused_parity_under_node_orders(node_order):
    """Acceptance: the fused kernel keeps 1e-12 float64 parity with the
    monolithic gather backend under every within-tile node order."""
    g = _spheres()
    base = dict(collision=C.CollisionConfig(tau=0.7), dtype="float64",
                periodic=(True, True, True), u0=(0.01, 0.0, 0.02))
    ref = SparseTiledLBM(g, LBMConfig(backend="gather", **base))
    eng = SparseTiledLBM(g, LBMConfig(backend="fused",
                                      node_order=node_order, **base))
    ref.run(4)
    eng.run(4)
    r0, u0 = ref.macroscopics()
    r1, u1 = eng.macroscopics()
    d = np.abs(untile(ref.tiling, np.asarray(r0), 0.0)
               - untile(eng.tiling, np.asarray(r1), 0.0))
    du = np.abs(untile(ref.tiling, np.asarray(u0), 0.0)
                - untile(eng.tiling, np.asarray(u1), 0.0))
    assert float(d.max()) < 1e-12
    assert float(du.max()) < 1e-12


# ------------------------------------------- absent boundary type (fix)
def test_boundary_pass_tables_empty_returns_none():
    t = tile_geometry(np.ones((8, 8, 8), np.uint8), 4)
    # INLET declared, but the geometry holds only FLUID nodes
    out = boundary_pass_tables(t.node_types, build_neighbor_table(t),
                               ((INLET, BCS[0][1]),))
    assert out is None


@pytest.mark.usefixtures("x64")
def test_fused_skips_pass_for_absent_boundary_type():
    """A geometry whose declared boundary type matches no nodes must run
    (pass skipped), matching the gather backend."""
    g = _spheres()   # spheres pack: FLUID + SOLID only, no INLET nodes
    base = dict(collision=C.CollisionConfig(tau=0.7), dtype="float64",
                periodic=(True, True, True), boundaries=BCS[:1])
    e_g = SparseTiledLBM(g, LBMConfig(backend="gather", **base))
    e_f = SparseTiledLBM(g, LBMConfig(backend="fused", **base))
    assert "bc" not in e_f.backend.tables
    e_g.run(3)
    e_f.run(3)
    c_g = np.asarray(e_g.backend.canonical(e_g.f))
    c_f = np.asarray(e_f.backend.canonical(e_f.f))
    assert float(np.abs(c_g - c_f).max()) < 1e-12
