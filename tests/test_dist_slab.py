"""Host-side slab-plan properties (no subprocess, no multi-device mesh)."""
import numpy as np
import pytest

from repro.core.lattice import get_lattice
from repro.core.streaming import build_stream_tables
from repro.core.tiling import SOLID, tile_geometry
from repro.data import geometry as geo
from repro.dist.lbm import balanced_layer_partition, make_slab_plan


def test_partition_balanced_uniform():
    """Equal-weight layers split into equal contiguous slabs."""
    parts = balanced_layer_partition(np.ones(16), 4)
    assert parts == [(0, 4), (4, 8), (8, 12), (12, 16)]
    assert balanced_layer_partition(np.ones(8), 8) == [
        (i, i + 1) for i in range(8)]


def test_partition_balanced_weighted():
    """Cuts track cumulative weight, every slab gets >= 1 layer."""
    w = np.array([100, 1, 1, 1, 1, 1, 1, 100], float)
    parts = balanced_layer_partition(w, 4)
    assert parts[0] == (0, 1)             # the heavy layer stands alone
    assert parts[-1][1] == 8
    assert all(zh > zl for zl, zh in parts)
    # contiguous cover
    assert all(parts[i][1] == parts[i + 1][0] for i in range(3))


@pytest.mark.parametrize("n_dev", [1, 2, 4])
def test_slab_plan_fluid_conservation(n_dev):
    """Owned fluid nodes over all slabs == global fluid nodes, and owned
    tile sets are disjoint by construction (distinct z layers)."""
    g = geo.duct(12, 12, 48, open_ends=True)
    plan = make_slab_plan(g, 4, n_dev)
    assert plan.n_fluid_own == tile_geometry(g, 4).n_fluid_nodes
    # balanced on the uniform duct: every slab owns the same layer count
    counts = [zh - zl for zl, zh in plan.layer_of_dev]
    assert max(counts) - min(counts) <= 1


def test_slab_plan_layers_cover_grid():
    g = geo.duct(12, 12, 48, open_ends=True)
    plan = make_slab_plan(g, 4, 3)
    assert plan.layer_of_dev[0][0] == 0
    assert plan.layer_of_dev[-1][1] == plan.tile_layers
    for d in range(plan.n_dev - 1):
        assert plan.layer_of_dev[d][1] == plan.layer_of_dev[d + 1][0]


def test_cross_slab_links_resolve_in_halo():
    """Every streaming link out of an owned tile resolves either inside the
    owned layers or into the halo tile layer — never out of the slab."""
    g = geo.duct(12, 12, 48, open_ends=True)
    plan = make_slab_plan(g, 4, 4)
    lat = get_lattice("D3Q19")
    n = plan.nodes_per_tile
    for d, lt in enumerate(plan.local_tilings):
        tabs = build_stream_tables(lt, lat, "paper")
        m = lt.num_tiles * n
        src_tile = (tabs.gather_idx.astype(np.int64) % m) // n  # (Q, T, n)
        lo, hi = plan.owned_layer_range_local(d)
        halo = set(plan.halo_layers_local(d))
        owned_tiles = np.nonzero(plan.own[d, :lt.num_tiles])[0]
        src_layers = lt.tile_coords[src_tile[:, owned_tiles], 2]
        ok = ((src_layers >= lo) & (src_layers < hi))
        for hl in halo:
            ok |= src_layers == hl
        assert ok.all(), f"device {d}: link escapes the slab+halo region"
        # and a cross-slab link actually exists for interior slabs
        if halo:
            outside = (src_layers < lo) | (src_layers >= hi)
            assert outside.any()


def test_slab_plan_own_excludes_halo_and_padding():
    g = geo.duct(12, 12, 48, open_ends=True)
    plan = make_slab_plan(g, 4, 3)
    for d, lt in enumerate(plan.local_tilings):
        lo, hi = plan.owned_layer_range_local(d)
        own_d = plan.own[d]
        assert not own_d[lt.num_tiles:].any()          # padding + dummy
        zc = lt.tile_coords[:, 2]
        np.testing.assert_array_equal(
            own_d[:lt.num_tiles], (zc >= lo) & (zc < hi))


def test_duct_wrap_closes_porous_block():
    g = geo.random_spheres(box=24, porosity=0.7, diameter=8, seed=1)
    w = geo.duct_wrap(g)
    assert w.shape == (26, 26, 24)
    # side walls are solid
    assert (w[0] == SOLID).all() and (w[-1] == SOLID).all()
    assert (w[:, 0] == SOLID).all() and (w[:, -1] == SOLID).all()
    # open faces: inlet/outlet exactly where the block had fluid
    from repro.core.tiling import FLUID, INLET, OUTLET
    np.testing.assert_array_equal(
        w[1:-1, 1:-1, 0] == INLET, g[:, :, 0] == FLUID)
    np.testing.assert_array_equal(
        w[1:-1, 1:-1, -1] == OUTLET, g[:, :, -1] == FLUID)


# --------------------------------------------------------------------------
# the sharded engine's fused set-up and its per-slab state API
# --------------------------------------------------------------------------
def _fused_sharded(slabs: int = 4, dryrun: bool = True):
    """A fused ``ShardedLBM`` of a duct-wrapped sphere pack (34x34x32, 8
    tile layers) with a velocity inlet and a pressure outlet, over a mesh
    that repeats the one CPU device ``slabs`` times (built as for a dry
    run: nothing is placed)."""
    import jax
    from jax.sharding import Mesh

    from repro.core import collision as C
    from repro.core.boundary import BoundarySpec
    from repro.core.engine import LBMConfig
    from repro.core.tiling import INLET, OUTLET
    from repro.dist.lbm import ShardedLBM

    g = geo.duct_wrap(geo.random_spheres(box=32, porosity=0.7, diameter=8,
                                         seed=0))
    bcs = ((INLET, BoundarySpec("velocity", (0, 0, 1),
                                velocity=(0, 0, 0.02))),
           (OUTLET, BoundarySpec("pressure", (0, 0, -1), rho=1.0)))
    cfg = LBMConfig(collision=C.CollisionConfig(tau=0.6), backend="fused",
                    boundaries=bcs)
    mesh = Mesh(np.array([jax.devices()[0]] * slabs), ("data",))
    return g, ShardedLBM(g, cfg, mesh, dryrun=dryrun)


# sha256 (first 16 hex digits) of each fused step table of _fused_sharded()
# over its bytes, shape and dtype: the tables the kernel, the NEBB pass and
# the halo exchange read, pinned bit for bit
FUSED_TABLE_DIGESTS = {
    "bcm": "89364d91d257ed77", "bcn": "774eca8c007ad457",
    "bcs": "65f8d79d376ed71d", "bct": "bfc275ebfa76ecbd",
    "nbrs": "fe496db75d025907", "own_nodes": "a691cf25005b0797",
    "rd": "f358e613aff5a6e2", "rdm": "718965407a54beef",
    "ru": "9a638aaa3e7eb18f", "rum": "0b8fb940f0071727",
    "sd": "6d44d9f2e833a0cc", "solid": "e3e1cd7c06d32910",
    "su": "84eba82789f43ac7", "mask": "e59e73dcbc35aad3",
}


def test_fused_sharded_builds_no_stream_tables(monkeypatch):
    import repro.dist.lbm as dist_lbm

    def refuse(*args, **kwargs):
        raise AssertionError("the fused path built stream tables")

    monkeypatch.setattr(dist_lbm, "build_stream_tables", refuse)
    _, eng = _fused_sharded()
    assert "gather" not in eng.table_shapes()
    assert eng.stream_fracs is None
    assert not any(k.startswith("lbm.stream.") for k in eng.model_metrics())


def test_fused_sharded_tables_keep_their_bits():
    import hashlib

    _, eng = _fused_sharded()
    got = {}
    for k, v in eng._tbl_np.items():
        v = np.ascontiguousarray(v)
        got[k] = hashlib.sha256(
            v.tobytes() + str((v.shape, v.dtype)).encode()).hexdigest()[:16]
    assert got == FUSED_TABLE_DIGESTS


def test_fused_sharded_mask_bounces_padding_and_dummy_rows():
    """Each slab's bounce mask: the rows past its tiles (padding to the
    fullest slab, then the dummy row) bounce every pull and are SOLID, and
    its tiles' rows are the mask of the slab's own tiling, halo included."""
    from repro.core.lattice import d3q19
    from repro.kernels.stream_collide import (build_bounce_mask,
                                              build_neighbor_table)

    _, eng = _fused_sharded()
    mask, plan = eng._tbl_np["mask"], eng.plan
    assert mask.shape == (plan.n_dev, plan.t_pad, 1, 64)
    all_bits = (2 << 19) - 1
    held = [lt.num_tiles for lt in plan.local_tilings]
    assert min(held) < plan.t_pad - 1          # some slab has padding rows
    for d, lt in enumerate(plan.local_tilings):
        assert (mask[d, held[d]:] == all_bits).all()
        own = build_bounce_mask(lt.node_types, build_neighbor_table(lt),
                                d3q19())
        np.testing.assert_array_equal(mask[d, :held[d]], own[:-1])


def test_owned_node_coords_cover_the_tiling_once():
    g, eng = _fused_sharded()
    coords = eng.owned_node_coords()
    assert [len(c) for c in coords] == list(eng.plan.own.sum(axis=1))
    whole = tile_geometry(g, 4).node_coords()
    key = lambda c: np.sort(np.ravel_multi_index(            # noqa: E731
        tuple(c.reshape(-1, 3).T), (36, 36, 32)))
    np.testing.assert_array_equal(key(np.concatenate(coords)), key(whole))


def test_load_then_read_owned_round_trips():
    _, eng = _fused_sharded(slabs=1, dryrun=False)
    rng = np.random.default_rng(3)
    state = [rng.random((19, len(c), 64), np.float32)
             for c in eng.owned_node_coords()]
    eng.load_state(state)
    back = eng.read_owned()
    assert len(back) == 1
    np.testing.assert_array_equal(np.asarray(back[0]), state[0])
    with pytest.raises(ValueError):
        eng.load_state([state[0][:, 1:]])
