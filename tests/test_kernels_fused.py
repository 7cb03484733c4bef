"""Fused stream+collide Pallas kernel (the paper's Algorithm 2, one kernel
per tile with scalar-prefetched tileMap) vs the SparseTiledLBM engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import collision as C
from repro.core.backends import boundary_pass_tables
from repro.core.boundary import BoundarySpec
from repro.core.engine import LBMConfig, SparseTiledLBM
from repro.core.lattice import d3q19
from repro.core.streaming import build_stream_tables
from repro.core.tiling import INLET, OUTLET, tile_geometry
from repro.data.geometry import duct_wrap, random_spheres
from repro.kernels import stream_collide as sc
from repro.kernels.stream_collide import (
    build_bounce_mask, build_neighbor_table, nebb_stream_tiles,
    pack_engine_state, stream_collide_tiles, unpack_engine_state,
)


def _engine(seed=0, p_fluid=0.7, model="lbgk", fluid="incompressible"):
    rng = np.random.default_rng(seed)
    g = (rng.random((12, 12, 12)) < p_fluid).astype(np.uint8)
    g[4:8, 4:8, 4:8] = 1
    cfg = LBMConfig(
        collision=C.CollisionConfig(model=model, fluid=fluid, tau=0.7),
        layout_scheme="xyz", dtype="float32", u0=(0.01, 0.0, 0.02))
    return SparseTiledLBM(g, cfg), cfg


@pytest.mark.parametrize("model,fluid", [
    ("lbgk", "incompressible"), ("lbgk", "quasi_compressible"),
    ("lbmrt", "incompressible"),
])
def test_fused_kernel_matches_engine_step(model, fluid):
    eng, cfg = _engine(model=model, fluid=fluid)
    lat = d3q19()
    fp, mask, nbrs = pack_engine_state(eng.tiling, eng.f, lat)
    out = stream_collide_tiles(fp, mask, nbrs, lat, cfg.collision,
                               interpret=True)
    eng.step(1)
    err = float(jnp.max(jnp.abs(unpack_engine_state(out) - eng.f)))
    assert err < 5e-5, err


@pytest.mark.usefixtures("x64")
def test_fused_kernel_preserves_float64():
    """The kernel must compute in the storage dtype (it used to force
    float32, which silently capped the float64 parity tests)."""
    eng, cfg = _engine(seed=5, p_fluid=0.65)
    lat = d3q19()
    fp, mask, nbrs = pack_engine_state(
        eng.tiling, eng.f.astype(jnp.float64), lat)
    out = stream_collide_tiles(fp, mask, nbrs, lat, cfg.collision,
                               interpret=True)
    assert out.dtype == jnp.float64


def test_fused_kernel_multi_step_and_mass():
    eng, cfg = _engine(seed=3, p_fluid=0.6)
    lat = d3q19()
    fp, mask, nbrs = pack_engine_state(eng.tiling, eng.f, lat)
    m0 = float(jnp.sum(fp))
    for _ in range(5):
        fp = stream_collide_tiles(fp, mask, nbrs, lat, cfg.collision,
                                  interpret=True)
    eng.step(5)
    err = float(jnp.max(jnp.abs(unpack_engine_state(fp) - eng.f)))
    assert err < 2e-4, err
    # closed box (bounce-back everywhere): mass conserved through the kernel
    assert abs(float(jnp.sum(fp)) - m0) / m0 < 1e-4  # f32 sum noise


BCS = ((INLET, BoundarySpec("velocity", (0, 0, 1), velocity=(0, 0, 0.03))),
       (OUTLET, BoundarySpec("pressure", (0, 0, -1), rho=1.0)))


@pytest.mark.usefixtures("x64")
@pytest.mark.parametrize("node_order,tile_order,chunk", [
    ("canonical", "zmajor", None),
    ("sfc", "zmajor", None),
    ("canonical", "hilbert", None),
    ("frontier_last", "morton", 7),
])
def test_nebb_stream_equals_the_stream_tables_pull(node_order, tile_order,
                                                   chunk, monkeypatch):
    """The tile-list pull (``nebb_stream``) over the boundary tiles of the
    duct-wrapped sphere pack returns, bitwise at every node of every listed
    tile, the pull that the streaming gather table defines.  That table is
    the oracle here only; ``chunk`` shrinks ``TILES_PER_CALL`` so that the
    list runs in several calls with a clamped last one."""
    if chunk:
        monkeypatch.setattr(sc, "TILES_PER_CALL", chunk)
    lat = d3q19()
    g = duct_wrap(random_spheres(box=16, porosity=0.6, diameter=8, seed=1),
                  wall=4)
    tiling = tile_geometry(g, 4, order=tile_order, node_order=node_order)
    t, n = tiling.num_tiles, tiling.nodes_per_tile
    gather = build_stream_tables(tiling, lat, "xyz").gather_idx   # (Q, T, n)
    nbrs = build_neighbor_table(tiling)
    bt, rows, _, _ = boundary_pass_tables(tiling.node_types, nbrs, BCS)
    assert 0 < len(bt) < t and (not chunk or len(bt) % chunk)
    f = np.random.default_rng(7).random((lat.q, t, n))
    packed = np.zeros((t + 1, lat.q, n))
    packed[:t] = f.transpose(1, 0, 2)
    blk = nebb_stream_tiles(
        jnp.asarray(packed),
        jnp.asarray(build_bounce_mask(tiling.node_types, nbrs, lat,
                                      node_order=node_order)),
        jnp.asarray(bt), jnp.asarray(rows), lat, node_order=node_order,
        interpret=True)
    want = f.reshape(-1)[gather[:, bt, :]].transpose(1, 0, 2)     # (B, Q, n)
    np.testing.assert_array_equal(np.asarray(blk), want)


# --------------------------------------------------------------------------
# the bounce mask: one int32 word per node instead of 19 node-type blocks
# --------------------------------------------------------------------------
def _sparse_tiling(node_order, periodic=(False, False, False), seed=11):
    """A random sparse 16^3 geometry (empty tiles, out-of-grid faces) in
    ``node_order`` slots, and the kernel's neighbour table over it."""
    g = (np.random.default_rng(seed).random((16, 16, 16)) < 0.55)
    g = g.astype(np.uint8)
    g[:, :, 8:12] = 0                      # a solid slab: empty tiles
    tiling = tile_geometry(g, 4, node_order=node_order)
    return tiling, build_neighbor_table(tiling, periodic)


def _mask_by_definition(node_types, nbrs, lat, node_order):
    """Bit q: the source that ``perms`` / ``cases`` / ``nbrs`` name for the
    pull of direction q is SOLID (scratch row T included); bit Q: the node
    is SOLID.  Node by node, with no vectorised shortcut."""
    from repro.core.tiling import SOLID, neighbor_offset_index

    offsets, perms, cases = sc._pull_geometry(lat, 4, node_order)
    t, n = node_types.shape
    types = np.full((t + 1, n), SOLID, np.int64)
    types[:t] = node_types
    want = np.full((t + 1, 1, n), (1 << (lat.q + 1)) - 1, np.int64)
    for tile in range(t):
        for node in range(n):
            word = int(types[tile, node] == SOLID) << lat.q
            for q in range(lat.q):
                c = int(cases[q, node])
                src = (tile if c == 0 else
                       nbrs[tile, neighbor_offset_index(*offsets[c - 1])])
                word |= int(types[src, perms[q, node]] == SOLID) << q
            want[tile, 0, node] = word
    return want


@pytest.mark.parametrize("node_order,periodic", [
    ("canonical", (False, False, False)),
    ("sfc", (False, False, False)),
    ("frontier_last", (False, False, False)),
    ("canonical", (True, False, False)),
])
def test_bounce_mask_equals_its_definition(node_order, periodic):
    lat = d3q19()
    tiling, nbrs = _sparse_tiling(node_order, periodic)
    t = tiling.num_tiles
    assert (nbrs == t).any() and (nbrs < t).any()
    if periodic[0]:                     # the wrap links x's outer tiles
        assert (nbrs != build_neighbor_table(tiling)).any()
    mask = build_bounce_mask(tiling.node_types, nbrs, lat,
                             node_order=node_order)
    assert mask.shape == (t + 1, 1, 64) and mask.dtype == np.int32
    want = _mask_by_definition(tiling.node_types, nbrs, lat, node_order)
    np.testing.assert_array_equal(mask, want)
    assert (mask[t] == (2 << lat.q) - 1).all()        # scratch: all bounce


def test_bounce_mask_marks_solid_nodes_and_only_them():
    """Bit Q is the node's own solidity; in a channel periodic in y and
    z, a fluid node away from the x walls bounces in no direction, and
    one next to a wall bounces in each direction that leaves it."""
    lat = d3q19()
    g = np.ones((12, 12, 12), np.uint8)
    g[0], g[-1] = 0, 0
    tiling = tile_geometry(g, 4)
    nbrs = build_neighbor_table(tiling, (False, True, True))
    mask = build_bounce_mask(tiling.node_types, nbrs, lat)[:-1, 0]
    solid = (mask >> lat.q) & 1 == 1
    np.testing.assert_array_equal(solid, tiling.node_types == 0)
    coords = tiling.node_coords()                       # (T, n, 3)
    inner = (coords[..., 0] >= 2) & (coords[..., 0] <= 9)
    assert inner.any() and (mask[inner] == 0).all()
    from_wall = sum(1 << q for q in range(lat.q) if lat.e[q][0] == 1)
    np.testing.assert_array_equal(mask[coords[..., 0] == 1], from_wall)


# The parent design's pull, kept as the oracle of the mask kernel: every
# grid step DMAs the own tile's and each neighbour's f AND node-type
# blocks, gathers the types with ``perms`` and selects them with ``cases``
# as it does f, and bounces where the gathered type is SOLID.
def _type_gather_step(f, node_types, nbrs, lat, cfg, mode, node_order):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from repro.core.tiling import SOLID, neighbor_offset_index
    from repro.kernels.collide import _equilibrium_rows

    t1, q, n = f.shape
    offsets, perms, cases = sc._pull_geometry(lat, 4, node_order)
    opp = [int(o) for o in lat.opp]
    mrt = cfg.model == C.LBMRT and mode == "full"
    n_off = len(offsets)

    def kernel(nb_ref, own_f, own_t, perms_ref, cases_ref, *rest):
        out_ref, a_ref = rest[-1], (rest[-2] if mrt else None)
        nbr = rest[:2 * n_off]
        perms, cases = perms_ref[...], cases_ref[...]

        def pull(f_blk, t_row):
            t_q = jnp.broadcast_to(t_row, (q, n))
            return (jnp.take_along_axis(f_blk, perms, axis=1),
                    jnp.take_along_axis(t_q, perms, axis=1))

        t_own = own_t[0]
        src_f, src_t = pull(own_f[0], t_own)
        for c in range(n_off):
            hit = cases == (c + 1)
            nf, nt = pull(nbr[2 * c][0], nbr[2 * c + 1][0])
            src_f = jnp.where(hit, nf, src_f)
            src_t = jnp.where(hit, nt, src_t)
        for i in range(q):
            out_ref[0, i:i + 1, :] = own_f[0, opp[i]:opp[i] + 1, :]
        f_in = jnp.where(src_t == SOLID, out_ref[0], src_f)
        out_ref[0] = f_in
        if mode == "propagation_only":
            return
        solid_row = t_own == SOLID
        rows = [out_ref[0, i:i + 1, :] for i in range(q)]
        feq = _equilibrium_rows(rows, solid_row, lat, cfg, None)
        if not mrt:
            for i in range(q):
                f_out = rows[i] + (feq[i] - rows[i]) * (1.0 / cfg.tau)
                out_ref[0, i:i + 1, :] = jnp.where(solid_row, 0.0, f_out)
            return
        for i in range(q):
            out_ref[0, i:i + 1, :] = feq[i] - rows[i]
        f_out = f_in + jnp.dot(a_ref[...], out_ref[0],
                               preferred_element_type=f_in.dtype,
                               precision=jax.lax.Precision.HIGHEST)
        out_ref[0] = jnp.where(jnp.broadcast_to(t_own, (q, n)) == SOLID,
                               0.0, f_out)

    own = lambda i, nb: (i, 0, 0)                       # noqa: E731
    table = pl.BlockSpec((q, n), lambda i, nb: (0, 0))
    in_specs = [pl.BlockSpec((1, q, n), own), pl.BlockSpec((1, 1, n), own),
                table, table]
    operands = [f, node_types, jnp.asarray(perms),
                jnp.asarray(cases, jnp.int32)]
    for off in offsets:
        k = neighbor_offset_index(*off)
        nb_map = lambda i, nb, _k=k: (nb[i * 27 + _k], 0, 0)  # noqa: E731
        in_specs += [pl.BlockSpec((1, q, n), nb_map),
                     pl.BlockSpec((1, 1, n), nb_map)]
        operands += [f, node_types]
    if mrt:
        in_specs.append(pl.BlockSpec((q, q), lambda i, nb: (0, 0)))
        operands.append(jnp.asarray(C.collision_matrix_np(lat, cfg.tau),
                                    f.dtype))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(t1 - 1,), in_specs=in_specs,
            out_specs=pl.BlockSpec((1, q, n), own)),
        out_shape=jax.ShapeDtypeStruct(f.shape, f.dtype),
        interpret=True,
    )(jnp.asarray(nbrs).reshape(-1), *operands)
    return out.at[-1].set(0.0)


@pytest.mark.parametrize("model,mode,node_order,periodic", [
    ("lbgk", "full", "canonical", (False, False, False)),
    ("lbgk", "propagation_only", "sfc", (False, False, False)),
    ("lbmrt", "full", "frontier_last", (False, False, False)),
    ("lbgk", "full", "canonical", (True, False, False)),
])
def test_mask_kernel_is_bitwise_the_type_gather_pull(model, mode,
                                                     node_order, periodic):
    """The kernel over the bounce mask writes, bit for bit, what the
    type-gathering pull writes: the same selects on the same f values.
    Its NEBB tile-list pull equals that pull's rows for the listed tiles."""
    lat = d3q19()
    cfg = C.CollisionConfig(model=model, tau=0.7)
    tiling, nbrs = _sparse_tiling(node_order, periodic, seed=4)
    t = tiling.num_tiles
    f = np.zeros((t + 1, lat.q, 64), np.float32)
    f[:t] = np.random.default_rng(2).random((t, lat.q, 64)) * 0.1 + 0.01
    f = jnp.asarray(f)
    types = np.zeros((t + 1, 1, 64), np.int32)          # scratch: SOLID
    types[:t, 0] = tiling.node_types
    mask = jnp.asarray(build_bounce_mask(tiling.node_types, nbrs, lat,
                                         node_order=node_order))
    want = _type_gather_step(f, jnp.asarray(types), nbrs, lat, cfg, mode,
                             node_order)
    got = stream_collide_tiles(f, mask, jnp.asarray(nbrs), lat, cfg,
                               mode=mode, node_order=node_order,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    tiles = np.arange(1, t, 3, dtype=np.int32)
    pulled = _type_gather_step(f, jnp.asarray(types), nbrs, lat, cfg,
                               "propagation_only", node_order)
    blk = nebb_stream_tiles(f, mask, jnp.asarray(tiles),
                            jnp.asarray(nbrs[tiles]), lat,
                            node_order=node_order, interpret=True)
    np.testing.assert_array_equal(np.asarray(blk),
                                  np.asarray(pulled)[tiles])
