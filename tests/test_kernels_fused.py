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
    build_neighbor_table, kernel_node_types, nebb_stream_tiles,
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
    fp, types, nbrs = pack_engine_state(eng.tiling, eng.f, lat)
    out = stream_collide_tiles(fp, types, nbrs, lat, cfg.collision,
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
    fp, types, nbrs = pack_engine_state(
        eng.tiling, eng.f.astype(jnp.float64), lat)
    out = stream_collide_tiles(fp, types, nbrs, lat, cfg.collision,
                               interpret=True)
    assert out.dtype == jnp.float64


def test_fused_kernel_multi_step_and_mass():
    eng, cfg = _engine(seed=3, p_fluid=0.6)
    lat = d3q19()
    fp, types, nbrs = pack_engine_state(eng.tiling, eng.f, lat)
    m0 = float(jnp.sum(fp))
    for _ in range(5):
        fp = stream_collide_tiles(fp, types, nbrs, lat, cfg.collision,
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
    bt, rows, _, _ = boundary_pass_tables(
        tiling.node_types, build_neighbor_table(tiling), BCS)
    assert 0 < len(bt) < t and (not chunk or len(bt) % chunk)
    f = np.random.default_rng(7).random((lat.q, t, n))
    packed = np.zeros((t + 1, lat.q, n))
    packed[:t] = f.transpose(1, 0, 2)
    blk = nebb_stream_tiles(
        jnp.asarray(packed), jnp.asarray(kernel_node_types(tiling.node_types)),
        jnp.asarray(bt), jnp.asarray(rows), lat, node_order=node_order,
        interpret=True)
    want = f.reshape(-1)[gather[:, bt, :]].transpose(1, 0, 2)     # (B, Q, n)
    np.testing.assert_array_equal(np.asarray(blk), want)
