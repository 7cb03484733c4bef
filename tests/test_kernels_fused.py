"""Fused stream+collide Pallas kernel (the paper's Algorithm 2, one kernel
per tile with scalar-prefetched tileMap) vs the SparseTiledLBM engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import collision as C
from repro.core.engine import LBMConfig, SparseTiledLBM
from repro.core.lattice import d3q19
from repro.kernels.stream_collide import (
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
