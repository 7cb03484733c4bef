"""Pallas kernel validation: shape/dtype/model sweeps vs the pure-jnp
oracle (interpret=True on CPU; identical code path compiles on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import collision as C
from repro.core.lattice import d3q19
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.collide import collide_pallas


def _random_state(key, q, g, lanes=128, dtype=jnp.float32, solid_frac=0.2):
    k1, k2 = jax.random.split(key)
    f = 0.05 + 0.01 * jax.random.normal(k1, (q, g, lanes), dtype)
    solid = jax.random.uniform(k2, (g, lanes)) < solid_frac
    f = jnp.where(solid[None], 0.0, f)
    return f, solid


@pytest.mark.parametrize("model", ["lbgk", "lbmrt"])
@pytest.mark.parametrize("fluid", ["incompressible", "quasi_compressible"])
def test_collide_kernel_all_variants(model, fluid):
    lat = d3q19()
    cfg = C.CollisionConfig(model=model, fluid=fluid, tau=0.62)
    f, solid = _random_state(jax.random.PRNGKey(0), lat.q, 16)
    out_k = collide_pallas(f, solid.astype(jnp.uint8), lat, cfg,
                           block_rows=8, interpret=True)
    out_r = kref.collide_ref(f, solid, lat, cfg)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("g,block_rows", [(8, 8), (16, 4), (32, 16), (24, 8)])
def test_collide_kernel_shape_sweep(g, block_rows):
    lat = d3q19()
    cfg = C.CollisionConfig(tau=0.7)
    f, solid = _random_state(jax.random.PRNGKey(g), lat.q, g)
    out_k = collide_pallas(f, solid.astype(jnp.uint8), lat, cfg,
                           block_rows=block_rows, interpret=True)
    out_r = kref.collide_ref(f, solid, lat, cfg)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_collide_kernel_dtype_sweep(dtype):
    lat = d3q19()
    cfg = C.CollisionConfig(tau=0.8)
    f, solid = _random_state(jax.random.PRNGKey(7), lat.q, 8, dtype=dtype)
    out_k = collide_pallas(f, solid.astype(jnp.uint8), lat, cfg,
                           interpret=True)
    out_r = kref.collide_ref(f, solid, lat, cfg)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)


def test_collide_kernel_with_force():
    lat = d3q19()
    cfg = C.CollisionConfig(tau=0.6)
    f, solid = _random_state(jax.random.PRNGKey(3), lat.q, 8)
    force = (1e-4, -2e-4, 5e-5)
    out_k = collide_pallas(f, solid.astype(jnp.uint8), lat, cfg, force=force,
                           interpret=True)
    out_r = kref.collide_ref(f, solid, lat, cfg, force=force)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=2e-6)


def test_collide_tiles_wrapper_pads_and_unpads():
    """(Q, T, n) wrapper round-trips through the packed (Q, G, 128) layout
    for tile counts that don't fill the last vector row."""
    lat = d3q19()
    cfg = C.CollisionConfig(tau=0.75)
    t, n = 5, 64                      # 5 tiles -> 2.5 rows -> padding
    key = jax.random.PRNGKey(1)
    f = 0.05 + 0.01 * jax.random.normal(key, (lat.q, t, n))
    solid = jnp.zeros((t, n), bool)
    out = kops.collide_tiles(f, solid, lat, cfg, interpret=True)
    ref, _, _ = C.collide(f, lat, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_engine_with_kernel_matches_engine_without():
    from repro.core.engine import LBMConfig, SparseTiledLBM
    from repro.data.geometry import cavity3d
    g = cavity3d(12)
    base = dict(layout_scheme="paper", dtype="float32",
                collision=C.CollisionConfig(tau=0.65))
    e1 = SparseTiledLBM(g, LBMConfig(use_kernel=False, **base))
    e2 = SparseTiledLBM(g, LBMConfig(use_kernel=True, kernel_interpret=True,
                                     **base))
    e1.step(5)
    e2.step(5)
    np.testing.assert_allclose(np.asarray(e1.f), np.asarray(e2.f),
                               rtol=3e-5, atol=3e-6)


@pytest.mark.parametrize("platform,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None), ("rocm", None)])
def test_default_interpret_policy(monkeypatch, platform, interpret):
    """Interpret on the CPU, compile on the TPU, refuse anything else
    (no silent interpreter where a device was expected)."""
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if interpret is None:
        with pytest.raises(RuntimeError, match=platform):
            kops.default_interpret()
        with pytest.raises(RuntimeError, match=platform):
            kops.resolve_interpret(None)
    else:
        assert kops.default_interpret() is interpret
        assert kops.resolve_interpret(None) is interpret
    assert kops.resolve_interpret(True) is True      # explicit flag wins
    assert kops.resolve_interpret(False) is False


def test_fused_kernel_chunked_grid_matches_single_call(monkeypatch):
    """The neighbour table is prefetched per chunk of tiles (scalar memory
    bound); a multi-chunk grid, its last chunk clamped onto the previous
    one, must give the single-call result exactly."""
    from repro.core.engine import LBMConfig, SparseTiledLBM
    from repro.data.geometry import random_spheres
    from repro.kernels import stream_collide as sc

    g = random_spheres(box=16, porosity=0.6, diameter=8, seed=1)
    cfg = LBMConfig(collision=C.CollisionConfig(tau=0.7), dtype="float32",
                    periodic=(True, True, True), u0=(0.01, 0.0, 0.02))
    eng = SparseTiledLBM(g, cfg)
    lat = d3q19()
    fp, mask, nbrs = sc.pack_engine_state(eng.tiling, eng.f, lat)
    t = eng.tiling.num_tiles
    assert t % 5, "pick a chunk that leaves a partial last chunk"
    whole = sc.stream_collide_tiles(fp, mask, nbrs, lat, cfg.collision,
                                    interpret=True)
    monkeypatch.setattr(sc, "TILES_PER_CALL", t // 5)
    chunked = sc.stream_collide_tiles(fp, mask, nbrs, lat, cfg.collision,
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(chunked), np.asarray(whole))
