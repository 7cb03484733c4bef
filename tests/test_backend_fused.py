"""backend='fused' (persistent packed state + Pallas stream+collide kernel)
vs backend='gather' — float64 parity on the benchmark geometry families and
a jaxpr-level guarantee that the fused hot loop has no layout shuffles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import collision as C
from repro.core.boundary import BoundarySpec
from repro.core.engine import LBMConfig, SparseTiledLBM
from repro.core.tiling import INLET, OUTLET
from repro.data.geometry import duct_wrap, random_spheres


pytestmark = pytest.mark.usefixtures("x64")


TOL = 1e-12

BCS = ((INLET, BoundarySpec("velocity", (0, 0, 1), velocity=(0, 0, 0.03))),
       (OUTLET, BoundarySpec("pressure", (0, 0, -1), rho=1.0)))


def _spheres():
    return random_spheres(box=16, porosity=0.6, diameter=8, seed=1)


def _pair(g, steps=8, **kw):
    base = dict(dtype="float64", **kw)
    e_g = SparseTiledLBM(g, LBMConfig(backend="gather", **base))
    e_f = SparseTiledLBM(g, LBMConfig(backend="fused", **base))
    e_g.run(steps)
    e_f.run(steps)
    return e_g, e_f


def _assert_parity(e_g, e_f):
    c_g = e_g.backend.canonical(e_g.f)
    c_f = e_f.backend.canonical(e_f.f)
    assert float(jnp.max(jnp.abs(c_g - c_f))) < TOL
    r_g, u_g = e_g.macroscopics()
    r_f, u_f = e_f.macroscopics()
    assert float(jnp.max(jnp.abs(r_g - r_f))) < TOL
    assert float(jnp.max(jnp.abs(u_g - u_f))) < TOL


@pytest.mark.parametrize("model,fluid", [
    ("lbgk", "incompressible"),
    ("lbgk", "quasi_compressible"),
    ("lbmrt", "incompressible"),
])
def test_fused_matches_gather_spheres_periodic(model, fluid):
    """Random spheres, fully periodic, all collision/fluid models."""
    e_g, e_f = _pair(
        _spheres(), steps=6,
        collision=C.CollisionConfig(model=model, fluid=fluid, tau=0.7),
        periodic=(True, True, True), u0=(0.01, 0.0, 0.02))
    _assert_parity(e_g, e_f)


def test_fused_matches_gather_duct_wrap_open_boundaries():
    """duct_wrap: porous block in a solid duct, NEBB inlet/outlet."""
    g = duct_wrap(_spheres(), wall=4)        # (24, 24, 16): multiples of a
    e_g, e_f = _pair(
        g, steps=8, collision=C.CollisionConfig(tau=0.8), boundaries=BCS)
    _assert_parity(e_g, e_f)
    assert "bc" in e_f.backend.tables        # boundary pass actually active


def test_fused_matches_gather_cavity_lid():
    """Dense cavity with a moving-lid velocity BC on the -z normal."""
    from repro.data.geometry import LID, cavity3d

    bcs = ((LID, BoundarySpec("velocity", (0, 0, -1),
                              velocity=(0.05, 0.0, 0.0))),)
    e_g, e_f = _pair(cavity3d(12), steps=8,
                     collision=C.CollisionConfig(tau=0.6), boundaries=bcs)
    _assert_parity(e_g, e_f)


def test_fused_matches_gather_periodic_z_only():
    e_g, e_f = _pair(
        _spheres(), steps=6, collision=C.CollisionConfig(tau=0.7),
        periodic=(False, False, True), u0=(0.0, 0.0, 0.02))
    _assert_parity(e_g, e_f)


@pytest.mark.parametrize("mode", ["propagation_only", "rw_only"])
def test_fused_kernel_mode_variants_match(mode):
    e_g, e_f = _pair(
        _spheres(), steps=4, kernel_mode=mode,
        periodic=(True, True, True), u0=(0.01, 0.0, 0.02))
    c_g = e_g.backend.canonical(e_g.f)
    c_f = e_f.backend.canonical(e_f.f)
    assert float(jnp.max(jnp.abs(c_g - c_f))) == 0.0


def test_fused_with_force_matches():
    e_g, e_f = _pair(
        _spheres(), steps=5, collision=C.CollisionConfig(tau=0.7),
        periodic=(True, True, True), force=(1e-5, 0.0, 0.0))
    _assert_parity(e_g, e_f)


# --------------------------------------------------------------- guard rails
def test_fused_requires_xyz_layout():
    with pytest.raises(ValueError, match="xyz"):
        SparseTiledLBM(_spheres(),
                       LBMConfig(backend="fused", layout_scheme="paper"))


def test_fused_periodic_requires_tile_aligned_extent():
    g = np.ones((18, 16, 16), np.uint8)      # 18 % 4 != 0
    with pytest.raises(ValueError, match="periodic"):
        SparseTiledLBM(g, LBMConfig(backend="fused",
                                    periodic=(True, False, False)))


# ------------------------------------------------------------ jaxpr hygiene
def _collect_primitives(jaxpr, names, skip=("pallas_call",)):
    """All primitive names in ``jaxpr``, recursing through call/control-flow
    sub-jaxprs but NOT into skipped primitives (the kernel body gathers from
    VMEM by design — only the XLA-level hot loop must be shuffle-free)."""
    def _sub(v):
        if hasattr(v, "jaxpr"):              # ClosedJaxpr
            yield v.jaxpr
        elif hasattr(v, "eqns"):             # Jaxpr
            yield v
        elif isinstance(v, (list, tuple)):
            for x in v:
                yield from _sub(x)

    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name in skip:
            continue
        for v in eqn.params.values():
            for sub in _sub(v):
                _collect_primitives(sub, names, skip)
    return names


def _hot_loop_primitives(eng, steps=2):
    closed = jax.make_jaxpr(
        lambda f, tab: jax.lax.fori_loop(
            0, steps, lambda i, x: eng.backend.step(x, tab), f)
    )(eng.f, eng.backend.tables)
    return _collect_primitives(closed.jaxpr, [])


SHUFFLES = {"gather", "scatter", "transpose"}


def test_fused_run_hot_loop_has_no_layout_shuffles():
    """The acceptance criterion: no pack/unpack/gather inside the jitted
    fused run() loop (no boundaries, no periodic special cases)."""
    eng = SparseTiledLBM(
        _spheres(),
        LBMConfig(backend="fused", dtype="float64",
                  collision=C.CollisionConfig(tau=0.7)))
    names = _hot_loop_primitives(eng)
    assert "pallas_call" in names            # the kernel is really in there
    assert not SHUFFLES & set(names), sorted(SHUFFLES & set(names))


def test_primitive_walker_sees_gather_backend_shuffles():
    """Sanity for the detector: the gather backend's loop DOES gather."""
    eng = SparseTiledLBM(
        _spheres(),
        LBMConfig(backend="gather", dtype="float64",
                  collision=C.CollisionConfig(tau=0.7)))
    names = _hot_loop_primitives(eng)
    assert "gather" in names


def test_fused_boundary_pass_only_adds_tile_local_work():
    """With open boundaries the fused loop adds work on the boundary-tile
    subset only: the full-state (T, Q, n) array is never transposed (that
    would be a pack/unpack round-trip), and nothing is gathered outside
    the Pallas calls — the boundary tiles are re-streamed by the kernel's
    own pull (a second pallas_call), then scattered back."""
    g = duct_wrap(_spheres(), wall=4)
    eng = SparseTiledLBM(
        g, LBMConfig(backend="fused", dtype="float64", boundaries=BCS,
                     collision=C.CollisionConfig(tau=0.8)))
    b = int(eng.backend.tables["bc"]["tiles"].shape[0])
    t = eng.tiling.num_tiles
    assert b < t                             # pass is genuinely a subset
    closed = jax.make_jaxpr(
        lambda f, tab: jax.lax.fori_loop(
            0, 2, lambda i, x: eng.backend.step(x, tab), f)
    )(eng.f, eng.backend.tables)

    def _check(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                continue
            assert eqn.primitive.name != "gather", eqn
            if eqn.primitive.name == "transpose":
                # only the small (Q, B, n) boundary block may be transposed
                assert eqn.invars[0].aval.size <= eng.lat.q * b * (
                    eng.tiling.nodes_per_tile), eqn
            for v in eqn.params.values():
                for sub in ([v.jaxpr] if hasattr(v, "jaxpr")
                            else [v] if hasattr(v, "eqns") else []):
                    _check(sub)

    _check(closed.jaxpr)
    assert _collect_primitives(closed.jaxpr, []).count("pallas_call") == 2
