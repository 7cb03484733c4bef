"""The plain reference that decides ``correct``, and the seeded initial flow.

A dense-box D3Q19 LBM written from the paper's equations (arXiv:1611.02445
§2): pull streaming with half-way bounce-back off solid nodes, the
non-equilibrium bounce-back (NEBB) velocity and pressure faces as the solver
documents them (unknown populations rebuilt as
``f_i = f_opp(i) + 6 w_i rho (e_i . u)``, transverse corrections omitted),
and the LBGK collision of the incompressible model.  It shares no tiling,
table or kernel with the program: it runs on the geometry's bounding box
grown by solid layers, advanced in blocks of x planes so that two copies of
f and one block's temporaries are all it holds on the device.

Every contraction is written as sums of products with the lattice's
integer velocities, so no matrix unit rounds float32 operands.  ``dtype``
sets the precision of storage and arithmetic; the control runs it one step
below the configuration's (bfloat16 for float32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.geometry import SOLID

D3Q19_E = ((0, 0, 0),
           (1, 0, 0), (0, 1, 0), (-1, 0, 0), (0, -1, 0), (0, 0, 1), (0, 0, -1),
           (1, 1, 0), (-1, 1, 0), (-1, -1, 0), (1, -1, 0),
           (1, 0, 1), (0, 1, 1), (-1, 0, 1), (0, -1, 1),
           (1, 0, -1), (0, 1, -1), (-1, 0, -1), (0, -1, -1))
D3Q19_W = (1.0 / 3.0,) + (1.0 / 18.0,) * 6 + (1.0 / 36.0,) * 12
LATTICES = {"D3Q19": (D3Q19_E, D3Q19_W)}

BLOCK_X = 8          # x planes per block of the reference step


def lattice(name: str):
    """(e, w, opp) of a lattice as Python tuples."""
    e, w = LATTICES[name]
    opp = tuple(e.index(tuple(-c for c in v)) for v in e)
    return e, w, opp


def _dot(v, comps):
    """sum_k v[k] * comps[k] over the non-zero integer entries of v."""
    terms = [c if k == 1 else -c if k == -1 else k * c
             for k, c in zip(v, comps) if k != 0]
    return functools.reduce(lambda a, b: a + b, terms) if terms else None


def equilibrium(rho, u, e, w):
    """Incompressible equilibrium, Eqn (4): w (rho + 3 eu + 4.5 eu^2 - 1.5 u^2)."""
    usq = u[0] * u[0] + u[1] * u[1] + u[2] * u[2]
    out = []
    for v, wq in zip(e, w):
        eu = _dot(v, u)
        poly = -1.5 * usq if eu is None else 3.0 * eu + 4.5 * eu * eu - 1.5 * usq
        out.append(wq * (rho + poly))
    return out


# ---------------------------------------------------------------- initial flow
def draw_initial(seed: int, spec: dict, shape) -> dict:
    """Mode parameters of the seeded perturbation of rho and u (host, tiny).

    Each of rho, ux, uy, uz is ``amplitude / modes * sum_m a_m sin(2 pi k_m.x
    / L + phi_m)`` with integer wavenumbers ``1..max_wavenumber`` per axis
    over the box ``shape``; the same seed gives the same flow."""
    rng = np.random.default_rng(seed)
    m, kmax = int(spec["modes"]), int(spec["max_wavenumber"])
    k = rng.integers(1, kmax + 1, size=(4, m, 3))
    phase = rng.uniform(0.0, 2.0 * np.pi, size=(4, m))
    a = rng.uniform(-1.0, 1.0, size=(4, m))
    amp = np.array([spec["rho_amplitude"]] + [spec["u_amplitude"]] * 3) / m
    return {"k": (2.0 * np.pi * k / np.asarray(shape, float)).astype(np.float32),
            "phase": phase.astype(np.float32),
            "amp": (a * amp[:, None]).astype(np.float32)}


@functools.partial(jax.jit, static_argnames=("lat", "dtype"))
def initial_f(params, x, y, z, solid, *, lat: str, dtype: str):
    """Equilibrium populations (Q, *shape) of the seeded flow at global
    coordinates ``x, y, z``; zero on solid nodes."""
    e, w, _ = lattice(lat)
    k, phase, amp = params["k"], params["phase"], params["amp"]
    x, y, z = (c.astype(jnp.float32) for c in (x, y, z))
    fields = []
    for i in range(4):
        v = 0.0
        for j in range(k.shape[1]):
            arg = k[i, j, 0] * x + k[i, j, 1] * y + k[i, j, 2] * z + phase[i, j]
            v = v + amp[i, j] * jnp.sin(arg)
        fields.append(v)
    dt = jnp.dtype(dtype)
    rho = (1.0 + fields[0]).astype(dt)
    u = [c.astype(dt) for c in fields[1:]]
    return jnp.stack([jnp.where(solid, 0.0, f).astype(dt)
                      for f in equilibrium(rho, u, e, w)])


# ---------------------------------------------------------------- the step
def _nebb(fin, mask, bc, e, w, opp):
    """Rebuild the unknown populations of one open face (post-streaming)."""
    n = bc["normal"]
    en = [sum(a * b for a, b in zip(v, n)) for v in e]
    par = functools.reduce(lambda a, b: a + b,
                           [fin[q] for q in range(len(e)) if en[q] == 0])
    out = functools.reduce(lambda a, b: a + b,
                           [fin[q] for q in range(len(e)) if en[q] < 0])
    if bc["kind"] == "velocity":
        vel = bc["velocity"]
        un = sum(a * b for a, b in zip(vel, n))
        rho = (par + 2.0 * out) / (1.0 - un)
        eu = [sum(a * b for a, b in zip(v, vel)) for v in e]
        rebuilt = {q: fin[opp[q]] + 6.0 * w[q] * eu[q] * rho
                   for q in range(len(e)) if en[q] > 0}
    elif bc["kind"] == "pressure":
        rho = bc["rho"]
        un = 1.0 - (par + 2.0 * out) / rho       # velocity along n only
        rebuilt = {q: fin[opp[q]] + (6.0 * w[q] * rho * en[q]) * un
                   for q in range(len(e)) if en[q] > 0}
    else:
        raise ValueError(f"unknown boundary kind {bc['kind']!r}")
    return [jnp.where(mask, rebuilt[q], fin[q]) if q in rebuilt else fin[q]
            for q in range(len(e))]


def _block_update(fs, ts, *, e, w, opp, tau, boundaries):
    """New f of the interior planes of one x block (halo of one plane)."""
    nx, ny, nz = (s - 2 for s in fs.shape[1:])

    def sl(a, d):
        return a[..., 1 - d[0]:1 - d[0] + nx, 1 - d[1]:1 - d[1] + ny,
                 1 - d[2]:1 - d[2] + nz]

    solid = ts == SOLID
    centre = (0, 0, 0)
    fin = [jnp.where(sl(solid, v), sl(fs[opp[q]], centre), sl(fs[q], v))
           for q, v in enumerate(e)]
    types = sl(ts, centre)
    for bc in boundaries:
        fin = _nebb(fin, types == bc["node_type"], bc, e, w, opp)
    rho = functools.reduce(lambda a, b: a + b, fin)
    u = [_dot([v[k] for v in e], fin) for k in range(3)]
    feq = equilibrium(rho, u, e, w)
    dead = sl(solid, centre)
    return jnp.stack([jnp.where(dead, 0.0, f + (fe - f) / tau)
                      for f, fe in zip(fin, feq)])


def make_step(config: dict):
    """Jitted ``step(f, types) -> f``: one LBM step of the padded box, whose
    outermost planes are solid.  The last block of x planes is clamped to
    end at the box's last inner plane; planes it repeats are recomputed from
    the same old state, so they are rewritten with the same values."""
    e, w, opp = lattice(config["lattice"])
    col = config["collision"]
    if (col["model"], col["fluid"]) != ("lbgk", "incompressible"):
        raise NotImplementedError(f"reference collision {col}")
    update = functools.partial(
        _block_update, e=e, w=w, opp=opp, tau=float(col["tau"]),
        boundaries=tuple(config["boundaries"]))

    def step(f, types):
        inner = f.shape[1] - 2
        assert inner >= BLOCK_X, "box thinner than one block"

        def body(b, fn):
            x0 = jnp.minimum(b * BLOCK_X, inner - BLOCK_X)
            fs = jax.lax.dynamic_slice_in_dim(f, x0, BLOCK_X + 2, axis=1)
            ts = jax.lax.dynamic_slice_in_dim(types, x0, BLOCK_X + 2, axis=0)
            return jax.lax.dynamic_update_slice(
                fn, update(fs, ts), (0, x0 + 1, 1, 1))

        return jax.lax.fori_loop(0, -(-inner // BLOCK_X), body,
                                 jnp.zeros_like(f))

    return jax.jit(step)


def padded_box(geometry: np.ndarray, align: int):
    """The geometry's non-solid bounding box grown by at least one solid
    layer on every side, to whole multiples of ``align`` in global
    coordinates: ``(types, origin)`` where ``origin`` (a multiple of
    ``align``, possibly negative) is the global coordinate of
    ``types[0, 0, 0]``.  Nodes outside the geometry are solid."""
    lo, hi = [], []
    for ax in range(3):
        other = tuple(a for a in range(3) if a != ax)
        idx = np.nonzero((geometry != SOLID).any(axis=other))[0]
        lo.append((int(idx.min()) - 1) // align * align)
        hi.append(-(-(int(idx.max()) + 2) // align) * align)
    types = np.full([h - l for l, h in zip(lo, hi)], SOLID, np.uint8)
    src = tuple(slice(max(l, 0), min(h, n))
                for l, h, n in zip(lo, hi, geometry.shape))
    dst = tuple(slice(s.start - l, s.stop - l) for s, l in zip(src, lo))
    types[dst] = geometry[src]
    return types, tuple(lo)


def run(geometry: np.ndarray, config: dict, params: dict, steps: int,
        dtype: str):
    """Advance the seeded flow ``steps`` steps; returns ``(f, origin)`` with
    f (Q, X, Y, Z) on the device over :func:`padded_box`."""
    types_np, origin = padded_box(geometry, config["tile_edge"])
    types = jnp.asarray(types_np)
    grid = [origin[ax] + jnp.arange(types_np.shape[ax], dtype=jnp.int32)
            .reshape([-1 if a == ax else 1 for a in range(3)])
            for ax in range(3)]
    f = initial_f(params, *grid, types == SOLID, lat=config["lattice"],
                  dtype=dtype)
    step = make_step(config)
    for _ in range(steps):
        f = step(f, types)
    return f, origin


# ---------------------------------------------------------------- judging
@functools.partial(jax.jit, static_argnames=("a",))
def _max_abs_tiles(f_ref, slab_rows, slab_tiles, answer, fluid, *, a: int):
    """One slab of ``a`` x planes at a time: its blocks as rows, then the
    rows of the answer's tiles that lie in it (``-1``: none)."""
    q, nx, ny, nz = f_ref.shape

    def body(i, acc):
        slab = jax.lax.dynamic_slice_in_dim(f_ref, i * a, a, axis=1)
        blocks = (slab.reshape(q, a, ny // a, a, nz // a, a)
                  .transpose(2, 4, 0, 5, 3, 1).reshape(-1, q * a ** 3))
        tiles = jnp.maximum(slab_tiles[i], 0)
        ref = jnp.take(blocks, slab_rows[i], axis=0).reshape(-1, q, a ** 3)
        got = jnp.take(answer, tiles, axis=0).reshape(ref.shape)
        judged = (jnp.take(fluid, tiles, axis=0)
                  & (slab_tiles[i] >= 0)[:, None])[:, None, :]
        d = jnp.abs(ref.astype(jnp.float32) - got.astype(jnp.float32))
        return (jnp.maximum(acc[0], jnp.max(jnp.where(judged, d, 0.0))),
                acc[1] & jnp.all(jnp.isfinite(got)))

    return jax.lax.fori_loop(0, nx // a, body,
                             (jnp.float32(0.0), jnp.bool_(True)))


def max_abs_diff(f_ref, origin, corners: np.ndarray, answer, fluid,
                 a: int) -> float:
    """max |f_ref - answer| over the fluid nodes of ``a``-edged blocks.

    ``corners`` (T, 3): global coordinate of each block's low corner (a
    multiple of ``a``); ``answer`` (T, Q * a^3): the populations to judge,
    each block's nodes x fastest, then y, then z; ``fluid`` (T, a^3) bool.
    NaN when the answer holds a non-finite value."""
    _, nx, ny, nz = f_ref.shape
    c = (corners - np.asarray(origin)) // a
    order = np.argsort(c[:, 0], kind="stable")
    counts = np.bincount(c[:, 0], minlength=nx // a)
    first = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(len(order)) - np.repeat(first, counts)
    slab_tiles = np.full((nx // a, max(1, counts.max())), -1, np.int32)
    slab_rows = np.zeros_like(slab_tiles)
    slab_tiles[c[order, 0], pos] = order
    slab_rows[c[order, 0], pos] = c[order, 1] * (nz // a) + c[order, 2]
    worst, finite = _max_abs_tiles(f_ref, jnp.asarray(slab_rows),
                                   jnp.asarray(slab_tiles), answer,
                                   jnp.asarray(fluid), a=a)
    return float(worst) if bool(finite) else float("nan")

