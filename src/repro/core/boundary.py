"""Boundary conditions (paper §2.2).

* walls: half-way bounce-back (applied inside streaming — see streaming.py)
* inlet: Zou-He-type velocity boundary (non-equilibrium bounce-back, NEBB)
* outlet: constant-pressure boundary

The NEBB reconstruction used here is the standard simplification of Zou-He
for arbitrary axis-aligned faces: after streaming, the incoming unknown
populations are rebuilt as

    f_i = f_opp(i) + 2 w_i rho (e_i . u) / cs^2        (velocity BC)

with rho from the known populations, and for the pressure BC the same with
rho := rho_bc and the normal velocity solved from mass conservation.  It
conserves mass exactly in the face-normal direction; transverse Zou-He
corrections are omitted (noted in DESIGN.md).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from . import collision as col
from .lattice import Lattice


@dataclasses.dataclass(frozen=True)
class BoundarySpec:
    """An axis-aligned open boundary.

    normal: unit int vector pointing INTO the fluid, e.g. (0, 0, 1) for an
    inlet at the low-z face.
    """

    kind: str                       # 'velocity' | 'pressure'
    normal: tuple[int, int, int]
    velocity: tuple[float, float, float] = (0.0, 0.0, 0.0)
    rho: float = 1.0


def _direction_sets(lat: Lattice, normal):
    n = np.asarray(normal)
    edotn = lat.e @ n
    unknown = np.nonzero(edotn > 0)[0]   # incoming (to reconstruct)
    outgoing = np.nonzero(edotn < 0)[0]
    parallel = np.nonzero(edotn == 0)[0]
    return unknown, outgoing, parallel


def apply_open_boundary(
    f: jnp.ndarray,
    mask: jnp.ndarray,
    spec: BoundarySpec,
    lat: Lattice,
):
    """Rebuild unknown populations on nodes selected by ``mask``.

    f: (Q, ...), mask: (...) bool.  Returns updated f.
    """
    dtype = f.dtype
    unknown, outgoing, parallel = _direction_sets(lat, spec.normal)
    n = jnp.asarray(np.asarray(spec.normal, np.float64), dtype=dtype)

    # sums of static rows in a fixed order, not f[index_array]: XLA
    # compiles the latter as a gather that it splits into more pieces the
    # larger the domain, and a reduce may be reassociated differently from
    # one program to the next (the split and ensemble steps must stay
    # bitwise equal to the monolithic one)
    f_par = sum(f[int(i)] for i in parallel)
    f_out = sum(f[int(i)] for i in outgoing)

    if spec.kind == "velocity":
        u = jnp.asarray(np.asarray(spec.velocity, np.float64), dtype=dtype)
        un = jnp.dot(u, n, precision=col.HIGHEST)
        rho = (f_par + 2.0 * f_out) / (1.0 - un)
        u_full = jnp.broadcast_to(
            u.reshape((3,) + (1,) * mask.ndim), (3,) + mask.shape
        )
        rho_full = rho
    elif spec.kind == "pressure":
        rho_bc = jnp.asarray(spec.rho, dtype=dtype)
        # mass conservation normal to the face: rho (1 - u.n) = f_par + 2 f_out
        # => u.n = 1 - (f_par + 2 f_out) / rho  (n points INTO the fluid, so
        # outflow through this face has u.n < 0).
        un = 1.0 - (f_par + 2.0 * f_out) / rho_bc
        # velocity purely normal (standard constant-pressure outlet)
        u_full = un[None] * jnp.broadcast_to(
            n.reshape((3,) + (1,) * mask.ndim), (3,) + mask.shape
        )
        rho_full = rho_bc
    else:
        raise ValueError(spec.kind)

    # NEBB reconstruction for unknown directions
    w = jnp.asarray(lat.w, dtype=dtype)
    e = jnp.asarray(lat.e.astype(np.float64), dtype=dtype)
    new_f = f
    for i in unknown:
        i = int(i)
        opp = int(lat.opp[i])
        eu = jnp.tensordot(e[i], u_full, axes=1, precision=col.HIGHEST)
        rebuilt = f[opp] + 2.0 * w[i] * rho_full * eu * 3.0
        new_f = new_f.at[i].set(jnp.where(mask, rebuilt, f[i]))
    return new_f
