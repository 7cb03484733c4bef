"""Collision operators — paper Eqns (2)-(8).

Both collision models (LBGK, LBMRT) in both fluid models (incompressible,
quasi-compressible), matching the four kernel variants the paper benchmarks.

All functions take ``f`` with the direction axis FIRST: (Q, ...) — the
trailing dims are arbitrary (dense grids, tile slots, Pallas blocks), so the
same code backs the dense engine, the sparse engine, and the kernel oracle.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .lattice import Lattice, d3q19_mrt_collision_matrix

INCOMPRESSIBLE = "incompressible"
QUASI_COMPRESSIBLE = "quasi_compressible"

LBGK = "lbgk"
LBMRT = "lbmrt"


@dataclasses.dataclass(frozen=True)
class CollisionConfig:
    model: str = LBGK                 # 'lbgk' | 'lbmrt'
    fluid: str = INCOMPRESSIBLE       # 'incompressible' | 'quasi_compressible'
    tau: float = 0.6

    def __post_init__(self):
        assert self.model in (LBGK, LBMRT)
        assert self.fluid in (INCOMPRESSIBLE, QUASI_COMPRESSIBLE)
        assert self.tau > 0.5, "tau <= 0.5 is unstable (negative viscosity)"

    @property
    def viscosity(self) -> float:
        return (self.tau - 0.5) / 3.0


# Full-precision contractions: the TPU's default rounds float32 matmul
# operands to bfloat16, which costs the velocity moments three digits.
HIGHEST = jax.lax.Precision.HIGHEST


def _e_matrix(lat: Lattice, dtype) -> jnp.ndarray:
    return jnp.asarray(lat.e.astype(np.float64), dtype=dtype)  # (Q, 3)


def macroscopics(f: jnp.ndarray, lat: Lattice, fluid: str):
    """rho and u from f — Eqns (5) (quasi-compressible) / (6) (incompressible).

    f: (Q, ...) -> rho (...), u (3, ...)
    """
    e = _e_matrix(lat, f.dtype)
    rho = jnp.sum(f, axis=0)
    j = jnp.tensordot(e.T, f, axes=1, precision=HIGHEST)  # (3, ...)
    if fluid == QUASI_COMPRESSIBLE:
        u = j / rho
    else:
        u = j
    return rho, u


def equilibrium(rho: jnp.ndarray, u: jnp.ndarray, lat: Lattice, fluid: str):
    """Equilibrium distribution — Eqn (3) (quasi) / Eqn (4) (incompressible).

    rho: (...), u: (3, ...) -> feq (Q, ...)
    """
    dtype = u.dtype
    e = _e_matrix(lat, dtype)                      # (Q, 3)
    w = jnp.asarray(lat.w, dtype=dtype)            # (Q,)
    eu = jnp.tensordot(e, u, axes=1, precision=HIGHEST)   # (Q, ...)
    u2 = jnp.sum(u * u, axis=0)                    # (...)
    # cs^2 = 1/3: 1/cs^2 = 3, 1/(2 cs^4) = 4.5, 1/(2 cs^2) = 1.5
    poly = 3.0 * eu + 4.5 * eu * eu - 1.5 * u2     # (Q, ...)
    wq = w.reshape((lat.q,) + (1,) * (u.ndim - 1))
    if fluid == QUASI_COMPRESSIBLE:
        return wq * rho[None] * (1.0 + poly)
    return wq * (rho[None] + poly)


def collide(
    f: jnp.ndarray,
    lat: Lattice,
    cfg: CollisionConfig,
    force: jnp.ndarray | None = None,
):
    """One collision step (post-streaming f -> post-collision f).

    ``force`` is an optional (3,) body-force density; applied via the
    velocity-shift (Shan-Chen) scheme: u_eq = u + tau * F / rho.
    Returns (f_out, rho, u) — rho/u are the pre-forcing macroscopics.
    """
    rho, u = macroscopics(f, lat, cfg.fluid)
    u_eq = u
    if force is not None:
        fvec = jnp.asarray(force, dtype=f.dtype).reshape((3,) + (1,) * (u.ndim - 1))
        if cfg.fluid == QUASI_COMPRESSIBLE:
            u_eq = u + cfg.tau * fvec / rho[None]
        else:
            u_eq = u + cfg.tau * fvec
    feq = equilibrium(rho, u_eq, lat, cfg.fluid)
    if cfg.model == LBGK:
        f_out = f + (feq - f) / cfg.tau
    else:
        a = collision_matrix(lat, cfg.tau, dtype=f.dtype)
        f_out = f + jnp.tensordot(a, feq - f, axes=1, precision=HIGHEST)
    return f_out, rho, u


def collision_matrix_np(lat: Lattice, tau: float) -> np.ndarray:
    """A = M^-1 S M as a cached numpy constant."""
    key = (lat.name, float(tau))
    if key not in _A_CACHE:
        if lat.q != 19:
            raise NotImplementedError("MRT matrix defined for D3Q19 only")
        _A_CACHE[key] = d3q19_mrt_collision_matrix(tau)
    return _A_CACHE[key]


def collision_matrix(lat: Lattice, tau: float, dtype) -> jnp.ndarray:
    """A = M^-1 S M as a compile-time constant (numpy cached; safe in jit)."""
    return jnp.asarray(collision_matrix_np(lat, tau), dtype=dtype)


_A_CACHE: dict[tuple, np.ndarray] = {}


def model_flops_per_node(cfg: CollisionConfig, lat: Lattice) -> int:
    """Analytic FLOP count for one node's collision + macroscopics.

    A portable analogue of the paper's Table 2 (their numbers come from
    disassembled SASS; ours from counting the arithmetic in the formulas —
    reported side by side in benchmarks/flops_table2.py).
    """
    q, d = lat.q, 3
    nonzero_e = int((lat.e != 0).sum())
    flops = (q - 1)                       # rho = sum f
    flops += nonzero_e * 2 - d            # j: adds+mults for nonzero e only
    if cfg.fluid == QUASI_COMPRESSIBLE:
        flops += d                        # u = j / rho
    # equilibrium: eu (nonzero e), poly (4 ops), weight apply (2)
    flops += nonzero_e * 2 - q + q * 6 + (q if cfg.fluid == QUASI_COMPRESSIBLE else 0)
    flops += 3                            # u2
    if cfg.model == LBGK:
        flops += q * 3                    # (feq - f)/tau + f
    else:
        flops += q * q * 2 + q * 2        # dense 19x19 matvec + update
    return flops
