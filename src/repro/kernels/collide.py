"""Pallas TPU kernel: fused LBM collision over tile-pair-packed blocks.

TPU adaptation of the paper's fused kernel (Algorithm 2, lines 13-15):
the compute stage (macroscopics + equilibrium + relaxation + solid masking)
runs entirely in VMEM over blocks of tile-pairs.

Data layout (DESIGN.md §2): f is (Q, G, 128) — each 128-lane row holds two
4^3 tiles (the paper packs one tile per two warps; we pack two tiles per
vector row so every data-block row is exactly one lane-aligned vreg row).
The grid walks G in blocks of ``block_rows`` rows; each kernel instance sees

    f_ref     : (Q, block_rows, 128)   VMEM
    solid_ref : (block_rows, 128)      VMEM (uint8; 1 = solid/padding)
    a_ref     : (Q, Q)                 VMEM (LBMRT collision matrix only)
    out_ref   : (Q, block_rows, 128)   VMEM

The direction vectors e_i and weights w_i are unrolled as python scalars:
multiplications by -1/0/+1 become adds/subs/skips — the same strength
reduction the paper observes in the compiled SASS (§2.3, Table 2).  LBGK is
pure VPU element-wise math; LBMRT contracts the 19x19 collision matrix
against the (Q, block_rows*128) block — an MXU matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import collision as col
from repro.core.lattice import Lattice

LANES = 128


def _signed_sum(terms):
    """Sum of (+/-) terms without multiplies, skipping zeros."""
    acc = None
    for sign, v in terms:
        t = v if sign > 0 else -v
        acc = t if acc is None else acc + t
    return acc


def _equilibrium_rows(f, solid, lat: Lattice, cfg: col.CollisionConfig,
                      force):
    """Macroscopics + equilibrium over Q same-shape 2-D arrays ``f[i]``,
    e/w unrolled as scalars.  Returns the Q equilibrium arrays as a list.

    Rows stay 2-D so the math lowers on Mosaic both for the (Q, R, L)
    collision kernel (``f[i]`` is an (R, L) slab) and for the fused kernel
    (``f[i]`` is one (1, n) direction row)."""
    q = lat.q
    ex, ey, ez = lat.ex, lat.ey, lat.ez
    w = lat.w

    rho = f[0]
    for i in range(1, q):
        rho = rho + f[i]

    jx = _signed_sum([(int(ex[i]), f[i]) for i in range(q) if ex[i] != 0])
    jy = _signed_sum([(int(ey[i]), f[i]) for i in range(q) if ey[i] != 0])
    jz = _signed_sum([(int(ez[i]), f[i]) for i in range(q) if ez[i] != 0])

    if cfg.fluid == col.QUASI_COMPRESSIBLE:
        # FREC analogue (paper Table 2): one reciprocal, three multiplies;
        # guard solid slots (rho = 0) to keep the lanes finite.
        inv_rho = 1.0 / jnp.where(solid, jnp.ones_like(rho), rho)
        ux, uy, uz = jx * inv_rho, jy * inv_rho, jz * inv_rho
    else:
        ux, uy, uz = jx, jy, jz

    if force is not None:
        fx, fy, fz = (float(v) for v in force)
        if cfg.fluid == col.QUASI_COMPRESSIBLE:
            ux = ux + (cfg.tau * fx) * inv_rho
            uy = uy + (cfg.tau * fy) * inv_rho
            uz = uz + (cfg.tau * fz) * inv_rho
        else:
            ux, uy, uz = ux + cfg.tau * fx, uy + cfg.tau * fy, uz + cfg.tau * fz

    u2 = ux * ux + uy * uy + uz * uz

    feqs = []
    for i in range(q):
        terms = []
        if ex[i]:
            terms.append((int(ex[i]), ux))
        if ey[i]:
            terms.append((int(ey[i]), uy))
        if ez[i]:
            terms.append((int(ez[i]), uz))
        eu = _signed_sum(terms) if terms else None
        if eu is None:
            poly = -1.5 * u2
        else:
            poly = 3.0 * eu + 4.5 * (eu * eu) - 1.5 * u2
        wi = float(w[i])
        if cfg.fluid == col.QUASI_COMPRESSIBLE:
            feqs.append(wi * rho * (1.0 + poly))
        else:
            feqs.append(wi * (rho + poly))
    return feqs


def _collide_block(f, solid, a_mat, lat: Lattice, cfg: col.CollisionConfig, force):
    """Collision math on one (Q, R, L) block, e/w unrolled as scalars."""
    dtype = f.dtype
    q = lat.q
    feq = jnp.stack(_equilibrium_rows(f, solid, lat, cfg, force))

    if cfg.model == col.LBGK:
        f_out = f + (feq - f) * (1.0 / cfg.tau)
    else:
        # MRT: (19,19) x (19, R*L) — lands on the MXU.
        _, r, l = f.shape
        delta = (feq - f).reshape(q, r * l)
        f_out = f + jnp.dot(a_mat, delta, preferred_element_type=dtype).reshape(
            q, r, l
        )

    return jnp.where(solid[None], jnp.zeros_like(f_out), f_out)


def _kernel_lbgk(f_ref, solid_ref, out_ref, *, lat, cfg, force):
    f = f_ref[...]
    solid = solid_ref[...] != 0
    out_ref[...] = _collide_block(f, solid, None, lat, cfg, force)


def _kernel_mrt(f_ref, solid_ref, a_ref, out_ref, *, lat, cfg, force):
    f = f_ref[...]
    solid = solid_ref[...] != 0
    out_ref[...] = _collide_block(f, solid, a_ref[...], lat, cfg, force)


def collide_pallas(
    f: jnp.ndarray,            # (Q, G, 128)
    solid_u8: jnp.ndarray,     # (G, 128) uint8
    lat: Lattice,
    cfg: col.CollisionConfig,
    force=None,
    block_rows: int = 8,
    interpret: bool = True,
) -> jnp.ndarray:
    q, g, l = f.shape
    assert l == LANES and g % block_rows == 0, (f.shape, block_rows)
    grid = (g // block_rows,)
    f_spec = pl.BlockSpec((q, block_rows, LANES), lambda i: (0, i, 0))
    s_spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    o_spec = pl.BlockSpec((q, block_rows, LANES), lambda i: (0, i, 0))
    out_shape = jax.ShapeDtypeStruct((q, g, l), f.dtype)

    if cfg.model == col.LBGK:
        kernel = functools.partial(_kernel_lbgk, lat=lat, cfg=cfg, force=force)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[f_spec, s_spec],
            out_specs=o_spec,
            out_shape=out_shape,
            interpret=interpret,
            name="collide_lbgk",
        )(f, solid_u8)

    a_mat = jnp.asarray(col.collision_matrix_np(lat, cfg.tau), f.dtype)
    a_spec = pl.BlockSpec((q, q), lambda i: (0, 0))
    kernel = functools.partial(_kernel_mrt, lat=lat, cfg=cfg, force=force)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[f_spec, s_spec, a_spec],
        out_specs=o_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="collide_lbmrt",
    )(f, solid_u8, a_mat)
