"""Jit'd public wrappers around the Pallas kernels + interpret-mode policy.

``collide_tiles`` accepts the engine's canonical (Q, T, n) layout, packs it
into the kernel's tile-pair (Q, G, 128) layout (padding with solid slots),
runs the kernel, and unpacks.  The fused stream+collide kernel has no such
wrapper: the fused engine backend keeps its state in the kernel's packed
(T+1, Q, n) layout persistently (see ``repro.core.backends``), so nothing
needs packing per step.

Interpret mode: Pallas kernels run compiled on the TPU and interpreted on
the CPU (the test suite).  ``interpret=None`` everywhere means "auto":
:func:`default_interpret` picks from ``jax.default_backend()`` and refuses
any other platform, so no run falls into the interpreter where a device
was expected.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import collision as col
from repro.core.lattice import Lattice

from .collide import LANES, collide_pallas


def default_interpret() -> bool:
    """True on the CPU (interpret), False on the TPU (compile).

    Any other platform raises: the kernels are TPU Pallas (the fused one
    scalar-prefetches its neighbour table), and interpreting them there
    would hide the missing device behind a slow, valid-looking run.
    """
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas LBM kernels compile on tpu and are interpreted on cpu; "
            f"the jax backend is {backend!r}")
    return backend == "cpu"


def resolve_interpret(flag: bool | None) -> bool:
    """Resolve an ``interpret`` tri-state (None = auto) to a bool."""
    return default_interpret() if flag is None else bool(flag)


def _pack(f: jnp.ndarray, solid: jnp.ndarray, block_rows: int):
    """(Q, T, n) -> (Q, G, 128) with G a multiple of block_rows."""
    q = f.shape[0]
    m = f.shape[1] * f.shape[2]
    row_nodes = LANES * block_rows
    m_pad = -(-m // row_nodes) * row_nodes
    f_flat = f.reshape(q, m)
    s_flat = solid.reshape(m).astype(jnp.uint8)
    if m_pad != m:
        f_flat = jnp.pad(f_flat, ((0, 0), (0, m_pad - m)))
        s_flat = jnp.pad(s_flat, (0, m_pad - m), constant_values=1)
    return f_flat.reshape(q, m_pad // LANES, LANES), s_flat.reshape(-1, LANES), m


@partial(
    jax.jit,
    static_argnames=("lat", "cfg", "force", "block_rows", "interpret"),
)
def collide_tiles(
    f: jnp.ndarray,            # (Q, T, n) canonical post-streaming state
    solid: jnp.ndarray,        # (T, n) bool
    lat: Lattice,
    cfg: col.CollisionConfig,
    force=None,
    block_rows: int = 8,
    interpret: bool | None = None,
) -> jnp.ndarray:
    q, t, n = f.shape
    fp, sp, m = _pack(f, solid, block_rows)
    out = collide_pallas(fp, sp, lat, cfg, force, block_rows,
                         resolve_interpret(interpret))
    return out.reshape(q, -1)[:, :m].reshape(q, t, n)
