"""Production mesh builders.

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).

Mesh geometry (TPU v5e pods):
    single-pod:  (16, 16)    axes ("data", "model")        = 256 chips
    multi-pod :  (2, 16, 16) axes ("pod", "data", "model") = 512 chips

Parallelism mapping (see repro/dist/sharding.py):
    DP/FSDP over ("pod", "data")  — batch + ZeRO-3 weight sharding
    TP/EP    over "model"          — heads / ff / vocab / experts
    SP       over "model"          — inter-layer activation seq sharding
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding layer places values
    with ``with_sharding_constraint``, which refuses the Explicit axes that
    ``jax.make_mesh`` creates by default in current JAX."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Tiny mesh over the locally available devices (tests / smoke runs)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"))


def mesh_chip_count(mesh) -> int:
    return int(mesh.devices.size)
