"""Wrap a block in a solid duct along z: ``wall`` solid layers on the x and
y faces; fluid nodes on the first and last z plane become INLET and
OUTLET."""
from __future__ import annotations

import numpy as np

from bench.geometry import FLUID, INLET, OUTLET, SOLID


def apply(prev, wall: int = 1) -> np.ndarray:
    assert prev is not None and wall >= 1
    nx, ny, nz = prev.shape
    out = np.full((nx + 2 * wall, ny + 2 * wall, nz), SOLID, dtype=np.uint8)
    out[wall:-wall, wall:-wall, :] = prev
    inner = out[wall:-wall, wall:-wall, :]
    inner[:, :, 0] = np.where(inner[:, :, 0] == FLUID, INLET, inner[:, :, 0])
    inner[:, :, -1] = np.where(inner[:, :, -1] == FLUID, OUTLET,
                               inner[:, :, -1])
    return out
