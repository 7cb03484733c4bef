"""Randomly placed, overlapping solid spheres in a fluid box
(arXiv:1611.02445 Table 6): spheres are dropped at seeded random centres
until the box's non-solid fraction falls to ``porosity``."""
from __future__ import annotations

import numpy as np

from bench.geometry import FLUID, SOLID


def apply(prev, box: int, porosity: float, diameter: int, seed: int = 0,
          max_iter: int = 20000) -> np.ndarray:
    assert prev is None, "random_spheres starts a geometry"
    rng = np.random.default_rng(seed)
    g = np.full((box, box, box), FLUID, dtype=np.uint8)
    r = diameter / 2.0
    target_solid = (1.0 - porosity) * box ** 3
    xs = np.arange(box)
    solid_count = 0
    for _ in range(max_iter):
        if solid_count >= target_solid:
            break
        c = rng.uniform(r * 0.2, box - r * 0.2, size=3)
        lo = np.maximum(np.floor(c - r).astype(int), 0)
        hi = np.minimum(np.ceil(c + r).astype(int) + 1, box)
        sub = np.ix_(xs[lo[0]:hi[0]], xs[lo[1]:hi[1]], xs[lo[2]:hi[2]])
        dx = xs[lo[0]:hi[0], None, None] - c[0]
        dy = xs[None, lo[1]:hi[1], None] - c[1]
        dz = xs[None, None, lo[2]:hi[2]] - c[2]
        inside = dx * dx + dy * dy + dz * dz <= r * r
        newly = inside & (g[sub] != SOLID)
        solid_count += int(newly.sum())
        g[sub] = np.where(inside, SOLID, g[sub])
    return g
