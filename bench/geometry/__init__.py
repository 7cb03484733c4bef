"""Geometry of a configuration: a list of operations found by name.

A configuration's ``"geometry"`` is a list of ``{"op": <name>, ...args}``;
each op is the module ``bench/geometry/<name>.py`` with a function
``apply(prev, **args)`` that returns a uint8 node-type array (``prev`` is
the previous op's array, ``None`` for the first).  A new generator is a new
file.  Node types follow the solver's convention.
"""
from __future__ import annotations

import numpy as np

from bench import harness

SOLID, FLUID, INLET, OUTLET = 0, 1, 2, 3


def build(root: str, ops: list[dict]) -> np.ndarray:
    """Run the configuration's geometry ops, found under ``root``, in order."""
    g = None
    for op in ops:
        args = {k: v for k, v in op.items() if k != "op"}
        g = harness.module(root, "geometry", op["op"]).apply(g, **args)
    return np.ascontiguousarray(g, dtype=np.uint8)
