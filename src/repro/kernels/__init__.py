"""Pallas kernels for the sparse tiled LBM (paper §4).

* ``collide.py`` / ``ops.collide_tiles`` — collision-only kernel over
  tile-pair-packed blocks (used by the gather backend's ``use_kernel``).
* ``stream_collide.py`` — the paper's FUSED stream+collide kernel
  (Algorithm 2, one instance per tile, scalar-prefetched tileMap); the
  fused engine backend (``repro.core.backends.FusedBackend``) keeps its
  state in this kernel's packed (T+1, Q, n) layout persistently, and
  re-streams its open-boundary tiles with the same kernel's pull over a
  tile list (``nebb_stream_tiles``).
* ``flash.py`` — attention kernel for the LM stack (unrelated to LBM).

Kernels run compiled on the TPU and in interpret mode on the CPU; any
other platform is refused (``ops.default_interpret``).
"""
from .ops import collide_tiles, default_interpret, resolve_interpret
from .stream_collide import (build_bounce_mask, build_neighbor_table,
                             nebb_stream_tiles, pack_engine_state,
                             stream_collide_tiles, unpack_engine_state,
                             zero_scratch_row)

__all__ = [
    "collide_tiles", "default_interpret", "resolve_interpret",
    "build_bounce_mask", "build_neighbor_table", "nebb_stream_tiles",
    "pack_engine_state", "stream_collide_tiles", "unpack_engine_state",
    "zero_scratch_row",
]
