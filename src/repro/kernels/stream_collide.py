"""Pallas TPU kernel: the paper's FUSED LBM step (Algorithm 2) per tile.

One kernel instance = one tile (grid over non-empty tiles).  The paper's
shared-memory copy of the local tileMap (Fig. 11) becomes SCALAR-PREFETCHED
neighbour indices: the per-offset BlockSpec index_maps read the neighbour
tile id from the prefetched (T, 27) table (one chunk of ``TILES_PER_CALL``
tiles per call), so every pull source streams
HBM→VMEM as a whole data block — the TPU analogue of the paper's "minimal
fully-utilised transactions" (DESIGN.md §2).

Data layout: f is (T+1, Q, n) — one contiguous (Q, 64) data block per tile,
with a SCRATCH tile (all-solid, zero f) at index T; out-of-grid/empty
neighbours point at it, so half-way bounce-back falls out of the ordinary
"source is solid" test with no branches (the paper's Algorithm 2 lines
9-11).  Periodic axes wrap through the neighbour table itself
(:func:`build_neighbor_table`), so the kernel needs no periodic branches.

Pull geometry: node x pulls f_q from x - e_q, which lies in this tile or in
one of the D3Q19 linkage neighbours — for DIAGONAL directions an edge/corner
node's source may sit in a FACE neighbour rather than the diagonal one, so
the kernel loads all 18 linked neighbour f blocks (6 faces + 12 edges) once
and a static per-(direction, node) CASE table picks the source block.

Whether a pull's source is solid depends only on the geometry and the
neighbour table, so it is not recomputed per step: a per-tile BOUNCE MASK
(:func:`build_bounce_mask`, one int32 word per node) holds it, bit q set
when the pull of direction q reads a solid node (scratch tile included)
and bit Q set when the node itself is solid.  Each grid step therefore
DMAs the own f and mask blocks and the 18 neighbour f blocks
(:func:`blocks_per_tile`).  All tables are host-built numpy constants
shipped as kernel inputs, exactly like the paper builds its indices once
on CPU.

The kernel computes in the storage dtype (float32 on TPU, float64 for the
CPU validation runs), so the float64 parity tests against the gather
backend hold to 1e-12.  The paper's §4.1 kernel variants are supported via
``mode``: 'full' (stream + collide), 'propagation_only' (stream, no
collision math), 'rw_only' (read + write each tile's own data block — the
bandwidth ceiling probe).

Collision reuses the tile-pair collide math (kernels/collide.py) — LBGK is
pure VPU; LBMRT contracts the 19x19 collision matrix on the MXU.
Validated in interpret mode against SparseTiledLBM in
tests/test_kernels_fused.py; the same code compiles for TPU v5e
(tests/test_tpu_compile.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import collision as col
from repro.core.lattice import Lattice
from repro.core.tiling import (NEIGHBOR_OFFSETS, SOLID, Tiling,
                               neighbor_offset_index)
from repro.obs.trace import phase_scope

from .collide import _equilibrium_rows

MODES = ("full", "propagation_only", "rw_only")

# tiles per pallas_call: the call's (tiles, 27) int32 neighbour slice
# (442 KiB) must fit the scalar memory with room to spare
TILES_PER_CALL = 4096

_PULL_CACHE: dict[tuple, tuple] = {}


def _pull_geometry(lat: Lattice, a: int = 4, node_order: str = "canonical"):
    """Static pull tables.

    Returns (offsets, perms (Q, n) int32, cases (Q, n) int8) where
    offsets is the ordered list of distinct neighbour tile offsets the
    lattice links to, and cases[q, node] = 0 for an in-tile source or
    1 + offsets.index(node's source-tile offset).  Under a non-canonical
    ``node_order`` (repro.core.tiling.NODE_ORDERS) both tables are
    remapped into the within-tile slot enumeration: row index = dst slot,
    perm values = src slots."""
    key = (lat.name, a, node_order)
    if key in _PULL_CACHE:
        return _PULL_CACHE[key]
    n = a ** 3
    idx = np.arange(n)
    x, y, z = idx % a, (idx // a) % a, idx // (a * a)
    offsets: list[tuple[int, int, int]] = []
    perms = np.zeros((lat.q, n), np.int32)
    cases = np.zeros((lat.q, n), np.int8)
    for q in range(lat.q):
        e = lat.e[q]
        sx, sy, sz = x - e[0], y - e[1], z - e[2]
        perms[q] = (sx % a) + a * (sy % a) + a * a * (sz % a)
        dx, dy, dz = sx // a, sy // a, sz // a       # each in {-1, 0}
        for node in range(n):
            off = (int(dx[node]), int(dy[node]), int(dz[node]))
            if off == (0, 0, 0):
                continue
            if off not in offsets:
                offsets.append(off)
            cases[q, node] = 1 + offsets.index(off)
    if node_order != "canonical":
        from repro.core.tiling import node_order_permutation

        sigma = node_order_permutation(node_order, a)   # canonical -> slot
        inv = np.argsort(sigma, kind="stable")          # slot -> canonical
        perms = sigma[perms][:, inv].astype(np.int32)
        cases = cases[:, inv]
    _PULL_CACHE[key] = (offsets, perms, cases)
    return _PULL_CACHE[key]


def build_neighbor_table(
    tiling: Tiling, periodic: tuple[bool, bool, bool] = (False, False, False)
) -> np.ndarray:
    """Kernel-ready (T, 27) neighbour table: scratch index T for empty or
    out-of-grid neighbours, periodic axes wrapped through the tile grid.

    Periodic wrap happens at tile granularity, so a periodic axis needs its
    ORIGINAL extent to be a multiple of the tile edge ``a`` (otherwise the
    solid padding layer would sit inside the wrap); the gather backend has
    no such restriction because it wraps per node.
    """
    for ax in range(3):
        if periodic[ax] and tiling.orig_shape[ax] % tiling.a:
            raise ValueError(
                f"fused kernel: periodic axis {ax} needs extent % a == 0 "
                f"(got {tiling.orig_shape[ax]} % {tiling.a})")
    t = tiling.num_tiles
    grid = np.array(tiling.tile_grid, np.int64)
    shifted = (tiling.tile_coords[:, None, :].astype(np.int64)
               + NEIGHBOR_OFFSETS[None, :, :])                  # (T, 27, 3)
    in_grid = np.ones(shifted.shape[:2], bool)
    for ax in range(3):
        if periodic[ax]:
            shifted[..., ax] %= grid[ax]
        else:
            in_grid &= (shifted[..., ax] >= 0) & (shifted[..., ax] < grid[ax])
    clamped = np.clip(shifted, 0, grid - 1)
    nbr = tiling.tile_map[clamped[..., 0], clamped[..., 1], clamped[..., 2]]
    nbr = np.where(in_grid, nbr, -1)
    return np.where(nbr < 0, t, nbr).astype(np.int32)


def build_bounce_mask(node_types: np.ndarray, neighbors: np.ndarray,
                      lat: Lattice, a: int = 4,
                      node_order: str = "canonical") -> np.ndarray:
    """The kernel's (T+1, 1, n) int32 bounce mask.

    node_types: (T, n) node types in ``node_order`` slots
    neighbors:  (T, 27) the kernel's neighbour table (scratch index T,
                periodic wraps included: :func:`build_neighbor_table`)

    Bit q (q < Q) of ``mask[t, 0, i]`` is set when the pull of direction q
    into node i of tile t reads a SOLID source: the node that ``perms``
    picks in the tile that ``cases`` picks, through ``neighbors``.  Bit Q
    is set when node i itself is SOLID.  The scratch row T has every bit
    set.  The unit middle axis makes each per-tile block's last two dims
    equal the array's (Mosaic's block-shape rule).  Built one (direction,
    node) pull at a time over node-major rows, so that each pull reads one
    contiguous row of its source node's solidity, gathered by source tile
    where that lies in a neighbour (0.7 s at 236k tiles on one host core).
    """
    t, n = node_types.shape
    offsets, perms, cases = _pull_geometry(lat, a, node_order)
    solid = np.ones((n, t + 1), np.int32)
    solid[:, :t] = (node_types == SOLID).T
    src = neighbors[:, [neighbor_offset_index(*off) for off in offsets]].T
    bits = solid[:, :t] << lat.q                                    # (n, T)
    for q in range(lat.q):
        for i in range(n):
            c = cases[q, i]
            row = solid[perms[q, i]]
            bits[i] |= (row[:t] if c == 0 else row.take(src[c - 1])) << q
    mask = np.full((t + 1, 1, n), (2 << lat.q) - 1, np.int32)
    mask[:t, 0] = bits.T
    return mask


def blocks_per_tile(lat: Lattice, a: int = 4,
                    node_order: str = "canonical") -> int:
    """Per-tile input blocks each grid step of the pull DMAs: the own f
    and bounce-mask blocks, and the f block of each linked neighbour."""
    return 2 + len(_pull_geometry(lat, a, node_order)[0])


def make_kernel(lat: Lattice, cfg: col.CollisionConfig, n_offsets: int,
                force=None, mode: str = "full"):
    """Kernel body for one tile.

    Mosaic constraints shape it: every in-kernel value is 2-D ((Q, n)
    blocks or (1, n) direction rows), and the pull is a 2-D lane gather
    ``take_along_axis(block, perms, axis=1)`` over the whole (Q, n) f
    block (a 1-D ``jnp.take`` per direction does not lower; a one-hot
    (n, n) selection on the MXU would cost Q*n^2 MACs per block and round
    float32 operands unless run at HIGHEST precision, while the gather is
    exact and stays on the VPU/XLU).  Which pulls bounce back, and which
    nodes are solid, is read from the own tile's bounce mask row
    (:func:`build_bounce_mask`) by a shift per direction row, not gathered
    from the neighbours.  The static row permutation ``f[opp]`` and the
    per-direction rows the collision needs go through the output block
    itself as a staging buffer: single-row ref loads/stores lower where
    value-level row shuffles do not, and the block is fully overwritten
    before the kernel returns.
    """
    q = lat.q
    opp = [int(o) for o in lat.opp]
    mrt = cfg.model == col.LBMRT and mode == "full"

    def kernel(start_ref, nb_ref, own_f, own_m, perms_ref, cases_ref, *rest):
        # rest: neighbour f x n_offsets, [A], aliased output buffer, out
        out_ref = rest[-1]
        a_ref = rest[-3] if mrt else None
        perms = perms_ref[...]                # (Q, n) int32
        cases = cases_ref[...]                # (Q, n) int32
        n = perms.shape[1]

        src_f = jnp.take_along_axis(own_f[0], perms, axis=1)
        for c in range(n_offsets):
            src_f = jnp.where(cases == (c + 1),
                              jnp.take_along_axis(rest[c][0], perms, axis=1),
                              src_f)

        # half-way bounce-back: a solid source returns the node's own
        # opposite population, f_own[opp[q]]
        m_own = own_m[0]                      # (1, n) int32
        row = jax.lax.broadcasted_iota(jnp.int32, (q, n), 0)
        bounce = ((jnp.broadcast_to(m_own, (q, n)) >> row) & 1) == 1
        for i in range(q):
            out_ref[0, i:i + 1, :] = own_f[0, opp[i]:opp[i] + 1, :]
        f_in = jnp.where(bounce, out_ref[0], src_f)   # (Q, n)
        out_ref[0] = f_in
        if mode == "propagation_only":
            return

        solid_row = ((m_own >> q) & 1) == 1   # (1, n)
        rows = [out_ref[0, i:i + 1, :] for i in range(q)]
        feq = _equilibrium_rows(rows, solid_row, lat, cfg, force)
        if not mrt:
            for i in range(q):
                f_out = rows[i] + (feq[i] - rows[i]) * (1.0 / cfg.tau)
                out_ref[0, i:i + 1, :] = jnp.where(solid_row, 0.0, f_out)
            return
        # MRT: (Q, Q) x (Q, n) on the MXU; the delta rows are staged
        # through the output block to form the (Q, n) operand
        for i in range(q):
            out_ref[0, i:i + 1, :] = feq[i] - rows[i]
        f_out = f_in + jnp.dot(a_ref[...], out_ref[0],
                               preferred_element_type=f_in.dtype,
                               precision=jax.lax.Precision.HIGHEST)
        out_ref[0] = jnp.where(jnp.broadcast_to(solid_row, (q, n)),
                               0.0, f_out)

    return kernel


def _pull_inputs(f, mask, lat: Lattice, a: int, node_order: str, nw: int,
                 own_map):
    """BlockSpecs and operands of the pull, shared by the kernel's two
    calls (:func:`stream_collide_tiles`, :func:`nebb_stream_tiles`): the
    own tile's f and bounce-mask blocks (``own_map``), the static
    perms/cases tables, then the f block of each linked neighbour tile,
    whose id the index map reads from the neighbour rows prefetched as the
    LAST scalar-prefetch operand (``nw`` entries per grid step)."""
    q, n = f.shape[1], f.shape[2]
    offsets, perms, cases = _pull_geometry(lat, a, node_order)
    table_spec = pl.BlockSpec((q, n), lambda i, *_: (0, 0))
    in_specs = [
        pl.BlockSpec((1, q, n), own_map),                    # own f
        pl.BlockSpec((1, 1, n), own_map),                    # own mask
        table_spec, table_spec,                              # perms, cases
    ]
    operands = [f, mask, jnp.asarray(perms), jnp.asarray(cases, jnp.int32)]
    for off in offsets:
        k = neighbor_offset_index(*off)

        def nb_map(i, *prefetched, _k=k):
            return (prefetched[-1][i * nw + _k], 0, 0)

        in_specs.append(pl.BlockSpec((1, q, n), nb_map))
        operands.append(f)
    return in_specs, operands


def _rw_kernel(own_f, out_ref):
    """paper §4.1 'rw_only' variant: read + write the tile's own block."""
    out_ref[0] = own_f[0]


def zero_scratch_row(f: jnp.ndarray, row: int) -> jnp.ndarray:
    """Reset the scratch tile row (lowered as dynamic_update_slice, NOT a
    scatter — the fused hot loop must stay free of gather/scatter ops)."""
    zeros = jnp.zeros((1,) + f.shape[1:], f.dtype)
    return jax.lax.dynamic_update_slice(f, zeros, (row,) + (0,) * (f.ndim - 1))


def stream_collide_tiles(f, mask, neighbors, lat: Lattice,
                         cfg: col.CollisionConfig, a: int = 4, force=None,
                         interpret: bool | None = None, mode: str = "full",
                         node_order: str = "canonical"):
    """One fused LBM step over all tiles.

    f:          (T+1, Q, n) — scratch tile at index T must be zero
    mask:       (T+1, 1, n) int32 bounce mask (:func:`build_bounce_mask`
                over ``neighbors``) — scratch row all bits set
    neighbors:  (T, 27) int32 — empty/out-of-grid entries = T (scratch)
    mode:       'full' | 'propagation_only' | 'rw_only' (paper §4.1)
    node_order: within-tile node enumeration the caller's f/mask use
                (repro.core.tiling.NODE_ORDERS); the static pull tables are
                remapped to match
    interpret:  None = auto (:func:`repro.kernels.ops.default_interpret`)
    Returns the post-step (T+1, Q, n) (scratch row zeroed).

    The Pallas calls are named ``stream_collide`` (``rw_only`` for that
    mode), so a device trace names the kernel's instructions; they run
    under the named scope ``lbm.phase.stream_collide``, and the output
    buffer and scratch-row reset around them under ``lbm.phase.pack``.
    """
    from .ops import resolve_interpret

    assert mode in MODES, mode
    interpret = resolve_interpret(interpret)
    t1, q, n = f.shape
    t = t1 - 1

    if mode == "rw_only":
        with phase_scope("lbm.phase.stream_collide"):
            out = pl.pallas_call(
                _rw_kernel,
                grid=(t,),
                in_specs=[pl.BlockSpec((1, q, n), lambda i: (i, 0, 0))],
                out_specs=pl.BlockSpec((1, q, n), lambda i: (i, 0, 0)),
                out_shape=jax.ShapeDtypeStruct((t1, q, n), f.dtype),
                interpret=interpret,
                name="rw_only",
            )(f)
        with phase_scope("lbm.phase.pack"):
            return zero_scratch_row(out, t)

    offsets = _pull_geometry(lat, a, node_order)[0]
    kernel = make_kernel(lat, cfg, len(offsets), force, mode)

    assert mask.shape == (t1, 1, n), mask.shape
    nw = neighbors.shape[1]

    def own_map(i, start, nb):
        return (start[0] + i, 0, 0)

    in_specs, operands = _pull_inputs(f, mask, lat, a, node_order, nw,
                                      own_map)
    if cfg.model == col.LBMRT and mode == "full":
        in_specs.append(pl.BlockSpec((q, q), lambda i, start, nb: (0, 0)))
        operands.append(jnp.asarray(col.collision_matrix_np(lat, cfg.tau),
                                    f.dtype))
    # the output buffer rides in aliased and untouched (pl.ANY: no DMA);
    # each call writes only its own chunk's tile blocks
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))

    # The neighbour table is scalar-prefetched into SMEM (1 MiB on v5e),
    # which a whole-domain table outgrows (236k tiles x 27 int32 = 25 MB).
    # So the grid runs in chunks of ``TILES_PER_CALL`` tiles, one
    # pallas_call per chunk inside a fori_loop.  The last chunk is clamped
    # to end at tile T-1; the tiles it repeats are recomputed from the
    # same read-only input, so they are rewritten with identical values.
    chunk = min(t, TILES_PER_CALL)
    n_chunks = -(-t // chunk)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(chunk,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, q, n), own_map),
        ),
        out_shape=jax.ShapeDtypeStruct((t1, q, n), f.dtype),
        input_output_aliases={2 + len(operands): 0},
        interpret=interpret,
        name="stream_collide",
    )
    nb_flat = neighbors.reshape(-1)

    def run_chunk(c, out):
        start = jnp.minimum(c * chunk, t - chunk)
        nb = jax.lax.dynamic_slice_in_dim(nb_flat, start * nw, chunk * nw)
        return call(start[None], nb, *operands, out)

    with phase_scope("lbm.phase.pack"):
        out = jnp.zeros_like(f)
    with phase_scope("lbm.phase.stream_collide"):
        out = jax.lax.fori_loop(0, n_chunks, run_chunk, out)
    with phase_scope("lbm.phase.pack"):
        return zero_scratch_row(out, t)


def nebb_stream_tiles(f, mask, tiles, rows, lat: Lattice, a: int = 4,
                      interpret: bool | None = None,
                      node_order: str = "canonical"):
    """The fused kernel's pull over a LIST of tiles: streaming plus
    half-way bounce-back, no collision (``mode='propagation_only'``).

    f, mask:    as in :func:`stream_collide_tiles` (scratch row last)
    tiles:      (B,) int32 tile rows of ``f`` to pull
    rows:       (B, 27) int32 their neighbour-table rows (``nbrs[tiles]``)
    Returns the post-streaming, pre-collision (B, Q, n) block.

    One Pallas call named ``nebb_stream`` per chunk of ``TILES_PER_CALL``
    list entries (``%nebb_stream.N`` in a device trace): the own-tile
    index map reads the tile id from the prefetched list, the neighbour
    index maps read the prefetched rows, and the kernel body is
    :func:`make_kernel`'s, so every value equals what the main call pulls
    for that tile.  The last chunk is clamped to end at entry B-1, as in
    the main call.
    """
    from .ops import resolve_interpret

    interpret = resolve_interpret(interpret)
    q, n = f.shape[1], f.shape[2]
    b, nw = rows.shape
    offsets = _pull_geometry(lat, a, node_order)[0]
    pull = make_kernel(lat, col.CollisionConfig(), len(offsets),
                       mode="propagation_only")

    def kernel(start_ref, ids_ref, nb_ref, *refs):
        pull(start_ref, nb_ref, *refs)

    def own_map(i, start, ids, nb):
        return (ids[i], 0, 0)

    in_specs, operands = _pull_inputs(f, mask, lat, a, node_order, nw,
                                      own_map)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))   # aliased output
    chunk = min(b, TILES_PER_CALL)
    n_chunks = -(-b // chunk)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(chunk,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, q, n), lambda i, start, ids, nb: (start[0] + i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, q, n), f.dtype),
        input_output_aliases={3 + len(operands): 0},
        interpret=interpret,
        name="nebb_stream",
    )
    rows_flat = rows.reshape(-1)

    def run_chunk(c, out):
        start = jnp.minimum(c * chunk, b - chunk)
        ids = jax.lax.dynamic_slice_in_dim(tiles, start, chunk)
        nb = jax.lax.dynamic_slice_in_dim(rows_flat, start * nw, chunk * nw)
        return call(start[None], ids, nb, *operands, out)

    return jax.lax.fori_loop(0, n_chunks, run_chunk,
                             jnp.zeros((b, q, n), f.dtype))


def pack_engine_state(tiling: Tiling, f_canon, lat: Lattice):
    """(Q, T, n) canonical engine state -> kernel inputs: f, bounce mask,
    neighbour table."""
    t, n = tiling.num_tiles, tiling.nodes_per_tile
    f = jnp.zeros((t + 1, lat.q, n), f_canon.dtype)
    f = f.at[:t].set(jnp.moveaxis(f_canon, 0, 1))
    nbrs = np.where(tiling.tile_neighbors < 0, t,
                    tiling.tile_neighbors).astype(np.int32)
    mask = build_bounce_mask(tiling.node_types, nbrs, lat, tiling.a,
                             tiling.node_order)
    return f, jnp.asarray(mask), jnp.asarray(nbrs)


def unpack_engine_state(f_packed):
    return jnp.moveaxis(f_packed[:-1], 0, 1)       # -> (Q, T, n)
