"""Step backends for :class:`repro.core.engine.SparseTiledLBM`.

A backend owns the device-resident representation of f and produces one LBM
iteration as a pure ``(state, tables) -> state`` function the engine jits
(and loops with ``fori_loop`` in ``run``).  ``backend.tables`` holds every
geometry-sized table (index, neighbour, type and boundary tables), placed on
the device once and passed to the jitted step as an ARGUMENT: closed over,
they would be embedded in the compiled program as constants, whose size
then grows with the geometry (gigabytes at chip-filling sizes).

* ``gather``  — one jnp gather per direction from the per-direction storage
  layout (supports every ``layout_scheme``), jnp or Pallas collision
  (``use_kernel``).  This is the reference path.
* ``fused``   — the paper's actual contribution: the fused Pallas
  stream+collide kernel (``repro.kernels.stream_collide``) over state kept
  PERSISTENTLY in the kernel's packed (T+1, Q, n) layout.  Packing happens
  once at init and unpacking only in diagnostics, so ``step``/``run``
  contain zero layout shuffles: the jitted hot loop is the pallas_call, a
  scratch-row reset, and (only when open boundaries exist) the NEBB
  reconstruction pass over the boundary tiles: the same kernel's pull over
  that tile list, the rebuild and collision, and one small scatter.

Both backends produce identical physics: float64 parity is pinned to 1e-12
in tests/test_backend_fused.py on all benchmark geometry families.

Ensemble stepping (``repro.sim.ensemble``): both backends can advance B
INDEPENDENT flow states over the SAME geometry in one dispatch, so the
indirection tables (the paper's dominant bandwidth cost on sparse
geometries) are loaded once per step for B states instead of once per
state:

* gather — a leading batch axis on f: ``ensemble_step`` is ``jax.vmap``
  of the scalar step, which keeps every replica BITWISE identical to an
  independent engine (the index tables are unbatched arguments shared
  across the batch).
* fused — a B-replicated packed state ``(B*T + 1, Q, n)``: the tile axis
  is replicated B times with per-replica offsets folded into the
  neighbour table (scratch row shared at index B*T), so one kernel grid
  over all B*T tiles advances every replica while the static (Q, n) pull
  perms/cases stay a single copy.

Tile traversal order (``LBMConfig.tile_order``): every per-tile table a
backend builds — packed state, the fused kernel's neighbour table, the
boundary-pass tables — is derived from ``tiling.tile_coords`` /
``tiling.tile_map`` / ``tables.gather_idx``, never from an assumed z-major
enumeration, so reordering tiles permutes storage without touching
physics.  tests/test_tile_order.py pins bitwise (gather) and 1e-12
(fused) parity across all TILE_ORDERS.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.obs.trace import phase_scope

from . import collision as col
from .boundary import apply_open_boundary
from .streaming import StreamTables
from .tiling import SOLID, Tiling

BACKENDS = ("gather", "fused")


def make_backend(name: str, cfg, lat, tiling: Tiling, tables: StreamTables,
                 interpret: bool):
    if name == "gather":
        return GatherBackend(cfg, lat, tiling, tables, interpret)
    if name == "fused":
        return FusedBackend(cfg, lat, tiling, tables, interpret)
    raise ValueError(f"unknown backend {name!r}; expected one of {BACKENDS}")


def place(host):
    """A tree of host tables on the device, under ``lbm.setup.place``
    (waited for only when the recorder is enabled, so that the span then
    times the transfer and not its dispatch)."""
    tr = obs.get_tracer()
    with tr.span("lbm.setup.place"):
        tree = jax.tree.map(jnp.asarray, host)
        if tr.enabled:
            jax.block_until_ready(tree)
    return tree


def boundary_pass_tables(node_types: np.ndarray, neighbors: np.ndarray,
                         boundaries):
    """Host-side tables for the fused backends' masked NEBB pass.

    ``node_types``: (T, n) uint8; ``neighbors``: the kernel's (T, 27)
    neighbour table.  Returns numpy ``(tiles (B,), rows (B, 27),
    type_masks (S, B, n), solid (B, n))`` restricted to the tiles that
    hold boundary nodes, ``rows`` being their neighbour-table rows — or
    ``None`` when no node matches any declared boundary type (a
    declared-but-absent boundary must skip the pass, not scatter over an
    empty (0, Q, n) block).  Shared by ``FusedBackend`` and ``ShardedLBM``
    so the two fused paths cannot drift.
    """
    node_bc = np.zeros_like(node_types, bool)
    for tv, _ in boundaries:
        node_bc |= node_types == tv
    bt = np.nonzero(node_bc.any(axis=1))[0].astype(np.int32)
    if not len(bt):
        return None
    type_masks = np.stack([node_types[bt] == tv for tv, _ in boundaries])
    return bt, neighbors[bt], type_masks, node_types[bt] == SOLID


def apply_split_stream(f_store, solid, *, intra, is_cross, nbr, case,
                       bounce_dst, irregular_dst, irregular_src, opp, perms):
    """Split-phase pull streaming: storage-layout ``f_store`` (Q, T, n) ->
    post-streaming ``f_in`` (Q, T, n) in node-axis (slot) order.

    Phase 1 (interior): ONE (Q, n) index table broadcast over the tile
    axis — no per-node index load for intra-tile links.  Phase 2
    (frontier): cross-tile sources are COMPUTED from the (T, 27) neighbour
    table + the same (Q, n) tables (zero per-link storage for regular
    cross links); bounce links scatter over the result from a compact flat
    destination list (their source is recomputed from ``opp``/``perms``),
    and the rare statically-unpredictable links use explicit (dst, src)
    pairs.  Solid destinations are zeroed — their post-collision value is
    masked to zero anyway, which keeps 'full'-mode steps bitwise identical
    to the monolithic gather.

    Shared by :class:`GatherBackend` and ``repro.dist.lbm.ShardedLBM`` so
    the two split paths cannot drift.
    """
    q, t, n = f_store.shape
    m = t * n
    flat = f_store.reshape(-1)
    with phase_scope("lbm.phase.stream_interior"):
        # ---- interior: (Q, n) static permutation broadcast over tiles
        f_in = jnp.take_along_axis(f_store, intra[:, None, :], axis=-1)
    with phase_scope("lbm.phase.stream_frontier"):
        # ---- frontier, regular cross links: computed indices, no
        # per-link table
        src_tile = jnp.moveaxis(jnp.take(nbr, case, axis=1), 0, 1)  # (Q,T,n)
        idx = (jnp.arange(q, dtype=src_tile.dtype)[:, None, None] * m
               + src_tile * n + intra[:, None, :])
        f_cross = jnp.take(flat, idx.reshape(-1)).reshape(q, t, n)
        f_in = jnp.where(is_cross[:, None, :], f_cross, f_in).reshape(-1)
        # ---- frontier, bounce links: dst list only; src recomputed on
        # the fly
        if bounce_dst.size:
            dq, rem = jnp.divmod(bounce_dst, m)
            dt_, ds = jnp.divmod(rem, n)
            src = opp[dq] * m + dt_ * n + perms.reshape(-1)[opp[dq] * n + ds]
            f_in = f_in.at[bounce_dst].set(jnp.take(flat, src))
        # ---- frontier, irregular links: explicit (dst, src) pairs
        if irregular_dst.size:
            f_in = f_in.at[irregular_dst].set(jnp.take(flat, irregular_src))
        f_in = f_in.reshape(q, t, n)
    return jnp.where(solid[None], 0.0, f_in)


def nebb_boundary_pass(f_pre, out, mask, cfg, lat, interpret, tiles, rows,
                       type_masks, solid):
    """The fused backends' post-kernel masked NEBB pass (device-side).

    Re-streams ONLY the boundary ``tiles`` from the pre-step packed state
    ``f_pre`` with the fused kernel's own pull over that tile list
    (:func:`repro.kernels.stream_collide.nebb_stream_tiles`; ``rows`` are
    their neighbour-table rows, ``mask`` the kernel's bounce mask), applies
    the NEBB rebuild per boundary spec of ``cfg`` + collision + solid
    masking, and scatters the result over the kernel output ``out``.
    Exactness: the rebuild sees post-streaming / pre-collision values, same
    as the gather backend's in-line application.
    """
    from repro.kernels.stream_collide import nebb_stream_tiles

    with phase_scope("lbm.phase.boundary"):
        f_in = jnp.moveaxis(nebb_stream_tiles(
            f_pre, mask, tiles, rows, lat, a=cfg.a, interpret=interpret,
            node_order=cfg.node_order), 0, 1)               # (Q, B, n)
        for on_face, (_, spec) in zip(type_masks, cfg.boundaries):
            f_in = apply_open_boundary(f_in, on_face, spec, lat)
        f_out, _, _ = col.collide(f_in, lat, cfg.collision, cfg.force)
        f_out = jnp.where(solid[None], 0.0, f_out)
        return out.at[tiles].set(jnp.moveaxis(f_out, 0, 1))


class GatherBackend:
    """One-gather-per-direction streaming + jnp (or Pallas) collision.

    With ``cfg.split_stream`` the monolithic (Q, T, n) gather is replaced
    by the split-phase path (:func:`apply_split_stream`): static interior
    permutation + compact frontier tables.  Output is bitwise identical in
    'full' mode; in 'propagation_only' mode solid slots read zero instead
    of the monolithic path's (physically meaningless) bounce value.
    """

    name = "gather"

    def __init__(self, cfg, lat, tiling: Tiling, tables: StreamTables,
                 interpret: bool):
        self.cfg, self.lat, self.tiling, self.stream = cfg, lat, tiling, tables
        self.interpret = interpret
        self._bc_specs = tuple(spec for _, spec in cfg.boundaries)
        with obs.get_tracer().span("lbm.setup.backend_tables"):
            types = tiling.node_types                        # (T, n) canonical
            host = {
                "solid": types == SOLID,
                "bc_masks": tuple(types == tv for tv, _ in cfg.boundaries),
            }
            if cfg.split_stream:
                sp = tables.split
                host["split"] = {
                    "intra": sp.intra_idx,
                    "case": sp.case.astype(np.int32),
                    "is_cross": sp.is_cross,
                    "nbr": sp.nbr,
                    "bounce_dst": sp.bounce_dst,
                    "irregular_dst": sp.irregular_dst,
                    "irregular_src": sp.irregular_src,
                    "opp": sp.opp,
                    "perms": tables.perms,
                }
            else:
                host["gather"] = tables.gather_idx.reshape(lat.q, -1)
        self.tables = place(host)
        self._solid = self.tables["solid"]

    # ------------------------------------------------- layout shuffles
    def to_storage(self, f_canon: jnp.ndarray) -> jnp.ndarray:
        """canonical node order -> per-direction storage layout."""
        if self.cfg.layout_scheme == "xyz":
            return f_canon
        return jnp.stack(
            [f_canon[q][..., self.stream.inv_perms[q]]
             for q in range(self.lat.q)]
        )

    def canonical(self, f_store: jnp.ndarray) -> jnp.ndarray:
        if self.cfg.layout_scheme == "xyz":
            return f_store
        return jnp.stack(
            [f_store[q][..., self.stream.perms[q]] for q in range(self.lat.q)]
        )

    # ------------------------------------------------------------ step
    def initial_state(self, feq_canon: jnp.ndarray) -> jnp.ndarray:
        return self.to_storage(feq_canon)

    def _collide(self, f_in, solid):
        if self.cfg.use_kernel:
            from repro.kernels import ops as kops

            return kops.collide_tiles(
                f_in,
                solid,
                self.lat,
                self.cfg.collision,
                force=self.cfg.force,
                interpret=self.interpret,
            )
        f_out, _, _ = col.collide(f_in, self.lat, self.cfg.collision,
                                  self.cfg.force)
        return f_out

    def step(self, f_store: jnp.ndarray, tab: dict) -> jnp.ndarray:
        """One iteration; ``tab`` is :attr:`tables` (passed in, not closed
        over, so the compiled program does not embed the geometry)."""
        q = self.lat.q
        t, n = self.tiling.num_tiles, self.tiling.nodes_per_tile
        if self.cfg.kernel_mode == "rw_only":
            # paper §4.1: read + write the node's own data, no propagation
            return f_store + 0.0
        solid = tab["solid"]
        if "split" in tab:
            # split-phase: static interior perm + compact frontier tables
            f_in = apply_split_stream(f_store, solid, **tab["split"])
        else:
            # streaming + bounce-back: one gather per direction
            with phase_scope("lbm.phase.stream"):
                f_in = jnp.take(f_store.reshape(-1), tab["gather"],
                                axis=0).reshape(q, t, n)
        if self.cfg.kernel_mode == "propagation_only":
            return self.to_storage(f_in)
        # open boundaries (Zou-He NEBB / constant pressure)
        with phase_scope("lbm.phase.boundary"):
            for mask, spec in zip(tab["bc_masks"], self._bc_specs):
                f_in = apply_open_boundary(f_in, mask, spec, self.lat)
        with phase_scope("lbm.phase.collide"):
            f_out = self._collide(f_in, solid)
        with phase_scope("lbm.phase.pack"):
            f_out = jnp.where(solid[None], 0.0, f_out)
            return self.to_storage(f_out)

    # ------------------------------------------------- ensemble (B states)
    def ensemble_state(self, f_single: jnp.ndarray, batch: int) -> jnp.ndarray:
        """Replicate one storage state (Q, T, n) into (B, Q, T, n)."""
        return jnp.repeat(f_single[None], batch, axis=0)

    def ensemble_tables(self, batch: int) -> dict:
        """The batched step shares the single-state tables unchanged."""
        return self.tables

    def ensemble_step(self, fb: jnp.ndarray, tab: dict) -> jnp.ndarray:
        """One step for B independent states: vmap of the scalar step.

        All index tables (monolithic gather or split frontier tables) are
        unbatched arguments, loaded once for the whole batch.  Each
        replica is bitwise identical to an unbatched step (pinned in
        tests/test_sim_ensemble.py).
        """
        return jax.vmap(self.step, in_axes=(0, None))(fb, tab)

    def ensemble_canonical(self, fb: jnp.ndarray) -> jnp.ndarray:
        return jax.vmap(self.canonical)(fb)

    def ensemble_get(self, fb: jnp.ndarray, b: int) -> jnp.ndarray:
        """Extract replica ``b`` as a single-engine storage state."""
        return fb[b]

    def ensemble_set(self, fb: jnp.ndarray, b: int,
                     f_single: jnp.ndarray) -> jnp.ndarray:
        return fb.at[b].set(f_single.astype(fb.dtype))


class FusedBackend:
    """Persistent packed (T+1, Q, n) state + the fused Pallas kernel.

    The scratch tile at index T stays all-zero / all-SOLID; out-of-grid and
    empty neighbours point at it so bounce-back needs no branches.  Open
    boundaries are handled by a post-kernel masked pass: the NEBB
    reconstruction (which must see post-streaming, pre-collision values)
    re-streams ONLY the tiles containing boundary nodes from the pre-step
    state with the kernel's own pull over that tile list, applies the
    boundary rebuild + collision there, and scatters those tiles over the
    kernel output.  Whether the pass runs depends only on whether any tile
    holds a declared boundary type: periodic and closed geometries skip it.

    Every device op of :meth:`step` sits under one named scope: the
    kernel (``%stream_collide`` in a device trace) under
    ``lbm.phase.stream_collide``, its output buffer and the scratch-row
    reset under ``lbm.phase.pack``, the NEBB pass (its pull is
    ``%nebb_stream``) under ``lbm.phase.boundary``.
    """

    name = "fused"

    def __init__(self, cfg, lat, tiling: Tiling, tables: StreamTables,
                 interpret: bool):
        from repro.kernels.stream_collide import (blocks_per_tile,
                                                  build_bounce_mask,
                                                  build_neighbor_table)

        if cfg.layout_scheme != "xyz":
            raise ValueError(
                "backend='fused' keeps f in the kernel's packed tile layout; "
                f"layout_scheme must be 'xyz' (got {cfg.layout_scheme!r})")
        self.cfg, self.lat, self.tiling = cfg, lat, tiling
        self.interpret = interpret
        with obs.get_tracer().span("lbm.setup.backend_tables"):
            self._nbrs_np = build_neighbor_table(tiling, cfg.periodic)
            self._mask_np = build_bounce_mask(          # (T+1, 1, n)
                tiling.node_types, self._nbrs_np, lat, cfg.a, cfg.node_order)
            self._bc_np = (boundary_pass_tables(
                tiling.node_types, self._nbrs_np, cfg.boundaries)
                if cfg.boundaries and cfg.kernel_mode == "full" else None)
            host = self._host_tables(1)
        self._solid, self.tables = place(
            (tiling.node_types == SOLID, host))
        self._ens_tables: dict[int, dict] = {1: self.tables}
        reg = obs.get_metrics()
        if reg.enabled:
            reg.gauge("lbm.kernel.blocks_per_tile").set(
                blocks_per_tile(lat, cfg.a, cfg.node_order))
        if reg.enabled and self._bc_np is not None:
            b = len(self._bc_np[0])
            reg.gauge("lbm.nebb.tiles").set(b)
            reg.gauge("lbm.nebb.tile_share").set(b / tiling.num_tiles)

    # ------------------------------------------------------------ state
    def initial_state(self, feq_canon: jnp.ndarray) -> jnp.ndarray:
        """Pack once — the only canonical->packed shuffle in the engine."""
        q, t, n = feq_canon.shape
        f = jnp.zeros((t + 1, q, n), feq_canon.dtype)
        return f.at[:t].set(jnp.moveaxis(feq_canon, 0, 1))

    def canonical(self, f_packed: jnp.ndarray) -> jnp.ndarray:
        """Unpack for diagnostics only — never called from step/run."""
        return jnp.moveaxis(f_packed[:-1], 0, 1)       # (Q, T, n)

    # ------------------------------------------------------------ step
    def step(self, f: jnp.ndarray, tab: dict) -> jnp.ndarray:
        """One fused-kernel step over every tile row of ``f``.  ``tab`` is
        :attr:`tables` for a single state, or ``ensemble_tables(B)`` for a
        B-replicated one (one pallas_call grid over all B*T tiles)."""
        from repro.kernels.stream_collide import stream_collide_tiles

        cfg = self.cfg
        # scoped inside: lbm.phase.stream_collide, lbm.phase.pack
        out = stream_collide_tiles(
            f, tab["mask"], tab["nbrs"], self.lat, cfg.collision,
            a=cfg.a, force=cfg.force, interpret=self.interpret,
            mode=cfg.kernel_mode, node_order=cfg.node_order)
        if "bc" in tab:
            bc = tab["bc"]
            out = nebb_boundary_pass(
                f, out, tab["mask"], cfg, self.lat, self.interpret,
                bc["tiles"], bc["nbrs"], bc["type_masks"], bc["solid"])
        return out

    # the step is shape-generic: B is carried by the tables and the state
    ensemble_step = step

    # ------------------------------------------------- ensemble (B states)
    def ensemble_tables(self, batch: int) -> dict:
        """Device tables for a B-replicated packed state (B = 1 is the
        single-state :attr:`tables`), built and placed once per B.

        Replica b's tiles occupy rows [b*T, (b+1)*T); the single scratch
        row moves to index B*T.  The neighbour table gets the per-replica
        row offset folded in (scratch references remapped to B*T), and the
        NEBB boundary tiles and their neighbour rows get the same offsets,
        so :func:`nebb_boundary_pass` runs unmodified over all replicas'
        boundary tiles in one pass.
        """
        if batch not in self._ens_tables:
            with obs.get_tracer().span("lbm.setup.backend_tables"):
                host = self._host_tables(batch)
            self._ens_tables[batch] = place(host)
        return self._ens_tables[batch]

    def _host_tables(self, batch: int) -> dict:
        """The numpy tables :meth:`ensemble_tables` places."""
        t = self.tiling.num_tiles

        def replicate(nb):
            """Neighbour rows of every replica: row offset b*T, scratch
            references remapped to B*T."""
            return np.concatenate(
                [np.where(nb == t, batch * t, nb + b * t)
                 for b in range(batch)]).astype(np.int32)

        mask = np.concatenate([self._mask_np[:t]] * batch
                              + [self._mask_np[t:]])
        tab = {"mask": mask, "nbrs": replicate(self._nbrs_np)}
        if self._bc_np is not None:
            bt, rows, type_masks, solid_b = self._bc_np
            tab["bc"] = {
                "tiles": np.concatenate(
                    [bt + b * t for b in range(batch)]).astype(np.int32),
                "nbrs": replicate(rows),
                "type_masks": np.concatenate([type_masks] * batch, axis=1),
                "solid": np.concatenate([solid_b] * batch),
            }
        return tab

    def ensemble_state(self, f_single: jnp.ndarray, batch: int) -> jnp.ndarray:
        """(T+1, Q, n) packed state -> (B*T + 1, Q, n) B-replicated."""
        return jnp.concatenate([f_single[:-1]] * batch + [f_single[-1:]])

    def ensemble_canonical(self, f: jnp.ndarray) -> jnp.ndarray:
        """(B*T + 1, Q, n) -> (B, Q, T, n) for diagnostics."""
        t = self.tiling.num_tiles
        batch = (f.shape[0] - 1) // t
        return jnp.swapaxes(f[:-1].reshape(batch, t, *f.shape[1:]), 1, 2)

    def ensemble_get(self, f: jnp.ndarray, b: int) -> jnp.ndarray:
        """Extract replica ``b`` as a single-engine packed state (own zero
        scratch row appended)."""
        t = self.tiling.num_tiles
        body = jax.lax.dynamic_slice_in_dim(f, b * t, t, axis=0)
        return jnp.concatenate([body, jnp.zeros_like(f[:1])])

    def ensemble_set(self, f: jnp.ndarray, b: int,
                     f_single: jnp.ndarray) -> jnp.ndarray:
        t = self.tiling.num_tiles
        return jax.lax.dynamic_update_slice(
            f, f_single[:-1].astype(f.dtype), (b * t, 0, 0))
