"""SparseTiledLBM — the paper's solver as a composable JAX module.

One LBM iteration (paper Algorithm 2, fused): pull-streaming (with half-way
bounce-back folded into the gather tables / the kernel's solid-source test),
open-boundary reconstruction, collision, solid masking.  Two copies of f are
kept implicitly by functional purity + buffer donation (the paper's explicit
f / f' pair).

The step itself is pluggable (``LBMConfig.backend``, see
``repro.core.backends``):

* ``backend="gather"`` — one jnp gather per direction over the
  per-direction storage layout; the collision math alone can be swapped for
  the Pallas collision kernel with ``use_kernel=True`` (NOT the paper's
  fused kernel — the state still round-trips through pack/unpack inside
  ``repro.kernels.ops.collide_tiles`` each step).  ``split_stream=True``
  replaces the monolithic (Q, T, n) index table with split-phase
  streaming: a static (Q, n) interior permutation broadcast over tiles
  plus compact frontier tables (~10x less indirection-table traffic,
  bitwise-identical streaming — see ``repro.core.streaming``).
* ``backend="fused"`` — the paper's fused Pallas stream+collide kernel
  (``repro.kernels.stream_collide``) over state held persistently in the
  kernel's packed (T+1, Q, n) layout: packed once at init, unpacked only in
  diagnostics, zero layout shuffles inside ``step``/``run``.

The same engine runs:
* on CPU for validation (Pallas kernels in interpret mode), and compiled
  on the TPU (``repro.kernels.ops.default_interpret``),
* distributed via ``repro.dist.lbm.ShardedLBM`` (slab decomposition of the
  tile grid — the multi-GPU extension the paper leaves as future work),
  which composes its halo exchange with either backend per slab.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.ops import resolve_interpret

from . import collision as col
from .backends import BACKENDS, make_backend
from .boundary import BoundarySpec
from .lattice import get_lattice
from .streaming import build_stream_tables
from .tiling import Tiling, tile_geometry, untile


@dataclasses.dataclass(frozen=True)
class LBMConfig:
    lattice: str = "D3Q19"
    collision: col.CollisionConfig = dataclasses.field(
        default_factory=col.CollisionConfig
    )
    a: int = 4                                # nodes per tile edge
    # tile traversal policy: 'zmajor' | 'morton' | 'hilbert' | 'morton_slab'
    # (repro.core.tiling.TILE_ORDERS).  Physics-neutral; reshapes the
    # spatial locality of the tile storage order.  ShardedLBM additionally
    # requires a slab-compatible ordering (zmajor / morton_slab).
    tile_order: str = "zmajor"
    # within-tile node enumeration: 'canonical' | 'sfc' | 'frontier_last'
    # (repro.core.tiling.NODE_ORDERS).  Physics-neutral like tile_order;
    # 'frontier_last' sorts tile-face nodes into a contiguous suffix per
    # tile so the split-phase frontier scatter touches dense ranges.
    node_order: str = "canonical"
    # split-phase streaming (gather backend only): replace the monolithic
    # (Q, T, n) gather table with a static (Q, n) interior permutation +
    # compact frontier tables (see repro.core.streaming.SplitStreamTables).
    # Bitwise identical physics; ~10x less index-table traffic.
    split_stream: bool = False
    layout_scheme: str = "xyz"                # 'xyz' | 'paper' | ...
    dtype: str = "float32"
    periodic: tuple[bool, bool, bool] = (False, False, False)
    # map node-type value -> open-boundary spec (walls need no spec:
    # bounce-back is implicit for SOLID neighbours)
    boundaries: tuple[tuple[int, BoundarySpec], ...] = ()
    force: tuple[float, float, float] | None = None
    rho0: float = 1.0
    u0: tuple[float, float, float] = (0.0, 0.0, 0.0)
    backend: str = "gather"                   # 'gather' | 'fused'
    use_kernel: bool = False                  # gather backend: Pallas collision
    # Pallas interpret mode: None = auto (interpret on cpu, compile on tpu)
    kernel_interpret: bool | None = None
    # paper §4.1 kernel variants: 'full' | 'propagation_only' | 'rw_only'
    kernel_mode: str = "full"


class SparseTiledLBM:
    """Sparse tiled LBM engine (the paper's contribution)."""

    def __init__(self, node_type: np.ndarray, cfg: LBMConfig):
        assert cfg.backend in BACKENDS, cfg.backend
        if cfg.split_stream and cfg.backend != "gather":
            raise ValueError(
                "split_stream restructures the gather backend's streaming; "
                f"backend must be 'gather' (got {cfg.backend!r} — the fused "
                "kernel already computes its pull indices from static "
                "tables)")
        self.cfg = cfg
        self.lat = get_lattice(cfg.lattice)
        self.dtype = jnp.dtype(cfg.dtype)
        self.kernel_interpret = resolve_interpret(cfg.kernel_interpret)
        # set-up spans: the backend adds lbm.setup.backend_tables and
        # lbm.setup.place; placements are awaited only when recording
        tr = obs.get_tracer()
        with tr.span("lbm.setup", backend=cfg.backend):
            with tr.span("lbm.setup.tiling"):
                self.tiling: Tiling = tile_geometry(
                    node_type, cfg.a, order=cfg.tile_order,
                    node_order=cfg.node_order)
            with tr.span("lbm.setup.stream_tables"):
                self.tables = build_stream_tables(
                    self.tiling, self.lat, cfg.layout_scheme, cfg.periodic,
                    split=cfg.split_stream,
                )
            self.backend = make_backend(cfg.backend, cfg, self.lat,
                                        self.tiling, self.tables,
                                        self.kernel_interpret)
            self._solid = self.backend._solid                # (T, n) canonical
            with tr.span("lbm.setup.initial_state"):
                self.f = self.backend.initial_state(self._initial_feq())
                if tr.enabled:
                    jax.block_until_ready(self.f)
        self._step_fn = jax.jit(self.backend.step, donate_argnums=0)
        self._multi_cache: dict[int, callable] = {}

    # ------------------------------------------------------------------ init
    def _initial_feq(self) -> jnp.ndarray:
        t, n = self.tiling.num_tiles, self.tiling.nodes_per_tile
        rho = jnp.full((t, n), self.cfg.rho0, dtype=self.dtype)
        u = jnp.broadcast_to(
            jnp.asarray(self.cfg.u0, self.dtype)[:, None, None], (3, t, n)
        )
        feq = col.equilibrium(rho, u, self.lat, self.cfg.collision.fluid)
        return jnp.where(self._solid[None], 0.0, feq)        # (Q, T, n)

    def reset(self) -> None:
        """Re-initialise f to the equilibrium state (t = 0).

        Lets callers warm/compile with a full ``run(steps)`` and then time
        (or measure physics over) EXACTLY ``steps`` iterations from t=0
        instead of 2x steps (launch.lbm.run_local).
        """
        self.f = self.backend.initial_state(self._initial_feq())

    # -------------------------------------------------------------- ensemble
    def ensemble(self, batch: int):
        """B independent flow states over THIS engine's tiling and stream
        tables, advanced in one dispatch per step (``repro.sim.ensemble``).

        The returned :class:`~repro.sim.ensemble.EnsembleLBM` shares the
        engine's geometry products (tiling, streaming tables, backend
        tables) — only the state carries a batch axis — which is exactly
        the amortisation the follow-up paper (arXiv:1703.08015) shows the
        sparse indirection tables need.
        """
        from repro.sim.ensemble import EnsembleLBM

        return EnsembleLBM(self, batch)

    # ------------------------------------------------------------------ step
    def step(self, steps: int = 1) -> None:
        for _ in range(steps):
            self.f = self._step_fn(self.f, self.backend.tables)
        reg = obs.get_metrics()
        if reg.enabled:
            reg.counter("lbm.step_total").inc(steps)

    def run_fn(self, steps: int):
        """The jitted ``(f, tables) -> f`` program :meth:`run` calls:
        ``steps`` iterations inside one fori_loop, ``f`` donated."""
        if steps not in self._multi_cache:
            self._multi_cache[steps] = jax.jit(
                lambda f, tab: jax.lax.fori_loop(
                    0, steps, lambda i, x: self.backend.step(x, tab), f
                ),
                donate_argnums=0,
            )
        return self._multi_cache[steps]

    def run(self, steps: int) -> None:
        """Run ``steps`` iterations inside a single jitted fori_loop."""
        fn = self.run_fn(steps)
        with obs.get_tracer().span("lbm.run", steps=steps):
            self.f = fn(self.f, self.backend.tables)
        reg = obs.get_metrics()
        if reg.enabled:
            reg.counter("lbm.step_total").inc(steps)

    # ----------------------------------------------------------- diagnostics
    def macroscopics(self):
        f_canon = self.backend.canonical(self.f)
        rho, u = col.macroscopics(f_canon, self.lat, self.cfg.collision.fluid)
        rho = jnp.where(self._solid, self.cfg.rho0, rho)
        u = jnp.where(self._solid[None], 0.0, u)
        return rho, u

    def fields_dense(self):
        """(rho, u) scattered back to the dense padded grid (numpy)."""
        rho, u = self.macroscopics()
        rho_d = untile(self.tiling, np.asarray(rho), fill=np.nan)
        u_d = untile(self.tiling, np.asarray(u), fill=0.0)
        return rho_d, u_d

    def total_mass(self) -> float:
        f_canon = self.backend.canonical(self.f)
        fluid = ~self._solid
        return float(jnp.sum(jnp.where(fluid[None], f_canon, 0.0)))

    # ------------------------------------------------------------ accounting
    @property
    def n_fluid_nodes(self) -> int:
        return self.tiling.n_fluid_nodes

    def bytes_per_step(self) -> int:
        """Eqn (10) minimum scaled by tile storage (incl. solid slots)."""
        n_d = self.dtype.itemsize
        stored = self.tiling.num_tiles * self.tiling.nodes_per_tile
        return 2 * self.lat.q * n_d * stored

    def index_bytes_per_step(self) -> int:
        """Indirection-table bytes the step loads besides f itself.

        gather backend: the (Q, T, n) int32 table — or the compact split
        tables under ``split_stream``.  fused backend: the (T, 27)
        neighbour table plus the static (Q, n) pull perms/cases.
        """
        q, n = self.lat.q, self.tiling.nodes_per_tile
        t = self.tiling.num_tiles
        if self.cfg.backend == "fused":
            return 27 * t * 4 + q * n * 4 + q * n * 1
        if self.cfg.split_stream:
            return self.tables.split.index_bytes
        return self.tables.index_bytes_mono

    def mflups(self, seconds_per_step: float) -> float:
        return self.n_fluid_nodes / seconds_per_step / 1e6

    def model_metrics(self) -> dict[str, float]:
        """Modelled per-step quantities under the CANONICAL metric names
        (``repro.obs.metrics.CATALOGUE``).

        Everything here is computed from static host tables — no jit, no
        device work, fully deterministic for deterministic geometries —
        which is what lets ``benchmarks/regression_gate.py`` gate on these
        numbers in CPU CI, and lets the dry-run report and the measured
        runtime share one naming scheme (modelled-vs-measured comparison
        is a single key join).
        """
        q, nf = self.lat.q, self.n_fluid_nodes
        min_bytes = 2 * q * nf * self.dtype.itemsize     # paper Eqn (10)
        idx = self.index_bytes_per_step()
        actual = self.bytes_per_step() + idx
        t = self.tables
        return {
            "lbm.bw.eqn10_min_bytes": float(min_bytes),
            "lbm.bw.eqn10_fraction": min_bytes / max(1, actual),
            "lbm.bytes.model_per_node": actual / max(1, nf),
            "lbm.index.bytes_per_node": idx / max(1, nf),
            "lbm.stream.interior_frac": float(t.interior_frac),
            "lbm.stream.frontier_frac": float(t.frontier_frac),
            "lbm.stream.bounce_frac": float(t.bounce_frac),
            "lbm.tiles.utilisation": float(self.tiling.tile_utilisation),
        }
