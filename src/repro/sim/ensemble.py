"""EnsembleLBM — B independent flow states over one geometry's tables.

The paper's central cost on sparse geometries is indirection-table
bandwidth during propagation (and the follow-up, arXiv:1703.08015, shows
the tables *dominate* as sparsity grows).  Batching B states over ONE
tiling / ONE set of (split-)stream tables amortises that traffic: on the
gather backend every index table is an unbatched argument under vmap, so
index-bytes **per node update** fall exactly as 1/B; on the fused backend
the (T, 27) neighbour table is replicated per replica and only the static
(Q, n) pull tables amortise (``index_bytes_per_step`` accounts per
backend; ``benchmarks/ensemble_scaling.py`` reports both columns).

Batch representation is backend-owned (``repro.core.backends``):

* gather — ``f`` carries a leading batch axis ``(B, Q, T, n)``;
  ``ensemble_step`` is ``jax.vmap`` of the scalar step, and each replica
  stays BITWISE identical to an independent engine.
* fused — the packed tile axis is replicated: ``(B*T + 1, Q, n)`` with
  per-replica offsets folded into the neighbour table and one shared
  scratch row, so a single pallas_call advances every replica (parity to
  an independent engine is 1e-12 in float64, like the fused-vs-gather
  parity itself).

Replica slots are independently settable/readable (``set_replica`` /
``replica_canonical``), which is what lets :mod:`repro.sim.service` treat
them as fixed session slots in the style of ``repro.serve.engine``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import collision as col
from repro.core.engine import SparseTiledLBM


class EnsembleLBM:
    """Batched stepping over a shared :class:`SparseTiledLBM`.

    The wrapped engine provides every geometry product (tiling, stream
    tables, backend tables) and its own state is untouched; the ensemble
    owns only the batched state ``self.f`` and its jitted step.
    """

    def __init__(self, engine: SparseTiledLBM, batch: int):
        if batch < 1:
            raise ValueError(f"batch must be >= 1 (got {batch})")
        if engine.cfg.backend == "gather" and engine.cfg.use_kernel:
            raise ValueError(
                "ensemble stepping on the gather backend requires "
                "use_kernel=False (vmap over the Pallas collision kernel is "
                "not supported); use backend='fused' for a kernelised "
                "ensemble")
        self.engine = engine
        self.batch = batch
        self.backend = engine.backend
        self._feq_single = None          # lazily built template state
        self.f = self.backend.ensemble_state(self._template(), batch)
        self._tables = self.backend.ensemble_tables(batch)
        self._step_fn = jax.jit(self.backend.ensemble_step, donate_argnums=0)
        self._multi_cache: dict[int, callable] = {}

    # ------------------------------------------------------------- plumbing
    @property
    def cfg(self):
        return self.engine.cfg

    @property
    def tiling(self):
        return self.engine.tiling

    @property
    def lat(self):
        return self.engine.lat

    def _template(self) -> jnp.ndarray:
        """Single-engine equilibrium state in the backend's layout."""
        if self._feq_single is None:
            self._feq_single = self.backend.initial_state(
                self.engine._initial_feq())
        return self._feq_single

    # ----------------------------------------------------------------- step
    def step(self, steps: int = 1) -> None:
        tr = obs.get_tracer()
        with tr.span("lbm.ensemble.step", batch=self.batch, steps=steps):
            for _ in range(steps):
                self.f = self._step_fn(self.f, self._tables)
        reg = obs.get_metrics()
        if reg.enabled:
            reg.counter("lbm.step_total").inc(steps)

    def run(self, steps: int) -> None:
        """``steps`` iterations for all replicas inside one jitted
        fori_loop (single dispatch for the whole measurement window)."""
        if steps not in self._multi_cache:
            fn = jax.jit(
                lambda f, tab: jax.lax.fori_loop(
                    0, steps, lambda i, x: self.backend.ensemble_step(x, tab),
                    f),
                donate_argnums=0,
            )
            self._multi_cache[steps] = fn
        with obs.get_tracer().span("lbm.ensemble.run", batch=self.batch,
                                   steps=steps):
            self.f = self._multi_cache[steps](self.f, self._tables)
        reg = obs.get_metrics()
        if reg.enabled:
            reg.counter("lbm.step_total").inc(steps)

    # ------------------------------------------------------------ state i/o
    def reset(self, b: int | None = None) -> None:
        """Reset one replica (or all of them) to the equilibrium state."""
        if b is None:
            self.f = self.backend.ensemble_state(self._template(), self.batch)
        else:
            self.f = self.backend.ensemble_set(self.f, b, self._template())

    def set_replica(self, b: int, f_canon) -> None:
        """Seat replica ``b`` from a CANONICAL (Q, T, n) state (the layout
        ``replica_canonical`` returns and checkpoints store)."""
        f_single = self.backend.initial_state(
            jnp.asarray(f_canon, self.engine.dtype))
        self.f = self.backend.ensemble_set(self.f, b, f_single)

    def replica_canonical(self, b: int) -> jnp.ndarray:
        """Replica ``b`` as a canonical (Q, T, n) array."""
        return self.backend.canonical(self.backend.ensemble_get(self.f, b))

    def canonical(self) -> jnp.ndarray:
        """All replicas, canonical: (B, Q, T, n)."""
        return self.backend.ensemble_canonical(self.f)

    # ----------------------------------------------------------- diagnostics
    def macroscopics(self, b: int | None = None):
        """(rho, u) for replica ``b`` — or for all replicas with a leading
        batch axis when ``b`` is None."""
        solid = self.backend._solid                      # (T, n)
        if b is not None:
            f_canon = self.replica_canonical(b)
            rho, u = col.macroscopics(f_canon, self.lat,
                                      self.cfg.collision.fluid)
            return (jnp.where(solid, self.cfg.rho0, rho),
                    jnp.where(solid[None], 0.0, u))
        f_canon = self.canonical()
        rho, u = jax.vmap(
            lambda f: col.macroscopics(f, self.lat,
                                       self.cfg.collision.fluid))(f_canon)
        return (jnp.where(solid[None], self.cfg.rho0, rho),       # (B, T, n)
                jnp.where(solid[None, None], 0.0, u))             # (B, 3, T, n)

    def total_mass(self) -> np.ndarray:
        """Per-replica total mass, shape (B,)."""
        f_canon = self.canonical()                       # (B, Q, T, n)
        fluid = ~self.backend._solid
        return np.asarray(
            jnp.sum(jnp.where(fluid[None, None], f_canon, 0.0),
                    axis=(1, 2, 3)))

    def replica_mass(self, b: int) -> float:
        """Total mass of ONE replica — O(Q*T*n), not O(B*Q*T*n) like
        ``total_mass`` (the service reads a single slot's mass on every
        seat/finish)."""
        f_canon = self.replica_canonical(b)
        fluid = ~self.backend._solid
        return float(jnp.sum(jnp.where(fluid[None], f_canon, 0.0)))

    # ------------------------------------------------------------ accounting
    @property
    def n_fluid_nodes(self) -> int:
        """Fluid nodes PER REPLICA (multiply by ``batch`` for aggregate)."""
        return self.engine.n_fluid_nodes

    def aggregate_mflups(self, seconds_per_step: float) -> float:
        """Million fluid-node updates/s across ALL replicas."""
        return self.batch * self.n_fluid_nodes / seconds_per_step / 1e6

    def index_bytes_per_step(self) -> int:
        """Indirection-table bytes ONE batched step actually loads.

        gather: every table (monolithic gather or split frontier tables)
        is an unbatched argument under vmap — one copy serves all B
        replicas, so the figure equals the single-engine one.  fused: the
        (T, 27) neighbour table is materialised PER REPLICA
        (``FusedBackend.ensemble_tables``), so that term scales with B;
        only the static (Q, n) pull perms/cases stay a single copy.
        """
        if self.cfg.backend == "fused":
            # the engine's figure plus (B-1) extra neighbour-table copies
            # (27 int32 entries per tile) — derived, not duplicated, from
            # SparseTiledLBM.index_bytes_per_step so the accounting has
            # one source of truth
            extra_nbr = 27 * self.tiling.num_tiles * 4
            return (self.engine.index_bytes_per_step()
                    + (self.batch - 1) * extra_nbr)
        return self.engine.index_bytes_per_step()

    def index_bytes_per_node_update(self) -> float:
        """Indirection-table bytes loaded per fluid-node update.

        For the gather backend this falls exactly as 1/B (the
        amortisation the ensemble exists for); for the fused backend only
        the static pull tables amortise — the per-replica neighbour-table
        term is the floor it approaches.
        """
        return (self.index_bytes_per_step()
                / (self.batch * max(1, self.n_fluid_nodes)))
