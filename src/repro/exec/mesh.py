"""Mesh execution backend: the fused DP under ``shard_map`` on a device mesh.

Wraps the column-batched all-gather SpMM and streamed eMA of
:mod:`repro.core.distributed`: vertices are 1-D row-partitioned across
every mesh axis, each DP stage broadcasts the passive M matrix in
``column_batch``-column slices (each collective serving all ``B`` chunked
colorings at once), and the eMA stays vertex-local.  The DP schedule —
canonical sharing and the liveness plan — comes from the engine's bound
:class:`~repro.plan.ir.TemplatePlan`; split tables are built once per plan
at construction, de-duplicated by ``(k, m, m_a)``, and closure-captured by
the shard_map program.

Each stage's collective runs in one of two modes, decided at plan time by
``CostModel.comm_schedule`` (overridable via ``REPRO_MESH_COMM`` or the
``mesh_comm=`` engine kwarg):

* ``blocking`` — one ``all_gather`` per column batch, then the edge
  segment-sum consumes the full buffer (the paper's synchronous scheme);
* ``pipelined`` — the double-buffered ring: per-shard row slices of the
  batch circulate via ``lax.ppermute``, the next slice in flight while the
  current one's edge bucket is consumed as a partial segment-sum.  Counts
  are bit-exact vs blocking: on bucketed single-axis meshes BOTH modes
  fold the same per-source-shard partial sums in the same ring order,
  blocking merely reading each owner's rows out of its one all-gathered
  buffer (see ``repro.core.distributed.make_batched_count_fn``).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .base import EngineBackend
from .select import mesh_comm_mode

__all__ = ["MeshBackend", "BagPlanUnsupported", "auto_axes"]


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` over the same devices with every axis ``Auto``.

    ``jax.make_mesh`` gives Explicit axes, whose sharding-in-types rules
    require every jit that touches them to run under ``jax.set_mesh``.  The
    DP program places its own data (``shard_map`` with full in/out specs),
    so it runs on Auto axes and callers never need a mesh context.
    """
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


class BagPlanUnsupported(NotImplementedError):
    """The mesh backend cannot execute bag (non-tree) plans.

    Structured for the serving layer: ``invalid_request`` routes it to the
    ``invalid`` failure family (``serve.resilience.classify_failure``) — a
    malformed *query*, not a poisoned engine key, so quarantine never
    strikes for it.
    """

    invalid_request = True

    def __init__(self, decomposition_widths):
        self.decomposition_widths = tuple(decomposition_widths)
        super().__init__(
            "backend='mesh' does not execute bag (non-tree) plans yet — "
            f"plan decomposition widths {self.decomposition_widths} include "
            "non-tree bags (width > 1); multi-axis bag states need a 2-D "
            "sharding story. Use a local backend for non-tree templates."
        )


class MeshBackend(EngineBackend):
    """Distributed backend (see module docstring).

    Args (via ``CountingEngine(...)``):
      mesh: the ``jax.sharding.Mesh`` to run on (required).
      column_batch: passive columns per collective; ``None`` auto-sizes via
        the cost model (``min(128, max passive columns)``).
      ema_mode: ``"streamed"`` (default — fused per-batch SpMM->eMA, the B
        matrix never materializes) or ``"loop"`` (paper-faithful Algorithm
        5 with the SpMM product memoized per canonical passive form).
      gather_dtype: optional wire dtype for compressed collectives
        (e.g. ``jnp.bfloat16``); accumulation stays fp32.
      balance_degrees: relabel vertices round-robin by degree rank before
        sharding (spreads hub rows; colorings are permuted to follow, so
        counts are unchanged).  Default True: the always-on src-bucketed
        edge layout pads every shard's buckets to the largest one, and on
        skewed graphs an unbalanced hub shard inflates that stride several
        fold — balancing makes the bucketed layout *smaller* than the
        unbucketed unbalanced one.
      comm: ``"blocking"`` | ``"pipelined"`` | ``None`` (auto).  Explicit
        beats the ``REPRO_MESH_COMM`` env override beats the cost model's
        per-stage ``comm_schedule`` decision.  A forced ``pipelined`` that
        the geometry cannot support (single shard, multi-axis mesh,
        non-streamed eMA) falls back to blocking with the reason recorded
        in ``describe_comm()``.
    """

    name = "mesh"

    # every chunk launch dispatches collectives, so the mesh backend
    # exposes the extra failure surface to the fault seam; the pipelined
    # path visits the site once per ring step (collective_dispatches)
    fault_sites = ("launch", "collective")

    def __init__(
        self,
        engine,
        mesh,
        *,
        column_batch: Optional[int] = None,
        ema_mode: str = "streamed",
        gather_dtype=None,
        balance_degrees: bool = True,
        comm: Optional[str] = None,
    ):
        super().__init__(engine)
        if engine.plan_ir.has_bag_stages:
            raise BagPlanUnsupported(engine.plan_ir.decomposition_widths)
        if mesh is None:
            raise ValueError("backend='mesh' needs a jax.sharding.Mesh (mesh=...)")
        if comm not in (None, "blocking", "pipelined"):
            raise ValueError(f"unknown mesh comm mode {comm!r}")
        from repro.core.distributed import make_batched_count_fn, shard_graph

        mesh = auto_axes(mesh)
        self.mesh = mesh
        self.ema_mode = ema_mode
        self.gather_dtype = gather_dtype
        n_shards = int(np.prod(mesh.devices.shape))
        # always the src-bucketed layout: blocking and pipelined engines
        # then run over literally the same edge arrays (the precondition
        # for their bit-exact A/B) and either mode can bind per stage
        self.sharded = shard_graph(
            engine.graph, n_shards, balance_degrees=balance_degrees,
            bucket_by_src=True,
        )
        if column_batch is None:
            column_batch = engine.cost.pick_mesh_column_batch()
        self.column_batch = int(column_batch)

        # -- comm resolution: explicit > env > cost model ---------------------
        forced = comm
        source = "explicit" if comm is not None else None
        if forced is None:
            forced = mesh_comm_mode()
            if forced is not None:
                source = "env"
        if source is None:
            source = "cost-model"
        eligible, why = self._pipeline_eligibility(n_shards)
        self.comm_fallback_reason = None
        if forced == "pipelined" and not eligible:
            self.comm_fallback_reason = why
            forced = "blocking"
        schedules = engine.cost.mesh_comm_schedules(
            n_shards,
            column_batch=self.column_batch,
            rows_per_shard=self.sharded.rows_per_shard,
            edges_per_shard=self.sharded.edges_per_shard,
            forced=forced,
        )
        if forced is None and not eligible:
            # the auto decision may not pick pipelined for ineligible
            # geometry either — re-force blocking and record why
            if any(s.mode == "pipelined" for s in schedules.values()):
                self.comm_fallback_reason = why
            schedules = engine.cost.mesh_comm_schedules(
                n_shards,
                column_batch=self.column_batch,
                rows_per_shard=self.sharded.rows_per_shard,
                edges_per_shard=self.sharded.edges_per_shard,
                forced="blocking",
            )
        self.comm_source = source
        self.comm_schedules = schedules
        # leader decisions expand to every member stage (one sweep each on
        # the mesh target; members inherit their leader's mode)
        stage_modes = {}
        for leader, sched in schedules.items():
            for member in engine.plan_ir.exec_groups[leader]:
                stage_modes[member] = sched.mode
        self.stage_comm_modes = stage_modes
        any_pipelined = any(m == "pipelined" for m in stage_modes.values())
        self.comm = "pipelined" if any_pipelined else "blocking"
        #: fault-seam dispatch multiplicity: the pipelined path crosses the
        #: ``collective`` injection site once per ring step
        self.collective_dispatches = n_shards if any_pipelined else 1

        self._count_fn = make_batched_count_fn(
            engine.plans,
            mesh,
            self.sharded.n_padded,
            self.sharded.edges_per_shard,
            column_batch=self.column_batch,
            ema_mode=ema_mode,
            gather_dtype=gather_dtype,
            plan_ir=engine.plan_ir,
            store_dtype=engine.policy.store_dtype,
            accum_dtype=engine.policy.accum_dtype,
            comm_mode="blocking",
            comm_schedule=stage_modes,
            bucket_stride=self.sharded.bucket_stride,
        )
        # each device holds its own edge partition; the relabel is replicated
        by_shard = NamedSharding(mesh, P(tuple(mesh.axis_names)))
        self._src = jax.device_put(self.sharded.src, by_shard)
        self._dst_local = jax.device_put(self.sharded.dst_local, by_shard)
        self._edge_mask = jax.device_put(self.sharded.edge_mask, by_shard)
        # colorings follow the degree-balancing relabel (scatter old -> new;
        # new ids range over [0, n_padded) with pad slots interleaved)
        self._perm = (
            jax.device_put(self.sharded.perm, NamedSharding(mesh, P()))
            if self.sharded.perm is not None
            else None
        )

    def _pipeline_eligibility(self, n_shards: int):
        """Whether this geometry can run the ring at all — ``(ok, why)``."""
        if self.ema_mode != "streamed":
            return False, (
                f"ema_mode={self.ema_mode!r} — the ring consumes slices "
                "inside the fused streamed sweep only"
            )
        if len(self.mesh.axis_names) != 1:
            return False, (
                f"mesh axes {tuple(self.mesh.axis_names)} — the ring "
                "circulates a single axis"
            )
        if n_shards < 2:
            return False, "single shard — nothing to overlap"
        return True, None

    def describe_comm(self) -> dict:
        """The resolved comm plan, for ``describe()`` / the plan
        inspector."""
        out = {
            "mode": self.comm,
            "source": self.comm_source,
            "collective_dispatches": self.collective_dispatches,
            "bucket_stride": self.sharded.bucket_stride,
            "schedule": [s.describe() for _, s in sorted(self.comm_schedules.items())],
        }
        if self.comm_fallback_reason:
            out["fallback_reason"] = self.comm_fallback_reason
        return out

    def counts_for_colors(self, colors: jnp.ndarray) -> jnp.ndarray:
        colors = jnp.asarray(colors)
        if self._perm is not None:
            padded = jnp.zeros((colors.shape[0], self.sharded.n_padded), colors.dtype)
            colors = padded.at[:, self._perm].set(colors)
        else:
            pad = self.sharded.n_padded - colors.shape[1]
            if pad:
                colors = jnp.pad(colors, ((0, 0), (0, pad)))
        return self._count_fn(colors, self._src, self._dst_local, self._edge_mask)

    # -- memory-model geometry (per shard!) ----------------------------------

    def transient_elements(self) -> int:
        """Per-shard collective scratch.

        Blocking: one all-gathered column batch (``n_padded *
        column_batch``) plus the per-shard edge message gather
        (``edges_per_shard * column_batch``).  Pipelined: the gathered
        buffer shrinks to the two ring slots (``2 * rows_per_shard *
        column_batch``) and the edge scratch to one source-shard bucket's
        partial messages (``edges_per_shard / n_shards``, dead after each
        per-bucket segment-sum) — the per-shard byte win the fig13 rows
        track.
        """
        if self.comm == "pipelined":
            return self.engine.cost.mesh_transient_elements(
                2 * self.sharded.rows_per_shard,
                max(1, self.sharded.edges_per_shard // self.sharded.n_shards),
                self.column_batch,
            )
        return self.engine.cost.mesh_transient_elements(
            self.sharded.n_padded, self.sharded.edges_per_shard, self.column_batch
        )

    def resident_elements(self) -> int:
        """Per-shard live DP state: local rows times the liveness-aware
        peak of padded M columns under the shared multi-template schedule."""
        return self.engine.cost.mesh_resident_elements(
            self.sharded.rows_per_shard, self.column_batch, self.ema_mode
        )
