"""Single-device execution backends for the fused counting pipeline.

Every local backend shares one DP executor (:class:`LocalBackend.
counts_for_colors`) that walks the engine's bound
:class:`~repro.plan.ir.TemplatePlan` — stage order, canonical sharing,
shared-passive exec groups, and the liveness schedule all come from the
plan IR; subclasses only supply the column-slice neighbor reduction
:meth:`LocalBackend.spmm` (or, for the fused Pallas kernel, override
:meth:`~repro.exec.base.EngineBackend.aggregate_ema` outright).
"""

from __future__ import annotations

from typing import Callable, Dict

import numpy as np

import jax
import jax.numpy as jnp

from repro import tracing
from repro.core.counting import fused_aggregate_ema_grouped
from repro.core.graph import BLOCKED_BLOCK_SIZE, build_sell
from repro.plan.cost import EDGE_CHUNK

from .base import (
    BagStageTables,
    EngineBackend,
    StageTables,
    build_bag_tables,
    build_stage_tables,
)

__all__ = [
    "LocalBackend",
    "EdgesBackend",
    "EllBackend",
    "SellBackend",
    "DenseBackend",
    "BlockedEllBackend",
    "CustomBackend",
    "MixedBackend",
    "LOCAL_BACKEND_CLASSES",
    "SELL_GROUP_SIZE",
]

#: Degree-sorted rows per SELL group (smaller = tighter padding).
SELL_GROUP_SIZE = 128


class LocalBackend(EngineBackend):
    """Shared single-device fused DP: subclasses only supply :meth:`spmm`.

    The multi-template DP walks every plan's stages with DP states memoized
    by rooted canonical form, all M matrices in the fused ``(n, B, C)``
    layout.  Each stage runs through the shared streamed
    :meth:`aggregate_ema` (passive column batches aggregated and consumed
    one at a time), and states are dropped at their liveness-scheduled last
    read — the aggregate product ``A_G @ M_p`` never exists.
    """

    def __init__(self, engine, shared: "LocalBackend" = None):
        super().__init__(engine)
        # Bucketed per-batch tables feed the local fused executor and the
        # Pallas kernel (the mesh backend builds its own streamed tables
        # at its own all-gather column batch).  A MixedBackend's sub-impls
        # pass ``shared=`` to alias the owner's tables instead of shipping
        # a second copy of every split table to the device.
        if shared is not None:
            self.stage_tables: Dict = shared.stage_tables
            self.bag_tables: Dict = shared.bag_tables
            self._bag_adj = shared._bag_adj
            return
        self.stage_tables = build_stage_tables(engine.plan_ir, engine.column_batch)
        self.bag_tables = build_bag_tables(engine.plan_ir)
        self._bag_adj = None
        if engine.plan_ir.has_bag_stages:
            # Edge masks of bag-extend steps multiply by A[u_w, u_x]; the
            # dense adjacency broadcasts scatter-free against any state rank.
            self._bag_adj = jnp.asarray(engine.graph.dense_adjacency())

    def spmm(self, m: jnp.ndarray) -> jnp.ndarray:
        """One neighbor reduction over a fused ``(n, B, c)`` column slice
        (the fused pipeline only ever passes ``column_batch``-wide slices);
        returns accum dtype."""
        raise NotImplementedError

    def _spmm_counted(self, m: jnp.ndarray) -> jnp.ndarray:
        # the Python-level counter runs once per traced aggregation launch
        self.engine.counters["passive_aggregations"] += 1
        return self.spmm(m)

    def aggregate_ema(self, m_p, m_a, tables: StageTables):
        return self.aggregate_ema_grouped(m_p, [(m_a, tables)])[0]

    def aggregate_ema_grouped(self, m_p, stage_inputs):
        pol = self.engine.policy
        return fused_aggregate_ema_grouped(
            m_p,
            [(m_a, tables.batches, tables.n_out) for m_a, tables in stage_inputs],
            self._spmm_counted,
            pol.accum_dtype,
        )

    def _group_aggregate(self, leader, m_p, stage_inputs):
        """Per-exec-group dispatch seam: ``leader`` is the group's
        ``(plan_idx, sub_idx)`` address.  Uniform backends ignore it;
        :class:`MixedBackend` routes each group to its bound sub-impl."""
        return self.aggregate_ema_grouped(m_p, stage_inputs)

    def counts_for_colors(self, colors: jnp.ndarray) -> jnp.ndarray:
        """(B, n) colorings -> (B, T) un-normalized colorful totals.

        The walk *is* the plan: sub-template states are memoized by
        canonical form, freed at the plan's liveness-scheduled last reads
        (Algorithm 5's in-place storage), and stages reading the same
        passive canonical form execute as one plan exec group — the
        group's passive column-batch sweep aggregates each slice once for
        all of them.  Bag plans (non-tree templates) walk their bag
        programs through the same slot/liveness discipline; single-axis
        bag states share slots with tree stages whenever canons agree.
        """
        eng = self.engine
        ir = eng.plan_ir
        pol = eng.policy
        with tracing.scope("colors"):
            leaf = jax.nn.one_hot(colors.T, eng.k, dtype=pol.store_dtype)  # (n, B, k)
        free_at = ir.free_at
        slots: Dict[str, jnp.ndarray] = {}
        totals = []
        executed = set()
        pos = 0
        for p_idx, cplan in enumerate(ir.counting_plans):
            canons = ir.canons[p_idx]
            if cplan.partition is None:
                ops = cplan.bag_program.ops
                for i, op in enumerate(ops):
                    key = canons[i]
                    if key in executed:
                        continue
                    executed.add(key)
                    if op.kind == "leaf":
                        slots[key] = leaf
                    elif key not in slots:
                        with tracing.scope(tracing.stage(p_idx, i, op.m)):
                            state = self._run_bag_op(cplan, canons, p_idx, i, op, leaf, slots)
                            with tracing.scope("ema"):
                                slots[key] = state.astype(pol.store_dtype)
                    for dead in free_at.get(pos, ()):
                        slots.pop(dead, None)
                    pos += 1
                # final op has no vertex axes: state is (B, 1) — the single
                # C(k, k) colorset column holds the full colorful total
                with tracing.scope("root"):
                    root = slots[canons[len(ops) - 1]].astype(pol.accum_dtype)
                    totals.append(root.sum(axis=-1).astype(jnp.float32))
                for dead in free_at.get(pos, ()):
                    slots.pop(dead, None)
                pos += 1
                continue
            for i, sub in enumerate(cplan.partition.subs):
                key = canons[i]
                if key in executed:
                    continue
                executed.add(key)
                if sub.is_leaf:
                    slots[key] = leaf
                elif key not in slots:
                    # group leader: execute every stage sharing this passive
                    # canon over one column-batch sweep (members whose active
                    # state is already live; singleton group otherwise)
                    members = ir.exec_groups[(p_idx, i)]
                    stage_inputs = []
                    for q, j in members:
                        sub_m = ir.counting_plans[q].partition.subs[j]
                        stage_inputs.append(
                            (
                                slots[ir.canons[q][sub_m.active]],
                                self.stage_tables[(q, j)],
                            )
                        )
                    with tracing.scope(tracing.stage(p_idx, i, sub.size)):
                        outs = self._group_aggregate(
                            (p_idx, i), slots[canons[sub.passive]], stage_inputs
                        )
                        with tracing.scope("ema"):
                            for (q, j), m_s in zip(members, outs):
                                slots[ir.canons[q][j]] = m_s.astype(pol.store_dtype)
                # else: already produced early as a member of a prior group
                for dead in free_at.get(pos, ()):
                    slots.pop(dead, None)
                pos += 1
            with tracing.scope("root"):
                root = slots[canons[cplan.partition.root_index]].astype(pol.accum_dtype)
                # reduce color sets first, then vertices: the per-coloring
                # order is independent of the batch size (bit-exact across
                # chunkings)
                totals.append(root.sum(axis=2).sum(axis=0).astype(jnp.float32))
            for dead in free_at.get(pos, ()):
                slots.pop(dead, None)
            pos += 1
        with tracing.scope("root"):
            return jnp.stack(totals, axis=1)  # (B, T)

    # -- bag-program execution ------------------------------------------------

    def _run_bag_op(self, cplan, canons, p_idx, i, op, leaf, slots) -> jnp.ndarray:
        """Execute one extend / forget / join bag op on the fused layout.

        States are ``(n,)*r + (B, C)`` tensors — vertex axes (sorted by
        template vertex id) in front of the tree family's fused ``(B, C)``
        tail, so single-axis states are layout-identical to tree states.
        """
        if op.kind == "extend":
            return self._bag_extend(cplan, canons, p_idx, i, op, leaf, slots)
        if op.kind == "forget":
            in_op = cplan.bag_program.ops[op.inputs[0]]
            state = slots[canons[op.inputs[0]]]
            return self._bag_forget(state, list(in_op.axes), op.forget_vertices)[0]
        if op.kind == "join":
            return self._bag_join(canons, p_idx, i, op, slots)
        raise ValueError(f"unknown bag op kind {op.kind!r}")

    @staticmethod
    def _bag_forget(state, axes_now, forget_vertices):
        for x in forget_vertices:
            ax = axes_now.index(x)
            state = state.sum(axis=ax)
            axes_now.pop(ax)
        return state, axes_now

    def _bag_extend(self, cplan, canons, p_idx, i, op, leaf, slots) -> jnp.ndarray:
        eng = self.engine
        pol = eng.policy
        n = eng.graph.n
        tables: BagStageTables = self.bag_tables[(p_idx, i)]
        in_op = cplan.bag_program.ops[op.inputs[0]]
        state = slots[canons[op.inputs[0]]]
        axes_now = list(in_op.axes)
        w = op.vertex
        if op.spmm_vertex is not None:
            # Contract the eliminated axis through the adjacency: apply edge
            # (spmm_vertex, w) with the backend's neighbor reduction (the
            # state is flattened to the (n, B', C) layout spmm expects).
            ax = axes_now.index(op.spmm_vertex)
            state = jnp.moveaxis(state, ax, 0)
            rest = state.shape[1:]
            flat = state.reshape(n, -1, state.shape[-1])
            with tracing.scope("spmm"):
                state = self._spmm_counted(flat).reshape((n,) + rest)
            axes_now.pop(ax)
            axes_now = [w] + axes_now
        else:
            # Broadcast introduction: the new vertex has no eliminated
            # neighbor; its edges (if any) arrive as masks below.
            state = jnp.broadcast_to(state[None, ...], (n,) + state.shape)
            axes_now = [w] + axes_now
        for x in op.mask_vertices:
            ax = axes_now.index(x)
            mask = self._bag_adj.reshape(
                (n,) + (1,) * (ax - 1) + (n,) + (1,) * (state.ndim - 1 - ax)
            )
            state = state * mask.astype(state.dtype)
        # Colorset update against the new vertex's one-hot leaf:
        # SplitTable(k, m, 1) — exactly the tree eMA with a width-1 active.
        accum = pol.accum_dtype
        r = state.ndim
        idx_a, idx_p = tables.idx_a, tables.idx_p

        def body(t, acc):
            ia = jax.lax.dynamic_index_in_dim(idx_a, t, axis=1, keepdims=False)
            ip = jax.lax.dynamic_index_in_dim(idx_p, t, axis=1, keepdims=False)
            la = jnp.take(leaf, ia, axis=2).astype(accum)  # (n, B, n_out)
            la = la.reshape((n,) + (1,) * (r - 3) + la.shape[1:])
            gp = jnp.take(state, ip, axis=-1).astype(accum)
            return acc + la * gp

        with tracing.scope("ema"):
            out = jax.lax.fori_loop(
                0,
                tables.n_terms,
                body,
                jnp.zeros(state.shape[:-1] + (tables.n_out,), accum),
            )
        out, axes_now = self._bag_forget(out, axes_now, op.forget_vertices)
        # Restore sorted-axis order (the new vertex axis sits in front).
        order = sorted(range(len(axes_now)), key=lambda idx: axes_now[idx])
        if order != list(range(len(axes_now))):
            perm = order + list(range(len(axes_now), out.ndim))
            out = jnp.transpose(out, perm)
        return out

    def _bag_join(self, canons, p_idx, i, op, slots) -> jnp.ndarray:
        pol = self.engine.policy
        tables: BagStageTables = self.bag_tables[(p_idx, i)]
        s1 = slots[canons[op.inputs[0]]]
        s2 = slots[canons[op.inputs[1]]]
        accum = pol.accum_dtype
        idx_a, idx_p = tables.idx_a, tables.idx_p

        def body(t, acc):
            ia = jax.lax.dynamic_index_in_dim(idx_a, t, axis=1, keepdims=False)
            ip = jax.lax.dynamic_index_in_dim(idx_p, t, axis=1, keepdims=False)
            g1 = jnp.take(s1, ia, axis=-1).astype(accum)
            g2 = jnp.take(s2, ip, axis=-1).astype(accum)
            return acc + g1 * g2

        with tracing.scope("ema"):
            return jax.lax.fori_loop(
                0,
                tables.n_terms,
                body,
                jnp.zeros(s1.shape[:-1] + (tables.n_out,), accum),
            )


class EdgesBackend(LocalBackend):
    """Edge-list gather + segment-sum (the skew-robust default).

    Edge lists longer than :data:`EDGE_CHUNK` are reduced one chunk of edges
    at a time, so the gathered ``(edges, B, c)`` messages never exist for
    the whole graph: on a TPU their small minor dims pad to full 128-lane
    tiles, and a deployment-size edge list would not fit the device.
    """

    name = "edges"

    def __init__(self, engine, shared=None):
        super().__init__(engine, shared=shared)
        g = engine.graph
        n_edges = g.num_directed
        self._edge_chunk = min(n_edges, EDGE_CHUNK) or 1
        pad = -n_edges % self._edge_chunk
        # padded edges point past the last segment: segment_sum drops them
        self._src = jnp.asarray(np.pad(g.src, (0, pad)))
        self._dst = jnp.asarray(np.pad(g.dst, (0, pad), constant_values=g.n))

    def spmm(self, m):
        accum = self.engine.policy.accum_dtype
        n = self.engine.graph.n
        chunk = self._edge_chunk

        def body(i, acc):
            src = jax.lax.dynamic_slice_in_dim(self._src, i * chunk, chunk)
            dst = jax.lax.dynamic_slice_in_dim(self._dst, i * chunk, chunk)
            return acc + jax.ops.segment_sum(
                m[src].astype(accum), dst, num_segments=n, indices_are_sorted=True
            )

        return jax.lax.fori_loop(
            0, self._src.shape[0] // chunk, body, jnp.zeros(m.shape, accum)
        )


class EllBackend(LocalBackend):
    """Padded-row neighbor gather (flat degree distributions)."""

    name = "ell"

    def __init__(self, engine, shared=None):
        super().__init__(engine, shared=shared)
        nbr, mask = engine.graph.ell()
        self._nbr = jnp.asarray(nbr)
        self._ell_mask = jnp.asarray(mask)

    def spmm(self, m):
        pol = self.engine.policy
        gathered = m[self._nbr].astype(pol.accum_dtype)  # (n, max_deg, B, c)
        return jnp.einsum(
            "ndbc,nd->nbc", gathered, self._ell_mask.astype(pol.accum_dtype),
            precision=jax.lax.Precision.HIGHEST,
        )


class SellBackend(LocalBackend):
    """Degree-bucketed sliced-ELL gather — scatter-free (rmat8k-class graphs).

    Vertices are degree-sorted into :data:`SELL_GROUP_SIZE`-row groups,
    each padded only to its own max degree (:func:`repro.core.graph.
    build_sell`); the neighbor reduction is a padded row gather + masked
    einsum per group, stitched back through one inverse-permutation gather.
    No scatter appears anywhere — this sidesteps the XLA:CPU scatter cliff
    that made the edge-list ``segment_sum`` 5–10x *slower* than the scalar
    traversal baseline on rmat8k, while keeping padding bounded on
    power-law degree distributions (unlike plain ELL).
    """

    name = "sell"

    def __init__(self, engine, group_size: int = SELL_GROUP_SIZE, shared=None):
        super().__init__(engine, shared=shared)
        sell = build_sell(engine.graph, group_size=group_size)
        self._sell_padded_slots = sell.padded_slots
        self._groups = tuple(
            (jnp.asarray(nbr), jnp.asarray(mask))
            for nbr, mask in zip(sell.group_nbr, sell.group_mask)
        )
        self._inv_order = jnp.asarray(sell.inv_order)

    def spmm(self, m):
        pol = self.engine.policy
        parts = [
            jnp.einsum(
                "rdbc,rd->rbc",
                m[nbr].astype(pol.accum_dtype),
                mask.astype(pol.accum_dtype),
                precision=jax.lax.Precision.HIGHEST,
            )
            for nbr, mask in self._groups
        ]
        return jnp.concatenate(parts, axis=0)[self._inv_order]

    def transient_elements(self) -> int:
        eng = self.engine
        return eng.cost.transient_elements(
            self.name, eng.column_batch, sell_padded_slots=self._sell_padded_slots
        )


class DenseBackend(LocalBackend):
    """Dense-adjacency matmul (tiny graphs)."""

    name = "dense"

    def __init__(self, engine, shared=None):
        super().__init__(engine, shared=shared)
        self._adj = jnp.asarray(engine.graph.dense_adjacency())

    def spmm(self, m):
        pol = self.engine.policy
        n, b, c = m.shape
        out = jnp.matmul(
            self._adj.astype(pol.store_dtype),
            m.reshape(n, b * c),
            preferred_element_type=pol.accum_dtype,
            precision=jax.lax.Precision.HIGHEST,
        )
        return out.reshape(n, b, c).astype(pol.accum_dtype)


class BlockedEllBackend(LocalBackend):
    """Fused Pallas SpMM+eMA kernel over blocked-ELL (large graphs on TPU).

    Each stage is ONE :func:`repro.kernels.spmm_ema.ops.spmm_ema` call: per
    destination vertex block the kernel accumulates that block's aggregate
    columns in VMEM scratch and consumes them in the eMA FMA against the
    resident ``M_a`` tile the moment the block's last edge pair lands —
    the aggregate product never reaches HBM.
    """

    name = "blocked"

    def __init__(self, engine, block_size: int = BLOCKED_BLOCK_SIZE, shared=None):
        super().__init__(engine, shared=shared)
        from repro.kernels.spmm_ema.ops import prepare_fused_operand

        self._fused_op = prepare_fused_operand(engine.graph, block_size=block_size)

    def spmm(self, m):
        # kernel is 2-D (n, C) — fuse batch into columns
        from repro.kernels.spmm_blocked.ops import spmm_blocked

        n, b, c = m.shape
        out = spmm_blocked(
            self._fused_op.blocked,
            m.reshape(n, b * c).astype(jnp.float32),
            interpret=self.engine.interpret,
        )
        return out.reshape(n, b, c).astype(self.engine.policy.accum_dtype)

    def max_chunk_size(self) -> int:
        """Colorings whose bands fit the kernel's VMEM at the widest stage."""
        from repro.kernels.spmm_ema.kernel import VMEM_BUDGET_BYTES, vmem_bytes

        block = self._fused_op.blocked.block_size
        per_coloring = max(
            (
                vmem_bytes(1, st.passive_columns, st.active_columns, st.columns, block)
                for st in self.engine.plan_ir.stages
                if st.table_key is not None
            ),
            default=1,
        )
        return max(1, min(super().max_chunk_size(), VMEM_BUDGET_BYTES // per_coloring))

    def aggregate_ema(self, m_p, m_a, tables: StageTables):
        from repro.kernels.spmm_ema.ops import spmm_ema_batched

        self.engine.counters["passive_aggregations"] += 1
        with tracing.scope("spmm_ema"):
            return spmm_ema_batched(
                self._fused_op,
                m_p,
                m_a,
                tables.idx_a_host,
                tables.idx_p_host,
                interpret=self.engine.interpret,
            ).astype(self.engine.policy.accum_dtype)

    def aggregate_ema_grouped(self, m_p, stage_inputs):
        # the Pallas kernel fuses SpMM+eMA per stage inside one launch; a
        # cross-stage sweep cannot share its VMEM aggregate scratch, so the
        # group degrades to the per-stage loop (counted per launch)
        return [self.aggregate_ema(m_p, m_a, tables) for m_a, tables in stage_inputs]


class CustomBackend(LocalBackend):
    """Caller-supplied ``(n, C) -> (n, C)`` neighbor-sum kernel."""

    name = "custom"

    def __init__(self, engine, spmm_fn: Callable):
        super().__init__(engine)
        self._spmm_fn = spmm_fn

    def spmm(self, m):
        n, b, c = m.shape
        out = self._spmm_fn(m.reshape(n, b * c))
        return out.reshape(n, b, c).astype(self.engine.policy.accum_dtype)


#: name -> class for the uniform single-device strategies (what a
#: TuningConfig's per-group bindings may name).
LOCAL_BACKEND_CLASSES = {
    "edges": EdgesBackend,
    "ell": EllBackend,
    "sell": SellBackend,
    "dense": DenseBackend,
    "blocked": BlockedEllBackend,
}


class MixedBackend(LocalBackend):
    """Per-exec-group backend dispatch from a tuned configuration.

    One sub-implementation per distinct backend the
    :class:`~repro.tune.config.TuningConfig` names, all sharing this
    owner's stage/bag tables (``shared=`` — split tables ship to the
    device once).  The DP walk stays the inherited one; only the
    :meth:`_group_aggregate` seam routes each shared-passive exec group to
    its bound sub-impl's column-batch sweep.  Bag ops and ungrouped
    ``spmm`` calls run on the config's ``default_backend``.

    Measurement-driven existence proof: on skewed graphs the hub-touching
    wide-passive groups want SELL's scatter-free gathers while narrow
    early stages amortize better on the edge list — a single engine-wide
    backend leaves one of the two on the wrong cost curve.
    """

    name = "mixed"

    def __init__(self, engine, tuning):
        super().__init__(engine)
        if tuning is None:
            raise ValueError("MixedBackend needs a TuningConfig (tuning=...)")
        self._tuning = tuning
        self._bindings = tuning.bindings()
        names = {tuning.default_backend, *self._bindings.values()}
        unknown = names - set(LOCAL_BACKEND_CLASSES)
        if unknown:
            raise ValueError(
                f"mixed backend binds unknown local backends {sorted(unknown)}"
            )
        self._impls = {
            name: LOCAL_BACKEND_CLASSES[name](engine, shared=self)
            for name in sorted(names)
        }
        self._default = self._impls[tuning.default_backend]

    def _operand_owners(self):
        return (self, *self._impls.values())

    def spmm(self, m):
        return self._default.spmm(m)

    def _group_aggregate(self, leader, m_p, stage_inputs):
        name = self._bindings.get(leader, self._tuning.default_backend)
        return self._impls[name].aggregate_ema_grouped(m_p, stage_inputs)

    def transient_elements(self) -> int:
        # one chunk's scratch peaks at the widest sub-impl's slice
        return max(impl.transient_elements() for impl in self._impls.values())
