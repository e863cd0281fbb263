"""Distributed SUBGRAPH2VEC: the paper's MPI scheme on a device mesh (shard_map).

This module is the device-mesh half of the :class:`~repro.core.engine.
CountingEngine` — the engine's ``mesh`` backend is a thin wrapper over
:func:`make_batched_count_fn` built here.  Decomposition (DESIGN.md §5):
vertices are 1-D row-partitioned across **all** mesh axes (the paper's
distributed layout), edges co-located with their destination vertex.  Per DP
stage:

* **SpMM** — the only communicating step.  The dense count matrix
  ``M_{s,p}`` is broadcast in **column batches** (the paper's batched SpMM,
  §V-C: "we also split columns of M_{s,p} into batches ... to save peak
  memory"): for each batch, ``all_gather`` the batch rows along the mesh,
  then a local edge segment-sum produces the batch of ``B``.
  Peak extra memory = one batch = ``n * batch_size * column_batch * 4`` bytes.
* **eMA** — entirely vertex-local (Equation 1's whole point), zero
  communication.

The final count is a ``psum`` of local totals.  Column batching makes the
collective volume *independent* of the template size per batch; the batch
size is the knob the perf log (§Perf) tunes against the ICI roofline.

Engine integration (PR 2): :func:`make_batched_count_fn` fuses a whole chunk
of ``B`` colorings into the batch dimension of the DP state — every local M
matrix is ``(rows, B, C)`` and each all-gathered column batch serves all
``B`` colorings at once — and counts several same-``k`` templates per
coloring with DP states shared by rooted canonical form.  Split tables are
built ONCE at construction (de-duplicated by ``(k, m, m_a)``) and
closure-captured, not re-shipped per call.

Edge-balance caveat: row-range partitions inherit degree skew (the paper's
Fig 10 observation); ``shard_graph`` therefore supports a round-robin
degree-rank balancing permutation as an option (``ShardedGraph.perm``
records the relabeling so colorings can follow it).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from .colorsets import binom
from .counting import CountingPlan, _ema_apply_fused, schedule_liveness
from .graph import Graph

__all__ = [
    "ShardedGraph",
    "shard_graph",
    "make_batched_count_fn",
    "make_distributed_count_fn",
    "distributed_input_specs",
    "build_streamed_tables",
]


@dataclass(frozen=True)
class ShardedGraph:
    """Host-side edge partition: shard i owns vertex rows
    ``[i * rows_per_shard, (i+1) * rows_per_shard)`` and every edge whose dst
    lies in that range, padded to ``edges_per_shard``.

    ``perm`` is the old-id -> new-id vertex relabeling applied when
    ``balance_degrees=True`` (``None`` for the identity layout).  New ids
    range over ``[0, n_padded)`` (round-robin by degree rank leaves pad
    slots interleaved), so callers that fix per-vertex data (colors,
    features) must scatter it into an ``(n_padded,)`` array:
    ``data_new[perm] = data_old``.

    ``bucket_stride`` is set by ``bucket_by_src=True``: each shard's edge
    list is then grouped by *source* shard into ``n_shards`` contiguous
    buckets of exactly ``bucket_stride`` slots (the max (dst, src)-pair
    edge count; short buckets are mask-padded), so
    ``edges_per_shard == n_shards * bucket_stride`` and the ring pipeline
    can address the edges readable from one circulating row slice with a
    single ``dynamic_slice``.
    """

    n: int
    n_padded: int
    n_shards: int
    rows_per_shard: int
    edges_per_shard: int
    src: np.ndarray        # (n_shards * edges_per_shard,) global src ids
    dst_local: np.ndarray  # (n_shards * edges_per_shard,) dst - shard offset
    edge_mask: np.ndarray  # (n_shards * edges_per_shard,) float32
    perm: Optional[np.ndarray] = None  # (n,) old -> new id in [0, n_padded)
    bucket_stride: Optional[int] = None  # slots per src-shard bucket


def shard_graph(
    graph: Graph,
    n_shards: int,
    balance_degrees: bool = False,
    bucket_by_src: bool = False,
) -> ShardedGraph:
    """1-D row partition of ``graph`` over ``n_shards`` (edges follow dst).

    ``balance_degrees=True`` relabels vertices round-robin by degree rank
    before partitioning, so consecutive hubs land on different shards
    (reduces the max per-shard edge padding on skewed graphs).

    ``bucket_by_src=True`` additionally orders every shard's edges into
    ``n_shards`` uniform-stride buckets by *source* shard (see
    :class:`ShardedGraph`).  The mesh backend always uses this layout so the
    blocking and pipelined comm paths run over literally the same edge
    arrays — the precondition for their bit-exact equivalence.
    """
    src, dst = graph.src, graph.dst
    rows = max(-(-graph.n // n_shards), 1)
    n_padded = rows * n_shards
    perm = None
    if balance_degrees:
        # round-robin by degree rank: rank r lands on shard r % n_shards at
        # row r // n_shards, so consecutive hubs go to DIFFERENT shards.
        # New ids live in [0, n_padded); unassigned slots are pad vertices.
        order = np.argsort(-graph.degrees(), kind="stable")
        ranks = np.arange(graph.n)
        perm = np.empty(graph.n, dtype=np.int64)
        perm[order] = (ranks % n_shards) * rows + ranks // n_shards
        src, dst = perm[src].astype(np.int32), perm[dst].astype(np.int32)
    shard_of = dst // rows
    order = np.argsort(shard_of, kind="stable")
    src_s, dst_s, shard_s = src[order], dst[order], shard_of[order]

    if bucket_by_src:
        # sub-bucket each dst shard's edges by src shard with ONE uniform
        # stride: pair (s, o) lives at rows [o*stride, (o+1)*stride) of
        # shard s's edge list.  Pad slots keep mask 0 / src 0 / dst 0.
        pair = shard_s.astype(np.int64) * n_shards + src_s // rows
        pair_counts = np.bincount(pair, minlength=n_shards * n_shards)
        stride = int(pair_counts.max(initial=1))
        order2 = np.argsort(pair, kind="stable")
        src_p, dst_p, pair_p = src_s[order2], dst_s[order2], pair[order2]
        src_out = np.zeros((n_shards * n_shards, stride), dtype=np.int32)
        dst_out = np.zeros((n_shards * n_shards, stride), dtype=np.int32)
        mask_out = np.zeros((n_shards * n_shards, stride), dtype=np.float32)
        starts = np.concatenate([[0], np.cumsum(pair_counts)])
        for p in range(n_shards * n_shards):
            lo, hi = int(starts[p]), int(starts[p + 1])
            c = hi - lo
            src_out[p, :c] = src_p[lo:hi]
            dst_out[p, :c] = dst_p[lo:hi] - (p // n_shards) * rows
            mask_out[p, :c] = 1.0
        return ShardedGraph(
            n=graph.n,
            n_padded=n_padded,
            n_shards=n_shards,
            rows_per_shard=rows,
            edges_per_shard=n_shards * stride,
            src=src_out.reshape(-1),
            dst_local=dst_out.reshape(-1),
            edge_mask=mask_out.reshape(-1),
            perm=perm,
            bucket_stride=stride,
        )

    counts = np.bincount(shard_of, minlength=n_shards)
    e_max = int(counts.max(initial=1))
    src_out = np.zeros((n_shards, e_max), dtype=np.int32)
    dst_out = np.zeros((n_shards, e_max), dtype=np.int32)
    mask_out = np.zeros((n_shards, e_max), dtype=np.float32)
    starts = np.concatenate([[0], np.cumsum(np.bincount(shard_s, minlength=n_shards))])
    for s in range(n_shards):
        lo, hi = int(starts[s]), int(starts[s + 1])
        c = hi - lo
        src_out[s, :c] = src_s[lo:hi]
        dst_out[s, :c] = dst_s[lo:hi] - s * rows
        mask_out[s, :c] = 1.0
    return ShardedGraph(
        n=graph.n,
        n_padded=n_padded,
        n_shards=n_shards,
        rows_per_shard=rows,
        edges_per_shard=e_max,
        src=src_out.reshape(-1),
        dst_local=dst_out.reshape(-1),
        edge_mask=mask_out.reshape(-1),
        perm=perm,
    )


def _pad_cols(c: int, batch: int) -> int:
    return ((c + batch - 1) // batch) * batch


def _compressed_gather(x, axes, gather_dtype):
    """All-gather with the payload genuinely cast on the wire.

    ``optimization_barrier`` stops XLA from commuting the converts across the
    collective (observed on XLA:CPU: convert(bf16)->gather->convert(f32) gets
    folded back to an f32 gather, rounding values without saving bytes).
    """
    if gather_dtype is None:
        return jax.lax.all_gather(x, axes, axis=0, tiled=True)
    payload = jax.lax.optimization_barrier(x.astype(gather_dtype))
    full = jax.lax.all_gather(payload, axes, axis=0, tiled=True)
    return jax.lax.optimization_barrier(full).astype(jnp.float32)


def _pvary_missing(x, axes):
    """Mark ``x`` varying over any mesh axes it is not already varying on
    (loop-carry inits must match the varying type of the loop body)."""
    vma = jax.typeof(x).vma
    missing = tuple(a for a in axes if a not in vma)
    return jax.lax.pcast(x, missing, to="varying") if missing else x


#: Most split entries the streamed eMA multiplies in one step: its product
#: is ``(rows, B, entries)``, and a wide stage holds thousands of entries
#: per passive column batch.
EMA_ENTRY_CHUNK = 128


def _gather_segment_sum(table, idx, dst, mask, rows: int, accum_dtype, axes):
    """``sum_e table[idx[e]] * mask[e]`` into row ``dst[e]``, ``(rows, ...)``.

    Edge lists longer than :data:`~repro.plan.cost.EDGE_CHUNK` are reduced a
    chunk at a time, so the gathered messages never exist for every edge at
    once (on a TPU their small minor dims pad to 128-lane tiles).
    """
    from repro.plan.cost import EDGE_CHUNK

    n_e = idx.shape[0]
    n_chunks = max(-(-n_e // EDGE_CHUNK), 1)
    chunk = -(-n_e // n_chunks)

    def body(i, acc):
        pos = i * chunk + jnp.arange(chunk)
        weight = jnp.where(pos < n_e, mask[jnp.minimum(pos, n_e - 1)], 0)
        pos = jnp.minimum(pos, n_e - 1)
        vals = table[idx[pos]].astype(accum_dtype) * weight[:, None, None]
        return acc + jax.ops.segment_sum(vals, dst[pos], num_segments=rows)

    init = _pvary_missing(jnp.zeros((rows,) + table.shape[1:], accum_dtype), axes)
    return jax.lax.fori_loop(0, n_chunks, body, init)


def _streamed_stage_tables(table, column_batch: int):
    """Re-bucket one stage's split table by passive-column batch.

    Returns ``(ent_out, ent_ia, ent_ip_local, ent_valid)`` shaped
    ``(n_batches, cap)`` (padded per batch, to whole
    :data:`EMA_ENTRY_CHUNK` chunks once wider than one): for batch ``bi``
    the streamed schedule applies exactly the (out, split) entries whose
    passive column falls in that batch.
    """
    n_out, n_splits = table.idx_a.shape
    flat_out = np.repeat(np.arange(n_out, dtype=np.int32), n_splits)
    flat_ia = table.idx_a.reshape(-1).astype(np.int32)
    flat_ip = table.idx_p.reshape(-1).astype(np.int32)
    c_p = binom(table.k, table.m_p)
    n_batches = (c_p + column_batch - 1) // column_batch
    bucket = flat_ip // column_batch
    order = np.argsort(bucket, kind="stable")
    flat_out, flat_ia, flat_ip, bucket = (
        flat_out[order], flat_ia[order], flat_ip[order], bucket[order],
    )
    counts = np.bincount(bucket, minlength=n_batches)
    cap = int(counts.max(initial=1))
    if cap > EMA_ENTRY_CHUNK:
        # whole entry chunks: the streamed eMA folds one chunk at a time
        cap = -(-cap // EMA_ENTRY_CHUNK) * EMA_ENTRY_CHUNK
    ent_out = np.zeros((n_batches, cap), np.int32)
    ent_ia = np.zeros((n_batches, cap), np.int32)
    ent_ip = np.zeros((n_batches, cap), np.int32)
    ent_valid = np.zeros((n_batches, cap), np.float32)
    starts = np.concatenate([[0], np.cumsum(counts)])
    for b in range(n_batches):
        lo, hi = int(starts[b]), int(starts[b + 1])
        c = hi - lo
        ent_out[b, :c] = flat_out[lo:hi]
        ent_ia[b, :c] = flat_ia[lo:hi]
        ent_ip[b, :c] = flat_ip[lo:hi] - b * column_batch
        ent_valid[b, :c] = 1.0
    return (
        jnp.asarray(ent_out),
        jnp.asarray(ent_ia),
        jnp.asarray(ent_ip),
        jnp.asarray(ent_valid),
    )


def build_streamed_tables(plan: CountingPlan, column_batch: int):
    """Per-stage split tables re-bucketed by passive-column batch.

    The streamed schedule (§Perf beyond-paper optimization) consumes each
    all-gathered SpMM column batch immediately: for batch ``bi`` it applies
    every (out, split) entry whose passive column falls in the batch.  ``B``
    is never materialized — peak per-stage memory drops from
    ``M_a + M_p + B + M_s`` to ``M_a + M_p + M_s + one batch`` and the
    B write+read HBM round-trip disappears.

    Returns ``{stage: (ent_out, ent_ia, ent_ip_local, ent_valid)}`` with
    arrays shaped ``(n_batches, cap)`` (padded per batch).
    """
    return {
        i: _streamed_stage_tables(t, column_batch)
        for i, t in enumerate(plan.tables)
        if t is not None
    }


def make_batched_count_fn(
    plans: Sequence[CountingPlan],
    mesh: Mesh,
    n_padded: int,
    edges_per_shard: int,
    *,
    column_batch: Optional[int] = 128,
    ema_mode: str = "streamed",
    gather_dtype=None,
    canons: Optional[Sequence[Sequence[str]]] = None,
    plan_ir=None,
    store_dtype=jnp.float32,
    accum_dtype=jnp.float32,
    comm_mode: str = "blocking",
    comm_schedule: Optional[Mapping[Tuple[int, int], str]] = None,
    bucket_stride: Optional[int] = None,
) -> Callable:
    """Build the jit-able mesh count over a batched chunk of colorings.

    This is the compute core of the engine's ``mesh`` backend.  Signature of
    the returned fn::

      (colors (B, n_padded) i32, src (S*E,) i32, dst_local (S*E,) i32,
       edge_mask (S*E,) f32) -> (B, T) f32 raw colorful totals

    where ``T == len(plans)``.  All split tables (plain or streamed) are
    built HERE, once, de-duplicated by ``(k, m, m_a)``, and closure-captured
    — they are never re-shipped per call.  A chunk of ``B`` colorings is
    fused into the batch dimension of the DP state so every all-gathered
    column batch serves all ``B`` colorings in one collective.

    Args:
      plans: one or more same-``k`` :class:`CountingPlan`; DP states are
        shared across plans by rooted canonical form (see ``canons``).
      mesh: the device mesh; tensors are sharded over every axis (1-D row
        partition of the vertex space).
      n_padded / edges_per_shard: the :class:`ShardedGraph` geometry.
      column_batch: passive columns all-gathered per collective.  ``None`` is
        probe mode: one full-width all-gather, no loop — lets
        ``cost_analysis`` see the full per-stage work (XLA counts while-loop
        bodies once).
      ema_mode: ``"streamed"`` (beyond-paper fusion: every all-gathered
        column batch is consumed immediately by the eMA updates that read
        it; ``B`` never exists), ``"loop"`` (paper-faithful Algorithm 5:
        full batched SpMM into B, then the eMA pass; B is memoized per
        passive canonical form, so templates sharing a passive sub-template
        share its SpMM), or ``"vectorized"`` (probe mode: loop-free
        gather-FMA einsum, fully visible to ``cost_analysis``).
      gather_dtype: ``jnp.bfloat16`` compresses the row all-gather payload 2x
        — the counting analogue of gradient compression.  Counts are an
        (eps, delta) ESTIMATOR, so the ~0.4% bf16 rounding is dominated by
        coloring variance.  Accumulation stays fp32.
      canons: per-plan, per-sub-template rooted canonical strings (legacy
        override; superseded by ``plan_ir``); equal strings share one DP
        state.
      plan_ir: optional :class:`repro.plan.ir.TemplatePlan` for the plan
        set — the engine's mesh backend passes its bound plan so the
        schedule (canonical sharing + liveness) is consumed, not
        re-derived.  Legacy callers omit it and one is planned here.
      store_dtype / accum_dtype: the engine's dtype policy — M matrices are
        kept (and all-gathered) in ``store_dtype``, reductions accumulate in
        ``accum_dtype``.
      comm_mode: ``"blocking"`` (one ``all_gather`` per column batch — the
        paper's synchronous scheme) or ``"pipelined"`` (double-buffered ring:
        each column batch circulates as per-shard row slices via
        ``lax.ppermute``, the NEXT slice in flight while the current one's
        edge bucket is consumed as a partial ``segment_sum``).  Pipelined
        requires the ``bucket_by_src`` edge layout, a single-axis mesh with
        >= 2 shards, and the streamed eMA mode; on such layouts the
        *blocking* streamed path runs the SAME per-source-shard bucket fold
        in the SAME ring order (reading each owner's rows out of its one
        all-gathered buffer), so counts are **bit-exact** across the two
        modes by construction.
      comm_schedule: optional per-stage override map ``(plan_idx, sub_idx)
        -> mode`` (the plan-time ``CostModel.comm_schedule`` decision);
        stages not in the map use ``comm_mode``.
      bucket_stride: the ``ShardedGraph.bucket_stride`` of the
        ``bucket_by_src`` layout (required whenever any stage is pipelined).
    """
    if not plans:
        raise ValueError("make_batched_count_fn needs at least one plan")
    ks = {p.k for p in plans}
    if len(ks) != 1:
        raise ValueError(f"all plans must share one k, got {sorted(ks)}")
    k = ks.pop()
    if ema_mode not in ("streamed", "loop", "vectorized"):
        raise ValueError(f"unknown ema_mode {ema_mode!r}")
    if comm_mode not in ("blocking", "pipelined"):
        raise ValueError(f"unknown comm_mode {comm_mode!r}")

    axes = tuple(mesh.axis_names)
    n_shards = int(np.prod(mesh.devices.shape))
    rows = n_padded // n_shards
    pad_unit = column_batch or 128

    comm_schedule = dict(comm_schedule or {})
    bad = {m for m in comm_schedule.values() if m not in ("blocking", "pipelined")}
    if bad:
        raise ValueError(f"unknown comm_schedule mode(s) {sorted(bad)}")
    any_pipelined = comm_mode == "pipelined" or "pipelined" in comm_schedule.values()
    if any_pipelined:
        if ema_mode != "streamed":
            raise ValueError(
                f"comm_mode='pipelined' requires ema_mode='streamed' "
                f"(got {ema_mode!r}) — the ring consumes each slice inside "
                "the fused SpMM+eMA sweep"
            )
        if column_batch is None:
            raise ValueError("comm_mode='pipelined' needs a finite column_batch")
        if len(axes) != 1:
            raise ValueError(
                f"comm_mode='pipelined' rings a single mesh axis (got {axes})"
            )
        if n_shards < 2:
            raise ValueError("comm_mode='pipelined' needs >= 2 shards")
        if bucket_stride is None or n_shards * bucket_stride != edges_per_shard:
            raise ValueError(
                "comm_mode='pipelined' needs the bucket_by_src edge layout: "
                f"bucket_stride={bucket_stride!r} with edges_per_shard="
                f"{edges_per_shard} and n_shards={n_shards}"
            )

    track_products = ema_mode != "streamed"
    if canons is not None:
        # legacy canons override: the DP walk keys states by THESE strings,
        # so the liveness schedule must be derived from them too (a plan's
        # schedule would disagree — don't build one)
        free_at = schedule_liveness(plans, canons, track_products=track_products)
    else:
        if plan_ir is None:
            # legacy surface (launch/cells probes, direct tests): plan the
            # set here — the schedule must come from ONE planner either way
            from repro.plan.ir import build_template_plan

            plan_ir = build_template_plan([p.template for p in plans], plans=plans)
        canons = plan_ir.canons
        # the plan's liveness schedule: only the non-streamed eMA modes
        # memoize aggregate products, so they free against that variant
        free_at = plan_ir.liveness(track_products=track_products)

    # --- split tables: built once, de-duplicated by (k, m, m_a).
    tables_dev = {}
    table_specs = {}
    stage_table_key = {}
    for p_idx, plan in enumerate(plans):
        for i, t in enumerate(plan.tables):
            if t is None:
                continue
            key = f"{t.k}.{t.m}.{t.m_a}"
            stage_table_key[(p_idx, i)] = key
            if key in tables_dev:
                continue
            if ema_mode == "streamed":
                tables_dev[key] = _streamed_stage_tables(t, pad_unit)
                table_specs[key] = (P(None, None),) * 4
            else:
                tables_dev[key] = (jnp.asarray(t.idx_a), jnp.asarray(t.idx_p))
                table_specs[key] = (P(None, None),) * 2

    def spmm_batched(m_p, src, dst_local, edge_mask):
        """Column-batched all-gather SpMM; m_p: (rows, B, C_pad) local."""
        bsz, c_pad = m_p.shape[1], m_p.shape[2]
        if column_batch is None:
            full = _compressed_gather(m_p, axes, gather_dtype)
            return _gather_segment_sum(
                full, src, dst_local, edge_mask, rows, accum_dtype, axes
            )
        n_batches = c_pad // column_batch

        def body(b_idx, acc):
            cols = jax.lax.dynamic_slice(
                m_p, (0, 0, b_idx * column_batch), (rows, bsz, column_batch)
            )
            full = _compressed_gather(cols, axes, gather_dtype)
            bcol = _gather_segment_sum(
                full, src, dst_local, edge_mask, rows, accum_dtype, axes
            )
            return jax.lax.dynamic_update_slice(acc, bcol, (0, 0, b_idx * column_batch))

        init = _pvary_missing(jnp.zeros(m_p.shape, accum_dtype), axes)
        return jax.lax.fori_loop(0, n_batches, body, init)

    # the bucketed consume is shared by the ring AND the single-axis
    # blocking path so the two modes fold bit-identically (see below)
    bucket_fold = bucket_stride is not None and len(axes) == 1 and n_shards >= 2

    def _bucket_partials(get_block, src, dst_local, edge_mask, bsz, cb):
        """Per-src-shard-bucket partial segment-sums, folded in ring step
        order (``owner = (my - d) mod D``).

        ``get_block(d, owner) -> (rows, B, cb)`` supplies src-shard
        ``owner``'s rows of the column batch — from the circulating ring
        slice (pipelined) or sliced out of the one all-gathered buffer
        (blocking).  Everything else — the bucket slices, the gather, the
        mask multiply, the per-bucket ``segment_sum``, and the fold order
        of the partials — is this one code path, shared by both modes.
        That sharing is the bit-exactness argument: the block values are
        elementwise identical (a gather reads the same stored floats
        whichever buffer holds them; ``ppermute`` moves bits verbatim), so
        every intermediate rounding happens on identical operands in an
        identical sequence.
        """
        ring = axes[0]
        my = jax.lax.axis_index(ring)
        bcol = _pvary_missing(jnp.zeros((rows, bsz, cb), accum_dtype), axes)
        for d in range(n_shards):
            owner = jnp.mod(my - d, n_shards)
            block = get_block(d, owner)
            b_src = jax.lax.dynamic_slice(
                src, (owner * bucket_stride,), (bucket_stride,)
            )
            b_dst = jax.lax.dynamic_slice(
                dst_local, (owner * bucket_stride,), (bucket_stride,)
            )
            b_mask = jax.lax.dynamic_slice(
                edge_mask, (owner * bucket_stride,), (bucket_stride,)
            )
            # valid slots sit in the owner's row range by the bucket
            # invariant; pad slots (mask 0) are clipped in-bounds and zeroed
            local = jnp.clip(b_src - owner * rows, 0, rows - 1)
            bcol = bcol + _gather_segment_sum(
                block, local, b_dst, b_mask, rows, accum_dtype, axes
            )
        return bcol

    def ring_spmm(cols, src, dst_local, edge_mask):
        """Double-buffered ring SpMM over one column batch.

        ``cols`` is this shard's ``(rows, B, cb)`` slice.  Slices circulate
        along the single mesh axis: after ``d`` hops device ``i`` holds
        shard ``(i - d) mod D``'s rows, and the ``ppermute`` for hop
        ``d + 1`` is issued BEFORE hop ``d``'s bucket is consumed, so the
        wire transfer hides under the edge gather + partial segment-sum
        (the expensive half of the SpMM).  Only two row slices are ever
        live — the full gathered buffer never materializes.
        """
        ring = axes[0]
        perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]
        bsz, cb = cols.shape[1], cols.shape[2]
        state = {"cur": cols}
        if gather_dtype is not None:
            # cast to the wire dtype ONCE; hops circulate the compressed
            # payload (bf16 -> f32 -> bf16 would be lossless anyway, but
            # one cast keeps the barrier structure identical to blocking's)
            state["cur"] = jax.lax.optimization_barrier(
                cols.astype(gather_dtype)
            )

        def block(d, owner):
            cur = state["cur"]
            if d + 1 < n_shards:  # prefetch the next slice NOW
                state["cur"] = jax.lax.ppermute(cur, ring, perm)
            if gather_dtype is not None:
                return jax.lax.optimization_barrier(cur).astype(jnp.float32)
            return cur

        return _bucket_partials(block, src, dst_local, edge_mask, bsz, cb)

    def spmm_ema_streamed(
        m_p, m_a, src, dst_local, edge_mask, n_out, stream_tbl, mode="blocking"
    ):
        """Fused per-batch SpMM -> eMA: gather a column batch, reduce it, and
        immediately scatter its contributions into M_s (B never exists)."""
        cb = pad_unit
        bsz = m_p.shape[1]
        n_batches = m_p.shape[2] // cb
        ent_out, ent_ia, ent_ip, ent_valid = stream_tbl

        def body(b_idx, m_s):
            cols = jax.lax.dynamic_slice(m_p, (0, 0, b_idx * cb), (rows, bsz, cb))
            if mode == "pipelined":
                bcol = ring_spmm(cols, src, dst_local, edge_mask)
            elif bucket_fold:
                # single-axis bucketed blocking: one all-gather, then the
                # SAME per-bucket fold the ring runs — this is what makes
                # blocking and pipelined engines bit-exact A/B arms
                full = _compressed_gather(cols, axes, gather_dtype)
                bcol = _bucket_partials(
                    lambda d, owner: jax.lax.dynamic_slice(
                        full, (owner * rows, 0, 0), (rows, bsz, cb)
                    ),
                    src, dst_local, edge_mask, bsz, cb,
                )
            else:
                full = _compressed_gather(cols, axes, gather_dtype)
                bcol = _gather_segment_sum(
                    full, src, dst_local, edge_mask, rows, accum_dtype, axes
                )
            row = [
                jax.lax.dynamic_index_in_dim(a, b_idx, keepdims=False)
                for a in (ent_out, ent_ia, ent_ip, ent_valid)
            ]

            def fold(j, m_s):
                eo, ia, ip, va = (
                    jax.lax.dynamic_slice_in_dim(a, j * width, width) for a in row
                )
                prod = (
                    jnp.take(m_a, ia, axis=2).astype(accum_dtype)
                    * jnp.take(bcol, ip, axis=2)
                    * va[None, None, :].astype(accum_dtype)
                )
                return m_s.at[:, :, eo].add(prod)

            return jax.lax.fori_loop(0, cap // width, fold, m_s)

        cap = ent_out.shape[1]
        width = min(cap, EMA_ENTRY_CHUNK)
        init = _pvary_missing(jnp.zeros((rows, bsz, n_out), accum_dtype), axes)
        return jax.lax.fori_loop(0, n_batches, body, init)

    def ema_loop(m_a, b, idx_a, idx_p):
        """Vertex-local eMA over fused (rows, B, C) state (Algorithm 5)."""
        init = _pvary_missing(
            jnp.zeros((rows, m_a.shape[1], idx_a.shape[0]), accum_dtype), axes
        )
        return _ema_apply_fused(m_a, b, idx_a, idx_p, init)

    def local_count(colors, src, dst_local, edge_mask, tables):
        # colors: (B, rows) local slice of the (B, n_padded) coloring batch.
        def pad_c(m):
            c = m.shape[-1]
            return jnp.pad(m, ((0, 0), (0, 0), (0, _pad_cols(c, pad_unit) - c)))

        def free(pos, slots, prods):
            # Algorithm 5's in-place storage, liveness-scheduled: drop DP
            # states / memoized SpMM products after their last reader.
            for key in free_at.get(pos, ()):
                if isinstance(key, tuple):
                    prods.pop(key[1], None)
                else:
                    slots.pop(key, None)

        leaf = pad_c(jax.nn.one_hot(colors.T, k, dtype=store_dtype))  # (rows, B, k_pad)
        executed = set()
        slots = {}
        prods = {}
        totals = []
        pos = 0
        for p_idx, plan in enumerate(plans):
            pc = canons[p_idx]
            for i, sub in enumerate(plan.partition.subs):
                ckey = pc[i]
                if ckey in executed:
                    continue
                executed.add(ckey)
                if sub.is_leaf:
                    slots[ckey] = leaf
                else:
                    m_a, m_p = slots[pc[sub.active]], slots[pc[sub.passive]]
                    tkey = stage_table_key[(p_idx, i)]
                    if ema_mode == "streamed":
                        m_s = spmm_ema_streamed(
                            m_p, m_a, src, dst_local, edge_mask,
                            plan.tables[i].n_out, tables[tkey],
                            mode=comm_schedule.get((p_idx, i), comm_mode),
                        )
                    else:
                        p_key = pc[sub.passive]
                        if p_key not in prods:
                            prods[p_key] = spmm_batched(m_p, src, dst_local, edge_mask)
                        b = prods[p_key]
                        idx_a, idx_p = tables[tkey]
                        if ema_mode == "vectorized":
                            # probe mode: single gather-FMA einsum (no
                            # fori_loop) so the split-axis work is visible to
                            # cost_analysis
                            m_s = jnp.einsum(
                                "rbos,rbos->rbo",
                                jnp.take(m_a, idx_a, axis=2).astype(accum_dtype),
                                jnp.take(b, idx_p, axis=2),
                                precision=jax.lax.Precision.HIGHEST,
                            )
                        else:
                            m_s = ema_loop(m_a, b, idx_a, idx_p)
                    slots[ckey] = pad_c(m_s.astype(store_dtype))
                free(pos, slots, prods)
                pos += 1
            root = slots[pc[plan.partition.root_index]].astype(accum_dtype)
            # reduce color sets first, then vertices, then shards: the local
            # order matches the single-host engine's per-coloring reduction
            total_local = root.sum(axis=2).sum(axis=0)
            totals.append(jax.lax.psum(total_local, axes))  # (B,), replicated
            free(pos, slots, prods)
            pos += 1
        return jnp.stack(totals, axis=1).astype(jnp.float32)  # (B, T)

    sharded = P(axes)
    mapped = jax.shard_map(
        local_count,
        mesh=mesh,
        in_specs=(P(None, axes), sharded, sharded, sharded, table_specs),
        out_specs=P(None, None),
    )

    def count(colors_batch, src, dst_local, edge_mask):
        return mapped(colors_batch, src, dst_local, edge_mask, tables_dev)

    return count


def make_distributed_count_fn(
    plan: CountingPlan,
    mesh: Mesh,
    n_padded: int,
    edges_per_shard: int,
    column_batch: Optional[int] = 128,
    ema_mode: str = "loop",
    gather_dtype=None,
):
    """One-coloring, one-template distributed count (compat / probe surface).

    A thin ``B=1`` wrapper over :func:`make_batched_count_fn` — kept for the
    dry-run/probe tooling (``launch.cells``) and ad-hoc single-coloring
    checks.  Estimation runs should use the engine's ``mesh`` backend
    (``CountingEngine(..., backend="mesh", mesh=mesh)``), which batches
    chunks of colorings into each collective.

    Signature of the returned fn::

      (colors (n_padded,) i32, src (S*E,) i32, dst_local (S*E,) i32,
       edge_mask (S*E,) f32) -> scalar f32 raw colorful total

    Split tables are built once here and closure-captured (they are no
    longer an argument).
    """
    batched = make_batched_count_fn(
        [plan],
        mesh,
        n_padded,
        edges_per_shard,
        column_batch=column_batch,
        ema_mode=ema_mode,
        gather_dtype=gather_dtype,
    )

    def count(colors, src, dst_local, edge_mask):
        return batched(colors[None, :], src, dst_local, edge_mask)[0, 0]

    return count


def distributed_input_specs(n_padded: int, n_shards: int, edges_per_shard: int):
    """ShapeDtypeStructs for the one-coloring distributed count (dry-run)."""
    e_total = n_shards * edges_per_shard
    return (
        jax.ShapeDtypeStruct((n_padded,), jnp.int32),   # colors
        jax.ShapeDtypeStruct((e_total,), jnp.int32),    # src (global)
        jax.ShapeDtypeStruct((e_total,), jnp.int32),    # dst (local)
        jax.ShapeDtypeStruct((e_total,), jnp.float32),  # edge mask
    )
