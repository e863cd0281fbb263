"""EngineBackend interface: how a TemplatePlan binds to devices and runs.

The third layer of the plan -> cost -> exec pipeline.  A backend owns:

* **operand construction** — its device-resident graph representation,
  built once in ``__init__`` (edge lists, ELL/SELL tables, dense
  adjacency, Pallas blocked operands, or the sharded edge partition +
  collective schedule for the mesh backend);
* **the DP execution** — :meth:`EngineBackend.counts_for_colors` maps a
  ``(B, n)`` chunk of colorings to ``(B, T)`` raw colorful totals by
  walking the engine's :class:`~repro.plan.ir.TemplatePlan` (stages,
  liveness, shared-passive groups — the backend never re-derives a
  schedule).  The per-stage primitive is :meth:`aggregate_ema`: ONE fused
  neighbor-aggregate + eMA step that never materializes the full
  ``A_G @ M_p`` product;
* **the memory-model geometry** — :meth:`transient_elements` /
  :meth:`resident_elements` feed the operand measurements into the plan
  layer's :class:`~repro.plan.cost.CostModel` formulas.

The engine compiles the backend's programs through :meth:`EngineBackend.jit`,
which hands the device operands to the program as arguments: a captured
array would be embedded in the program as a constant, and a deployment-size
edge list makes that program too large to compile.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.colorsets import bucketed_split_entries
from repro.plan.cost import MAX_CHUNK_SIZE

__all__ = [
    "StageTables",
    "BagStageTables",
    "EngineBackend",
    "build_stage_tables",
    "build_bag_tables",
    "make_backend",
]


def make_backend(engine, **kwargs) -> "EngineBackend":
    """Bind ``engine``'s resolved backend name to an implementation.

    ``kwargs`` carries the backend-specific knobs the engine collected
    (``spmm_fn``, ``block_size``, mesh parameters).  Imports are local so
    this module stays import-cycle-safe whichever package loads first.
    """
    from .local import (
        BlockedEllBackend,
        CustomBackend,
        DenseBackend,
        EdgesBackend,
        EllBackend,
        MixedBackend,
        SellBackend,
    )
    from repro.core.graph import BLOCKED_BLOCK_SIZE

    from .mesh import MeshBackend

    name = engine.backend
    if name == "custom":
        return CustomBackend(engine, kwargs["spmm_fn"])
    if name == "mixed":
        return MixedBackend(engine, kwargs.get("tuning"))
    if name == "edges":
        return EdgesBackend(engine)
    if name == "ell":
        return EllBackend(engine)
    if name == "sell":
        return SellBackend(engine)
    if name == "dense":
        return DenseBackend(engine)
    if name == "blocked":
        return BlockedEllBackend(engine, block_size=kwargs.get("block_size", BLOCKED_BLOCK_SIZE))
    if name == "mesh":
        return MeshBackend(
            engine,
            kwargs.get("mesh"),
            column_batch=kwargs.get("column_batch"),
            ema_mode=kwargs.get("ema_mode", "streamed"),
            gather_dtype=kwargs.get("gather_dtype"),
            balance_degrees=kwargs.get("balance_degrees", True),
            comm=kwargs.get("mesh_comm"),
        )
    raise ValueError(f"unknown backend {name!r}")


@dataclass(frozen=True)
class StageTables:
    """Split tables for one DP stage, in both shapes the fused pipeline needs.

    ``idx_a_host`` / ``idx_p_host`` are the plain ``(n_out, n_splits)`` rank
    tables, kept host-side: the fused Pallas kernel takes them flattened as
    scalar prefetch (``spmm_ema_batched``).  ``batches`` are
    the same entries re-bucketed by passive-column batch and shipped to the
    device (:func:`repro.core.colorsets.bucketed_split_entries`) for the
    streamed pure-JAX executor.  De-duplicated across stages by
    ``(k, m, m_a)``.
    """

    n_out: int
    column_batch: int
    idx_a_host: np.ndarray
    idx_p_host: np.ndarray
    batches: Tuple[Tuple[int, int, jnp.ndarray, jnp.ndarray, jnp.ndarray], ...]


def build_stage_tables(plan, column_batch: int) -> Dict[Tuple[int, int], StageTables]:
    """Bind a :class:`~repro.plan.ir.TemplatePlan`'s split tables to the
    device at one fused-slice width.

    Returns ``(plan_idx, sub_idx) -> StageTables`` for every non-leaf
    stage of every counting plan (duplicates included — aliases of one
    shared, de-duplicated-by-``(k, m, m_a)`` device table), so executors
    can look tables up by the stage address the schedule hands them.
    """
    cache: Dict[Tuple[int, int, int], StageTables] = {}
    out: Dict[Tuple[int, int], StageTables] = {}
    for p_idx, cplan in enumerate(plan.counting_plans):
        if cplan.partition is None:
            continue  # bag plans bind through build_bag_tables
        for i, table in enumerate(cplan.tables):
            if table is None:
                continue
            key = (table.k, table.m, table.m_a)
            if key not in cache:
                cache[key] = StageTables(
                    n_out=table.n_out,
                    column_batch=column_batch,
                    idx_a_host=table.idx_a,
                    idx_p_host=table.idx_p,
                    batches=tuple(
                        (
                            lo,
                            width,
                            jnp.asarray(ia),
                            jnp.asarray(ip),
                            None if va is None else jnp.asarray(va),
                        )
                        for lo, width, ia, ip, va in bucketed_split_entries(
                            table, column_batch
                        )
                    ),
                )
            out[(p_idx, i)] = cache[key]
    return out


@dataclass(frozen=True)
class BagStageTables:
    """Device-resident color tables for one bag op.

    ``extend`` ops carry a :class:`~repro.core.colorsets.SplitTable` with
    ``m_a = 1`` (the new vertex's one-hot color against the input's
    colorsets); ``join`` ops carry a
    :class:`~repro.core.colorsets.UnionSplitTable` (color-subset
    convolution).  Both reduce to the same gather-FMA loop over the term
    axis, so the executor only needs ``(idx_a, idx_p, n_out, n_terms)``.
    """

    kind: str  # "extend" | "join"
    n_out: int
    n_terms: int
    idx_a: jnp.ndarray  # (n_out, n_terms) int32, device
    idx_p: jnp.ndarray  # (n_out, n_terms) int32, device


def build_bag_tables(plan) -> Dict[Tuple[int, int], BagStageTables]:
    """Bind every bag plan's extend/join tables to the device.

    Returns ``(plan_idx, op_idx) -> BagStageTables`` for every extend and
    join op of every bag counting plan, de-duplicated by table identity so
    shared widths ship once (mirror of :func:`build_stage_tables` for the
    tree family).
    """
    cache: Dict[Tuple, BagStageTables] = {}
    out: Dict[Tuple[int, int], BagStageTables] = {}
    for p_idx, cplan in enumerate(plan.counting_plans):
        if cplan.partition is not None:
            continue
        for i, op in enumerate(cplan.bag_program.ops):
            table = cplan.tables[i]
            if table is None:
                continue
            if op.kind == "extend":
                key = ("extend", table.k, table.m, table.m_a)
                n_terms = table.n_splits
            else:
                key = ("join", table.k, table.m1, table.m2, table.overlap)
                n_terms = table.n_pairs
            if key not in cache:
                cache[key] = BagStageTables(
                    kind=op.kind,
                    n_out=table.n_out,
                    n_terms=n_terms,
                    idx_a=jnp.asarray(table.idx_a),
                    idx_p=jnp.asarray(table.idx_p),
                )
            out[(p_idx, i)] = cache[key]
    return out


def _is_operand(value) -> bool:
    """A device array, or a pytree whose every leaf is one."""
    leaves = jax.tree.leaves(value)
    return bool(leaves) and all(isinstance(x, jax.Array) for x in leaves)


class EngineBackend:
    """One fused SpMM+eMA execution strategy behind ``CountingEngine``.

    Backends keep a reference to the engine façade, which exposes the
    bound :class:`~repro.plan.ir.TemplatePlan` (``engine.plan_ir``), the
    :class:`~repro.plan.cost.CostModel` (``engine.cost``), the dtype
    policy, and the observability counters.
    """

    name: str = "abstract"

    #: Which fault-injection sites apply at this backend's launch boundary
    #: (``repro.testing.faults``; checked by ``CountingEngine.
    #: count_keys_chunk`` — Python-level, outside the jitted body).  The
    #: mesh backend adds ``"collective"`` for its all-gather dispatch.
    fault_sites: Tuple[str, ...] = ("launch",)

    def __init__(self, engine):
        self.engine = engine

    # -- execution ----------------------------------------------------------

    def aggregate_ema(
        self, m_p: jnp.ndarray, m_a: jnp.ndarray, tables: StageTables
    ) -> jnp.ndarray:
        """Fused per-stage step: ``(n, B, C_p), (n, B, C_a) -> (n, B, n_out)``
        in accum dtype, without materializing ``A_G @ M_p``."""
        raise NotImplementedError

    def aggregate_ema_grouped(
        self, m_p: jnp.ndarray, stage_inputs: Sequence[Tuple[jnp.ndarray, StageTables]]
    ) -> List[jnp.ndarray]:
        """Run several stages that share the passive state ``m_p``.

        Backends that can share the neighbor aggregation across the group
        override this (the streamed local pipeline computes each passive
        column-batch aggregate once for the whole group); the default is
        the unshared per-stage loop.
        """
        return [self.aggregate_ema(m_p, m_a, tables) for m_a, tables in stage_inputs]

    def counts_for_colors(self, colors: jnp.ndarray) -> jnp.ndarray:
        """``(B, n)`` colorings -> ``(B, T)`` un-normalized colorful totals."""
        raise NotImplementedError

    def counts_for_keys_chunk(self, keys_chunk: jnp.ndarray) -> jnp.ndarray:
        """``(B, 2)`` PRNG keys -> ``(B, T)`` un-normalized colorful totals.

        The engine scales them to estimates on the host, in float64: a u12
        estimate on a scale-20 RMAT exceeds the fp32 range.  The coloring draw is identical across backends (one ``randint`` per
        key over the *original* vertex ids), so the same keys produce the
        same colorings — and therefore fp-tolerance-comparable estimates —
        on every backend, mesh included.
        """
        eng = self.engine
        colors = jax.vmap(
            lambda key: jax.random.randint(key, (eng.graph.n,), 0, eng.k)
        )(keys_chunk)
        return self.counts_for_colors(colors)

    def make_run_fn(self) -> Callable:
        """One jit for the whole run: ``lax.map`` over key chunks.

        Tracing bumps the engine's ``trace_count`` (a Python side effect
        runs once per trace, i.e. per new compilation), so tests and the
        serving cache can assert that a warm engine never re-compiles.
        """
        engine = self.engine

        def run(keys):
            engine.trace_count += 1
            return jax.lax.map(self.counts_for_keys_chunk, keys)

        return self.jit(run)

    # -- device operands ----------------------------------------------------

    def _operand_owners(self) -> Tuple["EngineBackend", ...]:
        """Objects whose array attributes the programs read."""
        return (self,)

    def device_operands(self) -> Tuple[Dict[str, object], ...]:
        """Per owner, the attributes holding device arrays (or pytrees of
        them): the graph operands the jitted programs take as arguments."""
        return tuple(
            {k: v for k, v in vars(owner).items() if _is_operand(v)}
            for owner in self._operand_owners()
        )

    @contextlib.contextmanager
    def bind_operands(self, operands):
        """Point the operand attributes at ``operands`` (tracers, inside a
        trace) for the duration of the block."""
        owners = self._operand_owners()
        saved = [{k: getattr(o, k) for k in ops} for o, ops in zip(owners, operands)]
        try:
            for owner, ops in zip(owners, operands):
                for k, v in ops.items():
                    setattr(owner, k, v)
            yield
        finally:
            for owner, old in zip(owners, saved):
                for k, v in old.items():
                    setattr(owner, k, v)

    def jit(self, fn: Callable) -> Callable:
        """``jax.jit(fn)`` that reads this backend's device operands as
        program arguments.  The result is called like ``fn`` and has
        ``.lower(*args)`` for compile-only inspection."""

        def with_operands(operands, *args):
            with self.bind_operands(operands):
                return fn(*args)

        jitted = jax.jit(with_operands)

        def call(*args):
            return jitted(self.device_operands(), *args)

        call.lower = lambda *args: jitted.lower(self.device_operands(), *args)
        return call

    # -- memory-model geometry ----------------------------------------------

    def transient_elements(self) -> int:
        """Widest per-stage scratch one coloring needs, in store-dtype
        elements — the cost-model formula fed with this backend's built
        operand geometry."""
        eng = self.engine
        return eng.cost.transient_elements(self.name, eng.column_batch)

    def resident_elements(self) -> int:
        """Live M-matrix elements one coloring keeps resident."""
        return self.engine.cost.resident_elements()

    def bytes_per_coloring(self) -> int:
        """Calibrated live bytes one coloring contributes to a chunk."""
        return self.engine.cost.bytes_per_coloring(
            self.transient_elements(), self.resident_elements()
        )

    def max_chunk_size(self) -> int:
        """Most colorings one launch may fuse, whatever the memory budget."""
        return MAX_CHUNK_SIZE
