"""CountingEngine: the thin façade over the plan -> cost -> exec pipeline.

The engine answers batched multi-coloring, multi-template color-coding
runs.  It is a *compiler driver*, not a monolith — one construction is
exactly::

    plan   = repro.plan.build_template_plan(templates)   # backend-agnostic IR
    cost   = repro.plan.cost.CostModel(plan, graph, ...) # calibrated budgets
    select_backend(graph)                                # graph statistics
    repro.exec.make_backend(engine)                      # bind plan to devices
    chunk  = cost.pick_chunk_size(...)                   # fit the budget

and every public surface — :meth:`CountingEngine.describe`,
:meth:`CountingEngine.cache_key`, the memory figures, the chunked launch
API — is derived from the bound :class:`~repro.plan.ir.TemplatePlan`.
``repro.plan`` owns the static schedule + the calibrated cost model,
``repro.exec`` owns the execution strategies and backend auto-selection;
this module keeps the dtype policy, the cache-key identity, and the
chunked launch API.  See ``docs/architecture.md`` / ``docs/planning.md``.

Execution-model invariants (unchanged from the fused PR 3/4 pipeline): the
aggregate product ``A_G @ M_p`` is never materialized; a chunk of ``B``
colorings rides the fused column dimension of every M matrix (one jit per
run); DP states are freed at their liveness-scheduled last read; and
estimates are bit-exact across chunk sizes."""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

# Submodule imports only (repro.exec/.plan's __init__ import repro.core
# right back); every re-exported compat name is listed in __all__.
from repro.exec.base import EngineBackend, StageTables, make_backend
from repro.exec.local import SELL_GROUP_SIZE
from repro.exec.mesh import MeshBackend
from repro.exec.select import (
    BACKEND_ENV_VAR,
    BLOCKED_MAX_PADDING,
    DENSE_MAX_VERTICES,
    DENSE_WORK_ADVANTAGE,
    ELL_PAD_FACTOR,
    ENGINE_BACKENDS,
    SELL_MIN_SCATTER_WORK,
    resolve_backend_config,
    select_backend,
)
from repro.plan.cost import (
    DEFAULT_MEMORY_BUDGET_BYTES,
    LOCAL_COLUMN_BATCH,
    MAX_CHUNK_SIZE,
    CostModel,
    default_memory_budget_bytes,
    pick_chunk_size,
)
from repro.plan.ir import TemplatePlan, build_template_plan, template_set_canons
from repro.testing import faults as _faults

from .colorsets import colorful_probability
from .counting import CountingPlan
from .graph import BLOCKED_BLOCK_SIZE, Graph
from .templates import Template, sub_template_canonical

__all__ = [
    "DtypePolicy",
    "EstimateResult",
    "CountingEngine",
    "EngineBackend",
    "MeshBackend",
    "StageTables",
    "make_backend",
    "select_backend",
    "pick_chunk_size",
    "sub_template_canonical",
    "template_set_canons",
    "engine_cache_key",
    "CostModel",
    "ENGINE_BACKENDS",
    # re-exported tuning constants (homes: repro.plan.cost, repro.exec)
    "DEFAULT_MEMORY_BUDGET_BYTES", "MAX_CHUNK_SIZE", "LOCAL_COLUMN_BATCH",
    "BACKEND_ENV_VAR", "DENSE_MAX_VERTICES", "ELL_PAD_FACTOR",
    "BLOCKED_MAX_PADDING", "SELL_MIN_SCATTER_WORK", "SELL_GROUP_SIZE",
    "DENSE_WORK_ADVANTAGE",
]

logger = logging.getLogger("repro.engine")


@dataclass(frozen=True)
class DtypePolicy:
    """Storage vs accumulation dtypes for the DP state.

    ``store_dtype`` is what M matrices (and therefore the SpMM gather
    traffic — on the mesh backend, also the all-gather wire payload) are
    kept in; ``accum_dtype`` is what neighbor reductions and eMA FMAs
    accumulate in.  ``fp32`` keeps both at float32; ``bf16`` halves the
    storage/gather bytes while accumulating in float32 (paper §VI).
    """

    store_dtype: jnp.dtype
    accum_dtype: jnp.dtype

    @staticmethod
    def resolve(policy: Union[str, "DtypePolicy", jnp.dtype, None]) -> "DtypePolicy":
        """Coerce ``"fp32"`` | ``"bf16"`` | a dtype | a policy | None."""
        if policy is None:
            return DtypePolicy(jnp.float32, jnp.float32)
        if isinstance(policy, DtypePolicy):
            return policy
        if isinstance(policy, str):
            if policy in ("fp32", "float32"):
                return DtypePolicy(jnp.float32, jnp.float32)
            if policy in ("bf16", "bfloat16"):
                return DtypePolicy(jnp.bfloat16, jnp.float32)
            raise ValueError(f"unknown dtype policy {policy!r} (fp32 | bf16)")
        dt = jnp.dtype(policy)
        accum = jnp.float32 if dt in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)) else dt
        return DtypePolicy(dt, accum)


@dataclass
class EstimateResult:
    """Per-template estimation summary (API-compatible with the estimator's)."""

    mean: float
    std: float
    per_iteration: np.ndarray
    iterations: int


def _assemble_cache_key(
    signature: str,
    canons: Tuple[Tuple[str, ...], ...],
    backend: str,
    policy: "DtypePolicy",
    chunk_spec: Tuple,
    column_batch: Optional[int],
    tuning_fragment: Optional[Tuple] = None,
) -> Tuple:
    """The one place the cache-key tuple is laid out — shared by
    :func:`engine_cache_key` (pre-construction) and
    :meth:`CountingEngine.cache_key` (resolved values) so the two
    identities cannot drift.  The tuning fragment rides at the END so the
    positional consumers of the earlier elements (the serving layer's
    degradation ladder reads backend/chunk/column_batch at [3]/[6]/[7])
    keep their offsets."""
    return (
        "counting-engine",
        signature,
        canons,
        backend,
        str(jnp.dtype(policy.store_dtype)),
        str(jnp.dtype(policy.accum_dtype)),
        chunk_spec,
        None if column_batch is None else int(column_batch),
        tuning_fragment,
    )


def engine_cache_key(
    graph: Graph,
    templates: Sequence[Template],
    *,
    backend: str = "auto",
    dtype_policy: Union[str, "DtypePolicy", jnp.dtype, None] = "fp32",
    chunk_size: Optional[int] = None,
    memory_budget_bytes: Optional[int] = None,
    column_batch: Optional[int] = None,
    tuning=None,
) -> Tuple:
    """Hashable identity of a compiled :class:`CountingEngine`.

    Two constructions with equal keys trace and compile to the same
    programs, so a cache (``repro.serve.cache.EngineCache``) can hand back
    the warm engine and skip tracing entirely.  Anatomy::

        ("counting-engine",
         graph signature,           # content hash of (n, src, dst)
         template-set canons,       # DP-schedule identity, label-free
         resolved backend name,     # full resolution ladder folded in
         store dtype, accum dtype,  # dtype policy
         chunk spec,                # explicit chunk, or the budget that
                                    # deterministically picks one
         column_batch,              # fused-slice width override (or None)
         tuning fragment)           # TuningConfig.key_fragment(), or None

    Backend resolution runs the same ladder the constructor does
    (explicit > ``REPRO_ENGINE_BACKEND`` > tuned cache entry > analytic
    heuristic — :func:`repro.exec.select.resolve_backend_config`), and a
    tuned config's chunk/column-batch overrides are folded in exactly as
    construction would apply them, so the pre-construction key always
    matches the built engine's :meth:`CountingEngine.cache_key`.

    The template-set canons are exactly a ``TemplatePlan``'s schedule
    identity, so **plan equality implies cache-key equality** (pinned in
    ``tests/test_plan.py``).  The key is computable without constructing
    the engine (operands are only built on a cache miss)."""
    signature = graph.signature()
    canons = template_set_canons(templates)
    name, _source, _reason, cfg = resolve_backend_config(
        graph, backend=backend, canons=canons, tuning=tuning, signature=signature
    )
    if cfg is not None:
        if chunk_size is None and cfg.chunk_size is not None:
            chunk_size = cfg.chunk_size
        if column_batch is None and cfg.column_batch is not None:
            column_batch = cfg.column_batch
        if memory_budget_bytes is None and cfg.memory_budget_bytes is not None:
            memory_budget_bytes = cfg.memory_budget_bytes
    if memory_budget_bytes is None:
        memory_budget_bytes = default_memory_budget_bytes()
    return _assemble_cache_key(
        signature,
        canons,
        name,
        DtypePolicy.resolve(dtype_policy),
        ("chunk", int(chunk_size)) if chunk_size else ("budget", int(memory_budget_bytes)),
        column_batch,
        None if cfg is None else cfg.key_fragment(),
    )


class CountingEngine:
    """Batched color-coding counting runs over one graph.

    Args:
      graph: the network.
      templates: one :class:`Template` or a sequence of same-``k`` templates
        counted together per coloring (shared leaf one-hot / DP states).
      backend: ``auto`` | ``edges`` | ``ell`` | ``sell`` | ``dense`` |
        ``blocked`` | ``mixed`` | ``mesh``.  ``auto`` runs the resolution
        ladder (:func:`repro.exec.select.resolve_backend_config`):
        ``REPRO_ENGINE_BACKEND`` env override, then a tuned config (passed
        as ``tuning=`` or found in the tuning cache under ``REPRO_TUNE``),
        then graph-statistics heuristics — or resolves to ``mesh`` when
        ``mesh=`` is given.  ``mixed`` requires ``tuning=``.  Ignored when
        ``spmm_fn`` is given.
      spmm_fn: optional custom ``(n, C) -> (n, C)`` neighbor-sum kernel.
      dtype_policy: ``fp32`` | ``bf16`` | a :class:`DtypePolicy` | a dtype.
      memory_budget_bytes: live-footprint budget steering the chunk picker
        (per device — for the mesh backend the model is per shard).
        ``None`` resolves to the tuned config's budget (the tuner sweeps
        it) when one binds, else a share of the device's reported memory
        (``DEFAULT_MEMORY_BUDGET_BYTES`` where it reports none, as on CPU).
      chunk_size: explicit colorings-per-chunk override (skips the picker).
      plans: optional pre-built :class:`CountingPlan` per template.
      block_size / interpret: fused Pallas kernel knobs (``blocked``).
      column_batch: passive columns aggregated per fused SpMM+eMA slice.
        ``None`` auto-sizes: ``min(16, max passive columns)`` on the local
        backends, ``min(128, max passive columns)`` on the mesh backend
        (where a batch is also one all-gather collective).
      mesh / ema_mode / gather_dtype / balance_degrees / mesh_comm:
        mesh-backend knobs — see :class:`repro.exec.mesh.MeshBackend`
        (``mesh_comm`` forces ``blocking`` | ``pipelined`` collectives;
        ``None`` lets ``REPRO_MESH_COMM`` or the cost model's
        ``comm_schedule`` decide; a tuned config may also carry it).
      tuning: optional :class:`repro.tune.config.TuningConfig` (what
        ``python -m repro.tune`` / ``svc.tune`` produce) — binds per-group
        backends and overrides ``column_batch``/``chunk_size`` wherever the
        caller left them ``None``.  Beaten by an explicit ``backend=`` or
        the env override; ``describe()["backend"]["source"]`` records who
        won.

    The bound plan is ``engine.plan_ir``, the resource model is
    ``engine.cost``, the execution strategy is ``engine.backend_impl``.
    """

    def __init__(
        self,
        graph: Graph,
        templates: Union[Template, Sequence[Template]],
        *,
        backend: str = "auto",
        spmm_fn: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
        dtype_policy: Union[str, DtypePolicy, jnp.dtype, None] = "fp32",
        memory_budget_bytes: Optional[int] = None,
        chunk_size: Optional[int] = None,
        plans: Optional[Sequence[CountingPlan]] = None,
        block_size: int = BLOCKED_BLOCK_SIZE,
        interpret: bool = False,
        mesh=None,
        column_batch: Optional[int] = None,
        ema_mode: str = "streamed",
        gather_dtype=None,
        balance_degrees: bool = True,
        mesh_comm: Optional[str] = None,
        tuning=None,
    ):
        if isinstance(templates, Template):
            templates = [templates]
        if not templates:
            raise ValueError("CountingEngine needs at least one template")

        # fault-injection seam: construction is the first failure surface a
        # serving deployment meets (compile errors, operand OOMs) — the
        # chaos suite breaks it here, before any operand binds
        _faults.maybe_fail("engine_build", ctx=f"backend={backend}")

        # --- layer 1: the backend-agnostic plan (pure, graph-free).
        self.plan_ir: TemplatePlan = build_template_plan(templates, plans=plans)
        self.graph = graph
        self.templates: Tuple[Template, ...] = self.plan_ir.templates
        self.plans: Tuple[CountingPlan, ...] = self.plan_ir.counting_plans
        self.k = self.plan_ir.k
        self.policy = DtypePolicy.resolve(dtype_policy)
        self.interpret = interpret
        self.mesh = mesh

        # --- layer 2: the calibrated cost model.
        self.cost = CostModel(self.plan_ir, graph, self.policy.store_dtype)

        # --- backend resolution (operands bound once, below).  Runs before
        # the column-batch/chunk knobs are consumed: a tuned config may
        # override both, and only un-overridden (None) caller args yield.
        self._tuning = None
        if spmm_fn is not None:
            self.backend = "custom"
            self.backend_source = "custom"
            self.backend_reason = "caller-supplied spmm_fn"
        elif backend == "auto" and mesh is not None:
            self.backend = "mesh"
            self.backend_source = "mesh"
            self.backend_reason = "mesh= given"
        else:
            if backend != "auto" and backend not in ENGINE_BACKENDS:
                raise ValueError(
                    f"unknown backend {backend!r} (one of {ENGINE_BACKENDS})"
                )
            name, source, reason, cfg = resolve_backend_config(
                graph,
                backend=backend,
                canons=self.plan_ir.canons,
                tuning=tuning,
            )
            self.backend = name
            self.backend_source = source
            self.backend_reason = reason
            self._tuning = cfg
            if cfg is None and tuning is not None:
                # a config was offered but env/explicit resolution beat it —
                # surface that, an operator override silently eating a tuned
                # config is exactly the ambiguity the source field exists for
                logger.info(
                    "tuned config ignored: backend resolved by %s (%s)",
                    source,
                    reason,
                )
            if cfg is not None:
                if column_batch is None and cfg.column_batch is not None:
                    column_batch = cfg.column_batch
                if chunk_size is None and cfg.chunk_size is not None:
                    chunk_size = cfg.chunk_size
                if mesh_comm is None:
                    mesh_comm = getattr(cfg, "mesh_comm", None)

        # Budget resolution mirrors the other tuned knobs: an explicit
        # caller budget wins, else the budget the winning config was tuned
        # under, else the default — and it is part of the cache key, so
        # differently-budgeted engines never share compiled programs.
        if memory_budget_bytes is None and self._tuning is not None:
            memory_budget_bytes = self._tuning.memory_budget_bytes
        self.memory_budget_bytes = int(
            default_memory_budget_bytes()
            if memory_budget_bytes is None
            else memory_budget_bytes
        )

        # Fused-slice width: local default keeps the per-batch edge gather
        # cache-sized; the mesh backend auto-sizes its own (one batch there
        # is also one all-gather collective).
        if column_batch:
            self.column_batch = int(column_batch)
        else:
            self.column_batch = self.cost.pick_local_column_batch()

        norm = colorful_probability(self.k)
        # applied on the host in float64 (estimates outgrow fp32 first)
        self._norm_factors = np.asarray(
            [1.0 / (norm * plan.automorphisms) for plan in self.plans], np.float64
        )

        # Observability counters, Python-level: ``trace_count`` bumps once
        # per jit trace (== compilation), ``passive_aggregations`` once per
        # traced aggregation launch — a warm engine replaying compiled
        # programs holds steady on both.
        self.trace_count = 0
        self.counters: Dict[str, int] = {"passive_aggregations": 0}

        # --- layer 3: bind the plan to devices.
        self.backend_impl: EngineBackend = make_backend(
            self,
            spmm_fn=spmm_fn,
            block_size=block_size,
            mesh=mesh,
            column_batch=column_batch,
            ema_mode=ema_mode,
            gather_dtype=gather_dtype,
            balance_degrees=balance_degrees,
            mesh_comm=mesh_comm,
            tuning=self._tuning if self._tuning is not None else tuning,
        )

        # remembered for the cache key: a None chunk means "picked from the
        # budget", which is itself deterministic given the budget
        self._chunk_explicit = bool(chunk_size)
        self._column_batch_arg = column_batch
        self.chunk_size = int(chunk_size) if chunk_size else self.cost.pick_chunk_size(
            self.bytes_per_coloring(),
            self.memory_budget_bytes,
            self.backend_impl.max_chunk_size(),
        )

        self._graph_signature: Optional[str] = None  # computed lazily
        if logger.isEnabledFor(logging.INFO):
            # describe() hashes the graph (O(|E|) host work) — only pay for
            # it when the line is actually emitted; services that want the
            # record call describe() themselves
            d = self.describe()
            logger.info(
                "CountingEngine backend=%s (%s: %s) n=%d edges=%d k=%d templates=%d "
                "column_batch=%d chunk=%d predicted transient=%.2f MiB "
                "resident=%.2f MiB per coloring",
                d["backend"]["name"],
                d["backend"]["source"],
                d["backend"]["reason"],
                d["n"],
                d["num_directed"],
                d["k"],
                len(self.templates),
                d["column_batch"],
                d["chunk_size"],
                d["memory"]["predicted_transient_bytes"] / 2**20,
                d["memory"]["predicted_resident_bytes"] / 2**20,
            )

        self._run_fn = None  # built lazily (jit cache)
        self._chunk_fn = None  # streaming per-chunk jit (serving path)
        self._raw_fn = None  # fixed-coloring jit (raw_counts)

    # ------------------------------------------------------------------
    # Plan-derived views (compat names preserved for tests/benchmarks)
    # ------------------------------------------------------------------

    @property
    def _canons(self) -> Tuple[Tuple[str, ...], ...]:
        return self.plan_ir.canons

    @property
    def _free_at(self):
        return self.plan_ir.free_at

    @property
    def _exec_groups(self):
        return self.plan_ir.exec_groups

    @property
    def _stage_tables(self):
        """Device-bound split tables of the local backends (empty for mesh,
        which builds its own streamed tables at the all-gather width)."""
        return getattr(self.backend_impl, "stage_tables", {})

    def peak_columns(self) -> int:
        """Peak live M columns per coloring across the shared DP.

        Liveness-aware: states shared across templates by canonical form
        are freed at their last scheduled read, and the fused pipeline
        never holds an aggregate product, so the figure is the simulated
        peak of the schedule (for a single template it equals the in-place
        bound ``CountingPlan.peak_columns()``).
        """
        return self.plan_ir.peak_columns

    def _max_passive_columns(self) -> int:
        return self.plan_ir.max_passive_columns

    def _max_stage_columns(self) -> int:
        """Widest single stage: active + passive + output columns (the fused
        Pallas kernel's per-stage transposed staging footprint)."""
        return self.plan_ir.max_stage_columns

    # ------------------------------------------------------------------
    # Identity & observability (the serving layer builds on these)
    # ------------------------------------------------------------------

    def graph_signature(self) -> str:
        """Content hash of the graph (memoized; see :meth:`Graph.signature`)."""
        if self._graph_signature is None:
            self._graph_signature = self.graph.signature()
        return self._graph_signature

    def cache_key(self) -> Tuple:
        """This engine's :func:`engine_cache_key` (resolved values).

        Matches what a caller computes *before* construction with the same
        arguments, so ``CountingService`` can look up a warm engine without
        building one.  Only meaningful for the named local backends — a
        ``custom`` ``spmm_fn``'s identity is not captured by the key.
        """
        return _assemble_cache_key(
            self.graph_signature(),
            self.plan_ir.canons,
            self.backend,
            self.policy,
            ("chunk", self.chunk_size)
            if self._chunk_explicit
            else ("budget", self.memory_budget_bytes),
            self._column_batch_arg,
            None if self._tuning is None else self._tuning.key_fragment(),
        )

    def describe(self) -> Dict:
        """Structured construction/decision record: the backend decision
        and its reason, shapes, dtype policy, chunk plan, memory model,
        and the bound plan's summary — what the construction log line
        says, machine-readable (services attach it to cache entries)."""
        itemsize = jnp.dtype(self.policy.store_dtype).itemsize
        describe_comm = getattr(self.backend_impl, "describe_comm", None)
        return {
            # nested: which rung of the resolution ladder decided (explicit /
            # env / tuned / heuristic — plus custom / mesh), with the bound
            # TuningConfig's summary when one is live
            "backend": {
                "name": self.backend,
                "source": self.backend_source,
                "reason": self.backend_reason,
                "tuning": None if self._tuning is None else self._tuning.describe(),
            },
            "n": self.graph.n,
            "num_directed": self.graph.num_directed,
            "k": self.k,
            "templates": [t.name for t in self.templates],
            "dtype_policy": {
                "store": str(jnp.dtype(self.policy.store_dtype)),
                "accum": str(jnp.dtype(self.policy.accum_dtype)),
            },
            # the mesh backend aggregates at its own all-gather batch width
            "column_batch": getattr(self.backend_impl, "column_batch", self.column_batch),
            "chunk_size": self.chunk_size,
            # mesh backends: the resolved collective scheme + per-stage
            # comm schedule (None on local backends)
            "comm": describe_comm() if describe_comm is not None else None,
            "shared_passive_groups": sum(
                1 for m in self.plan_ir.exec_groups.values() if len(m) > 1
            ),
            "plan": self.plan_ir.describe(),
            "memory": {
                "budget_bytes": self.memory_budget_bytes,
                "fusion_slack": self.cost.fusion_slack,
                "predicted_transient_bytes": self.backend_impl.transient_elements()
                * itemsize,
                "predicted_resident_bytes": self.backend_impl.resident_elements()
                * itemsize,
                "bytes_per_coloring": self.bytes_per_coloring(),
            },
            "graph_signature": self.graph_signature(),
            "cache_key": self.cache_key(),
        }

    # ------------------------------------------------------------------
    # Memory planning (delegated to the cost model + backend geometry)
    # ------------------------------------------------------------------

    def bytes_per_coloring(self) -> int:
        """Calibrated live bytes one coloring contributes to a chunk.

        The cost model's formula fed with the bound backend's operand
        geometry: resident M-matrix state plus the widest per-stage
        transient (edge/row gather scratch for the local backends;
        all-gather buffer + per-shard message gather for the mesh backend,
        where the figure is per shard), corrected by the fusion-slack
        factor.
        """
        return self.backend_impl.bytes_per_coloring()

    def predicted_peak_bytes(self) -> int:
        """The chunk picker's live-footprint prediction for one chunk."""
        return self.chunk_size * self.bytes_per_coloring()

    def compiled_memory_analysis(self, iterations: Optional[int] = None) -> Dict[str, Optional[float]]:
        """Compile one run and compare XLA's measured temp allocation with
        the chunk picker's prediction (the fusion-slack calibration data:
        ``benchmarks/bench_counting`` commits the ratios as
        ``memory_model`` rows, which :func:`repro.plan.cost.
        load_fusion_slack` folds back into the picker).

        Returns ``{"predicted_bytes", "actual_temp_bytes", "ratio"}`` with
        ``actual_temp_bytes`` / ``ratio`` ``None`` when the backend does not
        expose ``memory_analysis()`` (it is optional in XLA).
        """
        iters = int(iterations) if iterations else self.chunk_size
        chunk = max(1, min(self.chunk_size, iters))
        n_chunks = -(-iters // chunk)
        keys = jnp.zeros((n_chunks, chunk, 2), jnp.uint32)
        predicted = float(self.predicted_peak_bytes())
        actual: Optional[float] = None
        try:
            compiled = self._get_run_fn().lower(keys).compile()
            analysis = compiled.memory_analysis()
            actual = float(analysis.temp_size_in_bytes)
        except (AttributeError, NotImplementedError, TypeError) as exc:  # pragma: no cover
            logger.info("memory_analysis unavailable on this backend: %s", exc)
        except Exception as exc:  # pragma: no cover - backend-specific failures
            logger.info("memory_analysis failed: %s", exc)
        return {
            "predicted_bytes": predicted,
            "actual_temp_bytes": actual,
            "ratio": (predicted / actual) if actual else None,
        }

    # ------------------------------------------------------------------
    # Public API
    # ------------------------------------------------------------------

    def raw_counts(self, colors) -> jnp.ndarray:
        """(n,) coloring -> (T,) raw colorful totals (test/inspection hook)."""
        if self._raw_fn is None:
            self._raw_fn = self.backend_impl.jit(self.backend_impl.counts_for_colors)
        colors = jnp.asarray(colors)
        return self._raw_fn(colors[None, :])[0]

    def _get_run_fn(self):
        if self._run_fn is None:
            self._run_fn = self.backend_impl.make_run_fn()
        return self._run_fn

    def _get_chunk_fn(self):
        if self._chunk_fn is None:
            impl = self.backend_impl

            def chunk_run(keys):
                self.trace_count += 1
                return impl.counts_for_keys_chunk(keys)

            self._chunk_fn = impl.jit(chunk_run)
        return self._chunk_fn

    def count_keys_chunk(self, keys) -> np.ndarray:
        """Streaming increment: one chunk-shaped launch, results back now.

        The serving path: callers stream iterations through repeated calls
        (adaptive stopping folds each increment into its running estimate)
        instead of fixing N upfront.  ``keys`` is ``(m, 2)`` with
        ``m <= chunk_size``; short increments are padded with the last key
        up to ``chunk_size`` so every call hits ONE compiled shape — a warm
        engine never re-traces, whatever increment sizes arrive
        (shape-bucketed padding).  Returns the ``(m, T)`` normalized
        estimates as a float64 host array.

        Fault seams (``repro.testing.faults``) fire HERE, at the Python
        launch boundary, not inside the backend's jitted body — an in-jit
        hook would only run at trace time, so a warm engine would never
        see it.  ``launch`` covers every backend; ``collective`` only the
        backends that declare it (``EngineBackend.fault_sites``).
        """
        keys = jnp.asarray(keys)
        m = int(keys.shape[0])
        if m == 0:
            return np.zeros((0, len(self.templates)), np.float64)
        if m > self.chunk_size:
            raise ValueError(
                f"increment of {m} keys exceeds chunk_size={self.chunk_size}; "
                "split it (count_keys handles multi-chunk runs)"
            )
        _faults.maybe_fail("launch", ctx=f"backend={self.backend}")
        if "collective" in getattr(self.backend_impl, "fault_sites", ()):
            # the pipelined mesh path crosses the collective seam once per
            # ring step (blocking: once per launch) — the injection site
            # fires with matching multiplicity so a seeded fault plan sees
            # every dispatch
            for step in range(getattr(self.backend_impl, "collective_dispatches", 1)):
                _faults.maybe_fail(
                    "collective", ctx=f"backend={self.backend} step={step}"
                )
        pad = self.chunk_size - m
        if pad:
            keys = jnp.concatenate([keys, keys[-1:].repeat(pad, axis=0)], axis=0)
        vals = self._get_chunk_fn()(keys)
        out = np.asarray(vals, dtype=np.float64)[:m] * self._norm_factors
        return _faults.corrupt_result("launch", out, ctx=f"backend={self.backend}")

    def count_keys(self, keys) -> np.ndarray:
        """Normalized per-iteration estimates for explicit PRNG keys.

        ``keys``: (iters, 2) uint32 PRNG keys (``jax.random.split`` output).
        Returns an (iters, T) float64 host array; all device work happens in
        one jit call (chunked ``lax.map`` over ``chunk_size``-wide batches).
        """
        keys = jnp.asarray(keys)
        iters = keys.shape[0]
        chunk = max(1, min(self.chunk_size, iters))
        n_chunks = -(-iters // chunk)
        pad = n_chunks * chunk - iters
        if pad:
            keys = jnp.concatenate([keys, keys[-1:].repeat(pad, axis=0)], axis=0)
        vals = self._get_run_fn()(keys.reshape(n_chunks, chunk, *keys.shape[1:]))
        flat = np.asarray(vals, dtype=np.float64).reshape(n_chunks * chunk, -1)
        return flat[:iters] * self._norm_factors

    def estimate(self, iterations: int = 32, seed: int = 0) -> List[EstimateResult]:
        """Run ``iterations`` random colorings; one :class:`EstimateResult`
        per template (paper Algorithm 1, batched)."""
        keys = jax.random.split(jax.random.PRNGKey(seed), iterations)
        vals = self.count_keys(keys)  # (iters, T)
        return [
            EstimateResult(
                mean=float(vals[:, t].mean()),
                std=float(vals[:, t].std()),
                per_iteration=vals[:, t],
                iterations=iterations,
            )
            for t in range(len(self.templates))
        ]
