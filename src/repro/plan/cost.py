"""The unified cost model: resource predictions for every execution target.

One :class:`CostModel` per (plan, graph, dtype) owns everything the engine
used to scatter across backends and the chunk picker:

* the **resident** figure — ``n * TemplatePlan.peak_columns`` live M-matrix
  elements per coloring (per shard on the mesh target, padded to the
  all-gather batch);
* the **transient** formulas per target — one fused ``column_batch``-wide
  slice of the backend's gather scratch (edge messages, padded rows, SELL
  groups, the all-gather buffer);
* **column-batch picking** — the fused-slice width per target;
* **chunk picking** — the largest coloring chunk whose live footprint fits
  the memory budget, with the analytic byte model corrected by the

**fusion-slack factor**: the analytic model is compared against XLA's
measured temp allocation on every bench run
(``CountingEngine.compiled_memory_analysis``) and the predicted/actual
ratios are committed as ``memory_model`` rows in ``BENCH_counting.json``.
:func:`load_fusion_slack` folds their geometric mean back into the picker
(effective bytes = analytic bytes / slack), so the picker stops trusting
the analytic model blindly.  With no bench rows the factor is a safe 1.0;
whenever calibration is applied it is logged on the ``repro.plan`` logger.
"""

from __future__ import annotations

import json
import logging
import math
import os
from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "CostModel",
    "AdmissionEstimate",
    "admission_estimate",
    "CommSchedule",
    "LadderRung",
    "degradation_ladder",
    "RankedCandidate",
    "load_fusion_slack",
    "load_backend_calibration",
    "fusion_slack_factor",
    "mesh_link_bytes_per_us",
    "pick_chunk_size",
    "DEFAULT_MEMORY_BUDGET_BYTES",
    "DEVICE_BUDGET_FRACTION",
    "TPU_LANES",
    "default_memory_budget_bytes",
    "MAX_CHUNK_SIZE",
    "EDGE_CHUNK",
    "LOCAL_COLUMN_BATCH",
    "MESH_COLUMN_BATCH",
    "MESH_LINK_BYTES_PER_US",
    "MESH_LINK_ENV_VAR",
    "RING_STEP_OVERHEAD_US",
    "SLACK_CLAMP",
    "BENCH_ENV_VAR",
    "CALIBRATION_CLAMP",
]

logger = logging.getLogger("repro.plan")

#: Live-footprint budget for one chunk of colorings (bytes) on a device
#: that reports no memory limit (the CPU backend).
DEFAULT_MEMORY_BUDGET_BYTES = 32 * 1024 * 1024

#: Share of a device's reported memory one chunk's live state may fill; the
#: rest holds the graph operands, XLA temporaries and allocator slack.
DEVICE_BUDGET_FRACTION = 0.5

#: Minor-dimension tile width of TPU arrays: the DP state's column axis is
#: the minor one of its ``(n, B, C)`` layout, so on a TPU every state and
#: gathered slice occupies whole 128-lane tiles.
TPU_LANES = 128

#: Hard cap on colorings fused into one chunk (diminishing returns beyond).
MAX_CHUNK_SIZE = 64

#: Most edges the ``edges`` backend gathers in one step (longer edge lists
#: are reduced chunk by chunk, bounding the gathered-message transient).
EDGE_CHUNK = 1 << 21

#: Default passive columns per fused SpMM+eMA slice on the local backends.
#: Empirically (2-core XLA:CPU interleaved A/B on the rmat2k bench graphs):
#: 16 beats both narrower slices (the per-call segment-sum fixed cost is
#: paid more often) and the full-width two-pass dataflow (whose edge-wide
#: transient thrashes cache), while keeping the chunk picker's fused
#: transient small enough to grow coloring chunks 2-4x over the seed.
LOCAL_COLUMN_BATCH = 16

#: Default passive columns per all-gather collective on the mesh target.
MESH_COLUMN_BATCH = 128

#: Calibration ratios outside this band are treated as measurement noise
#: (a wildly off bench row must not starve or blow the chunk picker).
SLACK_CLAMP = (0.5, 2.0)

#: Environment override for the bench file the slack factor is read from.
BENCH_ENV_VAR = "REPRO_FUSION_SLACK_BENCH"

#: Per-backend calibration ratios outside this band are treated as noise —
#: the lattice is a *ranker*, a 100x ratio would let one bad probe freeze a
#: backend out of every future candidate set.
CALIBRATION_CLAMP = (0.1, 10.0)

#: Nominal cost of one gathered/FMA'd element in the per-stage work model
#: (microseconds; absolute scale is arbitrary — the lattice only ranks).
WORK_ELEMENT_US = 1e-3

#: Fixed cost per fused column-batch sweep call (dispatch + segment-sum /
#: einsum setup) — what makes narrow column batches predictedly worse.
SWEEP_OVERHEAD_US = 12.0

#: Fixed per-chunk-launch cost, amortized over the chunk's colorings —
#: what makes tiny chunks predictedly worse.
LAUNCH_OVERHEAD_US = 150.0

#: Nominal mesh link bandwidth (bytes per microsecond) for the comm model —
#: ~4 GB/s, a conservative single-NIC / host-interconnect figure.  On real
#: ICI calibrate via :data:`MESH_LINK_ENV_VAR`; absolute scale only shifts
#: the blocking/pipelined crossover, the comm model still ranks.
MESH_LINK_BYTES_PER_US = 4000.0

#: Environment override (float, bytes/us) for the link-bandwidth constant —
#: the comm model's calibration knob.
MESH_LINK_ENV_VAR = "REPRO_MESH_LINK_BYTES_PER_US"

#: Fixed cost per ring step (ppermute dispatch + slice bookkeeping): the
#: term that keeps narrow stages on the blocking path, where one all-gather
#: beats ``n_shards`` tiny hops.
RING_STEP_OVERHEAD_US = 2.0


def default_memory_budget_bytes() -> int:
    """The chunk picker's default budget: :data:`DEVICE_BUDGET_FRACTION` of
    the default device's ``memory_stats()["bytes_limit"]``, else
    :data:`DEFAULT_MEMORY_BUDGET_BYTES` where the device reports no limit
    (CPU)."""
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    if not limit:
        return DEFAULT_MEMORY_BUDGET_BYTES
    return int(limit * DEVICE_BUDGET_FRACTION)


def mesh_link_bytes_per_us() -> float:
    """The comm model's link bandwidth, env-calibratable (bytes/us > 0).

    Bad values warn once and fall back to the default — cost modeling must
    never crash on a typo'd env var."""
    raw = os.environ.get(MESH_LINK_ENV_VAR, "").strip()
    if not raw:
        return MESH_LINK_BYTES_PER_US
    try:
        val = float(raw)
        if val > 0:
            return val
    except ValueError:
        pass
    if raw not in _BAD_LINK_VALUES_WARNED:
        _BAD_LINK_VALUES_WARNED.add(raw)
        logger.warning(
            "%s=%r is not a positive float — using the default %.0f bytes/us",
            MESH_LINK_ENV_VAR, raw, MESH_LINK_BYTES_PER_US,
        )
    return MESH_LINK_BYTES_PER_US


_BAD_LINK_VALUES_WARNED: set = set()

#: memoized slack factors, keyed by resolved bench path ('' = missing).
_SLACK_CACHE: Dict[str, float] = {}


def _default_bench_path() -> Optional[str]:
    env = os.environ.get(BENCH_ENV_VAR, "").strip()
    if env:
        return env
    # src/repro/plan/cost.py -> repo root (the committed bench lives there)
    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    )
    return os.path.join(root, "BENCH_counting.json")


def load_fusion_slack(path: Optional[str] = None) -> float:
    """Empirical fusion-slack factor from committed ``memory_model`` rows.

    Each row's ``derived`` field records ``predicted_over_actual`` — the
    analytic byte model divided by XLA's measured temp allocation for one
    bench engine config.  The factor returned is the geometric mean of the
    ratios, clamped to :data:`SLACK_CLAMP`; ``< 1`` means the analytic
    model under-predicts, so the picker inflates its byte estimates by
    ``1 / slack``.  **Safe default**: 1.0 whenever the bench file or the
    rows are missing or unparsable — the picker then behaves exactly like
    the uncalibrated analytic model.  Applied calibration is logged once
    per path on the ``repro.plan`` logger.
    """
    resolved = path if path is not None else _default_bench_path()
    key = resolved or ""
    if key in _SLACK_CACHE:
        return _SLACK_CACHE[key]
    slack = 1.0
    ratios = []
    try:
        with open(resolved) as fh:
            bench = json.load(fh)
        rows = bench.get("rows", []) if isinstance(bench, dict) else []
        for row in rows:
            if not isinstance(row, dict):
                continue
            if "memory_model" not in str(row.get("name", "")):
                continue
            fields = {}
            for part in str(row.get("derived", "")).split(";"):
                if "=" in part:
                    name, _, val = part.partition("=")
                    fields[name] = val
            try:
                ratio = float(fields["predicted_over_actual"])
                # rows written by a calibrated picker already fold a slack
                # into their prediction; multiply it back out so the loader
                # always sees the RAW analytic-model ratio (fixed point:
                # re-benching with calibration on does not double-correct)
                ratio *= float(fields.get("applied_fusion_slack", 1.0))
                if ratio > 0:  # '%.3f'-rounded zeros would poison the mean
                    ratios.append(ratio)
            except (KeyError, ValueError):
                pass
        if ratios:
            mean_log = sum(math.log(r) for r in ratios) / len(ratios)
            slack = min(max(math.exp(mean_log), SLACK_CLAMP[0]), SLACK_CLAMP[1])
            logger.info(
                "fusion-slack calibration applied: factor=%.4f from %d "
                "memory_model bench rows (%s)",
                slack,
                len(ratios),
                resolved,
            )
        else:
            logger.debug(
                "no memory_model rows in %s — fusion slack defaults to 1.0",
                resolved,
            )
    except (OSError, ValueError, TypeError, AttributeError, KeyError) as exc:
        logger.debug(
            "fusion-slack bench unavailable (%s) — defaulting to 1.0", exc
        )
    _SLACK_CACHE[key] = slack
    return slack


def fusion_slack_factor() -> float:
    """The memoized default-path slack (what engines constructed without an
    explicit ``fusion_slack`` use)."""
    return load_fusion_slack()


def load_backend_calibration(path: Optional[str] = None) -> Dict[str, float]:
    """Per-backend measured/predicted cost ratios from the tuning cache.

    The generalization of the fusion-slack mechanism to *time*: every
    tuning run records, for each uniform candidate it measured, the ratio
    of measured us-per-coloring to the lattice's raw (uncalibrated)
    prediction; :meth:`CostModel.candidate_lattice` multiplies each
    backend's predicted cost by its ratio, so rankings improve with every
    run even for workloads never tuned directly.  Ratios are clamped to
    :data:`CALIBRATION_CLAMP`; a missing/corrupt cache yields ``{}`` (the
    uncalibrated analytic ranking) — same safe-default contract as
    :func:`load_fusion_slack`.
    """
    # local import: repro.tune.cache is a leaf over repro.tune.config only
    from repro.tune.cache import load_calibration

    out = {}
    for name, ratio in load_calibration(path).items():
        out[name] = min(max(float(ratio), CALIBRATION_CLAMP[0]), CALIBRATION_CLAMP[1])
    return out


def _dense_work_advantage() -> int:
    # exec.select owns the constant (it imports nothing from plan)
    from repro.exec.select import DENSE_WORK_ADVANTAGE

    return DENSE_WORK_ADVANTAGE


@dataclass(frozen=True)
class RankedCandidate:
    """One point of the tuner's candidate lattice.

    ``predicted_us`` is the calibrated per-coloring cost estimate used for
    ranking/pruning; ``raw_us`` is the same figure *without* per-backend
    calibration (what measured ratios are computed against, so calibration
    reaches a fixed point instead of compounding run over run).
    """

    config: object  # TuningConfig (typed loosely: repro.tune is downstream)
    predicted_us: float
    raw_us: float


def pick_chunk_size(
    bytes_per_coloring: int,
    memory_budget_bytes: int,
    max_chunk: int = MAX_CHUNK_SIZE,
) -> int:
    """Largest chunk whose live footprint stays under the budget (>= 1)."""
    if bytes_per_coloring <= 0:
        return max_chunk
    return max(1, min(max_chunk, int(memory_budget_bytes // bytes_per_coloring)))


@dataclass(frozen=True)
class AdmissionEstimate:
    """Predicted footprint of one query, for serving-layer load shedding.

    Computed from the plan alone (no engine, no device operands, no
    compile), so the front-end can price a query at submit time in
    microseconds.  ``resident_bytes`` is the calibrated per-coloring
    live-DP-state figure; ``chunk_bytes`` is what one launch of the
    engine that would serve this query keeps live
    (``chunk_size * resident_bytes`` — the admission currency the
    front-end budgets against).  The backend gather transient is excluded
    on purpose: it is backend-geometry-specific and only known once an
    engine binds, so admission prices the dominant, backend-independent
    term and stays conservative-but-cheap.
    """

    resident_elements: int
    resident_bytes: int  # calibrated, per coloring
    chunk_size: int
    chunk_bytes: int  # resident_bytes * chunk_size — one launch's residency
    peak_columns: int


def admission_estimate(
    graph,
    templates,
    *,
    store_dtype=jnp.float32,
    chunk_size: Optional[int] = None,
    memory_budget_bytes: Optional[int] = None,
    fusion_slack: Optional[float] = None,
) -> AdmissionEstimate:
    """Price a ``(graph, templates)`` query without building an engine.

    Plans the template set (:func:`repro.plan.ir.build_template_plan` is
    pure and host-side), then reads the :class:`CostModel` resident
    formula — the same one the engine's chunk picker uses, including the
    empirical fusion-slack calibration — so the admission figure and the
    engine's own ``predicted_peak_bytes()`` agree on the resident term.
    With no explicit ``chunk_size`` the chunk is picked against
    ``memory_budget_bytes`` exactly as an engine construction would.
    """
    from .ir import build_template_plan  # local: keeps import cycles out

    plan = build_template_plan(list(templates))
    cm = CostModel(plan, graph, store_dtype, fusion_slack=fusion_slack)
    resident = cm.resident_elements()
    per_coloring = cm.bytes_per_coloring(0, resident)
    if memory_budget_bytes is None:
        memory_budget_bytes = default_memory_budget_bytes()
    chunk = (
        int(chunk_size)
        if chunk_size
        else cm.pick_chunk_size(per_coloring, memory_budget_bytes)
    )
    return AdmissionEstimate(
        resident_elements=resident,
        resident_bytes=per_coloring,
        chunk_size=chunk,
        chunk_bytes=per_coloring * chunk,
        peak_columns=plan.peak_columns,
    )


@dataclass(frozen=True)
class CommSchedule:
    """One DP stage's plan-time communication decision on the mesh target.

    ``mode`` is ``"blocking"`` (one all-gather per column batch) or
    ``"pipelined"`` (the double-buffered ring; ``ring_steps == n_shards``
    ``ppermute`` hops per batch, the next row slice in flight while the
    current one's edge messages are computed).  ``wire_bytes`` is the
    per-shard, per-coloring bytes on the wire for the whole stage;
    ``comm_us`` / ``compute_us`` are its modeled transfer and per-shard
    SpMM+eMA times; ``overlap_efficiency`` is the fraction of the wire
    time the ring hides under compute (``min(1, compute_step /
    comm_step)``).  ``reason`` records why the mode was picked (or
    forced).
    """

    stage: "Tuple[int, int]"  # exec-group leader (plan_idx, sub_idx)
    mode: str
    ring_steps: int  # 1 for blocking, n_shards for pipelined
    slice_rows: int  # rows_per_shard — the circulated slice height
    slice_cols: int  # column_batch — the circulated slice width
    wire_bytes: int
    comm_us: float
    compute_us: float
    overlap_efficiency: float
    reason: str

    def describe(self) -> Dict:
        return {
            "stage": list(self.stage),
            "mode": self.mode,
            "ring_steps": self.ring_steps,
            "slice_rows": self.slice_rows,
            "slice_cols": self.slice_cols,
            "wire_bytes": self.wire_bytes,
            "comm_us": round(self.comm_us, 3),
            "compute_us": round(self.compute_us, 3),
            "overlap_efficiency": round(self.overlap_efficiency, 4),
            "reason": self.reason,
        }


@dataclass(frozen=True)
class LadderRung:
    """One step of the memory degradation ladder (see
    :func:`degradation_ladder`)."""

    chunk_size: int
    column_batch: Optional[int]  # None = keep the engine's auto-pick
    backend: Optional[str]  # None = keep the configured backend
    action: str  # "halve_chunk" | "shrink_columns" | "fallback_backend"


def degradation_ladder(
    chunk_size: int,
    column_batch: Optional[int],
    backend: str,
) -> "list[LadderRung]":
    """The ordered retreat a memory failure walks before a query is
    rejected.

    Cheapest-first — each rung trades throughput for footprint along a
    knob the cost model already prices (so ``admission_estimate`` can
    re-price every rung without building anything):

    1. **halve ``chunk_size``** down to 1: the chunk is the multiplier on
       the whole live footprint, so halving it halves the launch residency
       with zero effect on results (estimates are bit-exact across chunk
       sizes — the engine invariant the retry path already leans on);
    2. **shrink ``column_batch``** (halving from its configured width down
       to 1, chunk pinned at 1): narrows the fused-slice transient;
    3. **fall back to the ``edges`` backend**: the smallest-transient
       executor (no padded rows, no SELL slots, no dense adjacency).

    Returns the rungs *below* the given configuration; an exhausted ladder
    (empty list / no rungs left) means the query genuinely cannot fit and
    fails with ``memory_exhausted``.
    """
    rungs = []
    chunk = int(chunk_size)
    while chunk > 1:
        chunk //= 2
        rungs.append(
            LadderRung(
                chunk_size=chunk, column_batch=None, backend=None,
                action="halve_chunk",
            )
        )
    cb = int(column_batch) if column_batch else LOCAL_COLUMN_BATCH
    while cb > 1:
        cb //= 2
        rungs.append(
            LadderRung(
                chunk_size=1, column_batch=cb, backend=None,
                action="shrink_columns",
            )
        )
    if backend not in ("edges", "custom", "mesh"):
        rungs.append(
            LadderRung(
                chunk_size=1, column_batch=1, backend="edges",
                action="fallback_backend",
            )
        )
    return rungs


class CostModel:
    """Resource predictions for one ``TemplatePlan`` on one graph.

    All element counts are *store-dtype elements per coloring*; byte
    figures multiply by the store itemsize and divide by the fusion-slack
    factor, so everything downstream (the chunk picker, ``describe()``,
    the bench calibration rows) sees one consistent, calibrated model.

    Operand-geometry arguments (``sell_padded_slots``, the mesh shard
    shape) are supplied by the bound backend — the formulas live here, the
    measurements live with the operands.
    """

    def __init__(
        self,
        plan,
        graph,
        store_dtype=jnp.float32,
        *,
        fusion_slack: Optional[float] = None,
    ):
        self.plan = plan
        self.graph = graph
        self.itemsize = jnp.dtype(store_dtype).itemsize
        #: column-axis tile width of the device's arrays (1: no padding)
        self.lanes = TPU_LANES if jax.default_backend() == "tpu" else 1
        self.fusion_slack = (
            load_fusion_slack() if fusion_slack is None else float(fusion_slack)
        )
        if not SLACK_CLAMP[0] <= self.fusion_slack <= SLACK_CLAMP[1]:
            raise ValueError(
                f"fusion_slack {self.fusion_slack} outside sane band {SLACK_CLAMP}"
            )

    # -- column-batch picking ------------------------------------------------

    def pick_local_column_batch(self) -> int:
        """Fused-slice width for the single-device backends: at least one
        lane tile on a TPU, where a narrower slice is padded to the tile's
        width and pays for it in every sweep."""
        return min(max(LOCAL_COLUMN_BATCH, self.lanes), self.plan.max_passive_columns)

    def pick_mesh_column_batch(self) -> int:
        """Columns per all-gather collective on the mesh target."""
        return min(MESH_COLUMN_BATCH, max(self.plan.max_passive_columns, self.plan.k))

    # -- local targets -------------------------------------------------------

    def resident_elements(self) -> int:
        """Live DP-state elements one coloring keeps resident.

        Tree-only plans: ``n`` rows times the plan's liveness-aware peak
        columns (unchanged).  Plans with bag stages use the element-level
        liveness peak — a bag state over ``r`` live axes is an
        ``n**r * C(k, m)`` tensor, so the row factor is no longer uniform.
        """
        if getattr(self.plan, "has_bag_stages", False):
            return self.plan.peak_elements(self.graph.n)
        if self.lanes > 1:
            return self.graph.n * self.plan.padded_peak_columns(self.lanes)
        return self.graph.n * self.plan.peak_columns

    def _lane_padded(self, columns: int) -> int:
        return -(-columns // self.lanes) * self.lanes

    def transient_elements(
        self,
        target: str,
        column_batch: int,
        *,
        sell_padded_slots: Optional[int] = None,
    ) -> int:
        """Widest per-stage scratch one coloring needs on ``target``.

        One fused slice: the backend's gather intermediate plus the
        aggregated ``(n, column_batch)`` slice — never the full passive
        width (that is the fused pipeline's whole point).

        Plans with bag stages take the max with the bag-op scratch
        (:meth:`bag_transient_elements`) — bag-join contractions run
        un-batched over the flattened state, so their slice can dominate.
        """
        g = self.graph
        if target != "blocked":
            # the blocked kernel keeps vertices on lanes; the XLA backends'
            # slices put the column batch there
            column_batch = self._lane_padded(column_batch)
        if target == "edges":
            out = (min(g.num_directed, EDGE_CHUNK) + g.n) * column_batch
        elif target == "custom":
            out = (g.num_directed + g.n) * column_batch
        elif target == "ell":
            out = (g.n * max(g.max_degree(), 1) + g.n) * column_batch
        elif target == "sell":
            if sell_padded_slots is None:
                raise ValueError("sell transient needs the built SELL geometry")
            out = (sell_padded_slots + g.n) * column_batch
        elif target == "dense":
            out = g.n * column_batch
        elif target == "blocked":
            # transposed-layout staging of one stage's operands/output; no
            # edge-wide or (n, C_p) aggregate intermediate exists
            out = g.n * self.plan.max_stage_columns
        else:
            raise ValueError(f"unknown cost target {target!r}")
        if getattr(self.plan, "has_bag_stages", False):
            out = max(
                out,
                self.bag_transient_elements(
                    target, sell_padded_slots=sell_padded_slots
                ),
            )
        return out

    def bag_transient_elements(
        self, target: str, *, sell_padded_slots: Optional[int] = None
    ) -> int:
        """Widest per-bag-op scratch one coloring needs on ``target``.

        Two shapes compete: the SpMM contraction of an ``extend`` runs the
        backend's gather intermediate over the *flattened* trailing width
        ``n**(r_in - 1) * C(k, m_in)`` (bag contractions are not
        column-batched), and the color-table loop of an extend/join holds
        two gathered operands plus the accumulator — three output-state
        tensors of ``n**r_out * C(k, m_out)`` elements.
        """
        # local import: core.engine imports this module at load time
        from repro.core.colorsets import binom

        g = self.graph
        if target in ("edges", "custom"):
            per_col = g.num_directed + g.n
        elif target == "ell":
            per_col = g.n * max(g.max_degree(), 1) + g.n
        elif target == "sell":
            if sell_padded_slots is None:
                raise ValueError("sell transient needs the built SELL geometry")
            per_col = sell_padded_slots + g.n
        elif target in ("dense", "blocked"):
            per_col = g.n
        else:
            raise ValueError(f"unknown cost target {target!r}")
        worst = 0
        for cplan in self.plan.counting_plans:
            if cplan.partition is not None:
                continue
            ops = cplan.bag_program.ops
            for op in ops:
                if op.kind == "leaf":
                    continue
                if op.kind == "extend" and op.spmm_vertex is not None:
                    src = ops[op.inputs[0]]
                    flat = g.n ** (len(src.axes) - 1) * binom(cplan.k, src.m)
                    worst = max(worst, per_col * flat)
                # gathered active/passive operands + the term accumulator
                r_out = len(op.axes) + len(op.forget_vertices)
                worst = max(worst, 3 * g.n**r_out * binom(cplan.k, op.m))
        return worst

    # -- mesh target (per shard!) --------------------------------------------

    def mesh_transient_elements(
        self, n_padded: int, edges_per_shard: int, column_batch: int
    ) -> int:
        """Per-shard collective scratch: one all-gathered column batch
        plus the per-shard edge message gather (one edge chunk of it)."""
        edges = min(edges_per_shard, EDGE_CHUNK)
        return (n_padded + edges) * self._lane_padded(column_batch)

    def mesh_resident_elements(
        self, rows_per_shard: int, column_batch: int, ema_mode: str = "streamed"
    ) -> int:
        """Per-shard live DP state: local rows times the liveness-aware
        peak of padded M columns (memoized SpMM products count too in the
        non-streamed eMA modes)."""
        peak = self.plan.padded_peak_columns(
            pad_unit=self._lane_padded(column_batch),
            track_products=(ema_mode != "streamed"),
        )
        return rows_per_shard * peak

    def comm_schedule(
        self,
        leader,
        n_shards: int,
        *,
        column_batch: int,
        rows_per_shard: Optional[int] = None,
        edges_per_shard: Optional[int] = None,
        link_bytes_per_us: Optional[float] = None,
        forced: Optional[str] = None,
    ) -> "CommSchedule":
        """Blocking vs pipelined for one exec group's mesh SpMM sweeps.

        Per stage, per shard, per coloring the collective moves
        ``(n_shards - 1) * rows * C_p_padded`` store elements regardless of
        mode; the ring buys back the fraction of that transfer it can hide
        under the stage's per-shard compute (edge-bucket gather + eMA).
        The decision rule: pipeline iff the predicted hidden time exceeds
        the ring's own dispatch overhead
        (``n_batches * n_shards * RING_STEP_OVERHEAD_US``).  ``forced``
        (``"blocking"`` | ``"pipelined"``) records an env/caller override
        verbatim — the model still fills in the diagnostic fields.
        """
        from repro.core.colorsets import binom  # local: cycle-free

        p_idx, i = leader
        cplan = self.plan.counting_plans[p_idx]
        sub = cplan.partition.subs[i]
        passive_cols = binom(cplan.k, cplan.partition.subs[sub.passive].size)
        cb = max(1, int(column_batch))
        n_batches = max(1, math.ceil(passive_cols / cb))
        padded_cols = n_batches * cb
        rows = (
            int(rows_per_shard)
            if rows_per_shard
            else max(1, -(-self.graph.n // max(1, n_shards)))
        )
        edges = (
            int(edges_per_shard)
            if edges_per_shard
            else max(1, -(-self.graph.num_directed // max(1, n_shards)))
        )
        link = link_bytes_per_us or mesh_link_bytes_per_us()
        wire_bytes = (n_shards - 1) * rows * padded_cols * self.itemsize
        comm_us = wire_bytes / link
        # per-shard compute: the edge-bucket gather over the stage's padded
        # passive width plus this shard's share of the group's eMA work
        gather = edges * padded_cols
        ema = 0
        for q, j in self.plan.exec_groups[leader]:
            mplan = self.plan.counting_plans[q]
            msub = mplan.partition.subs[j]
            ema += rows * binom(mplan.k, msub.size) * binom(
                msub.size, mplan.partition.subs[msub.active].size
            )
        compute_us = (gather + ema) * WORK_ELEMENT_US
        if n_shards >= 2:
            comm_step = comm_us / (n_shards - 1)
            compute_step = compute_us / n_shards
            overlap = min(1.0, compute_step / comm_step) if comm_step > 0 else 1.0
        else:
            overlap = 0.0
        hidden_us = overlap * comm_us
        ring_cost_us = n_batches * n_shards * RING_STEP_OVERHEAD_US
        if forced in ("blocking", "pipelined"):
            mode = forced
            reason = f"forced {forced} (env/caller override)"
        elif n_shards < 2:
            mode = "blocking"
            reason = "single shard — nothing to overlap"
        elif hidden_us > ring_cost_us:
            mode = "pipelined"
            reason = (
                f"hidden {hidden_us:.1f}us > ring overhead {ring_cost_us:.1f}us"
            )
        else:
            mode = "blocking"
            reason = (
                f"hidden {hidden_us:.1f}us <= ring overhead {ring_cost_us:.1f}us"
            )
        return CommSchedule(
            stage=(p_idx, i),
            mode=mode,
            ring_steps=n_shards if mode == "pipelined" else 1,
            slice_rows=rows,
            slice_cols=cb,
            wire_bytes=int(wire_bytes),
            comm_us=comm_us,
            compute_us=compute_us,
            overlap_efficiency=overlap,
            reason=reason,
        )

    def mesh_comm_schedules(
        self,
        n_shards: int,
        *,
        column_batch: int,
        rows_per_shard: Optional[int] = None,
        edges_per_shard: Optional[int] = None,
        link_bytes_per_us: Optional[float] = None,
        forced: Optional[str] = None,
    ) -> "Dict[Tuple[int, int], CommSchedule]":
        """The full per-stage comm plan: one :class:`CommSchedule` per tree
        exec-group leader (the unit one passive sweep serves)."""
        return {
            leader: self.comm_schedule(
                leader,
                n_shards,
                column_batch=column_batch,
                rows_per_shard=rows_per_shard,
                edges_per_shard=edges_per_shard,
                link_bytes_per_us=link_bytes_per_us,
                forced=forced,
            )
            for leader in self.tree_group_leaders()
        }

    # -- bytes + chunk -------------------------------------------------------

    def bytes_per_coloring(
        self, transient_elements: int, resident_elements: int
    ) -> int:
        """Calibrated live bytes one coloring contributes to a chunk.

        The analytic element model times the store itemsize, corrected by
        the empirical fusion-slack factor (``slack < 1`` means the model
        under-predicts, so the effective figure grows).
        """
        raw = (transient_elements + resident_elements) * self.itemsize
        return int(math.ceil(raw / self.fusion_slack))

    def pick_chunk_size(
        self,
        bytes_per_coloring: int,
        memory_budget_bytes: int = DEFAULT_MEMORY_BUDGET_BYTES,
        max_chunk: int = MAX_CHUNK_SIZE,
    ) -> int:
        return pick_chunk_size(bytes_per_coloring, memory_budget_bytes, max_chunk)

    def describe(self) -> Dict:
        out = {
            "fusion_slack": self.fusion_slack,
            "itemsize": self.itemsize,
            "peak_columns": self.plan.peak_columns,
            "resident_elements": self.resident_elements(),
        }
        if getattr(self.plan, "has_bag_stages", False):
            out["peak_elements"] = self.plan.peak_elements(self.graph.n)
            out["max_bag_axes"] = self.plan.max_bag_axes
        return out

    # -- tuning candidate lattice --------------------------------------------

    def feasible_backends(self, platform: Optional[str] = None) -> "list[str]":
        """Local backends worth *probing* for this (graph, plan).

        Wider than the heuristic's single pick, narrower than "everything":
        backends whose geometry would be pathological on this graph (ELL
        padding blown up by a hub row, an ``n x n`` dense adjacency that
        dwarfs the DP state) are excluded so the tuner never compiles them.
        ``blocked`` is TPU-only — on CPU the Pallas kernel runs in
        interpret mode, which is a correctness path, not a candidate.
        """
        g = self.graph
        edges = max(g.num_directed, 1)
        out = ["edges"]
        # probe-feasibility bound is deliberately looser than the
        # heuristic's ELL_PAD_FACTOR pick threshold: measurement decides
        if g.n * max(g.max_degree(), 1) <= 8 * edges:
            out.append("ell")
        out.append("sell")
        if g.n <= 8192:  # n^2 adjacency: 256 MB fp32 at 8k vertices
            out.append("dense")
        if platform == "tpu":
            out.append("blocked")
        return out

    def sell_padded_slots(self) -> int:
        """Host-built SELL geometry (memoized — the lattice prices the
        ``sell`` target per exec group, the probe engines rebuild it)."""
        cached = getattr(self, "_sell_padded_slots", None)
        if cached is None:
            from repro.core.graph import build_sell  # local: cycle-free

            cached = build_sell(self.graph).padded_slots
            object.__setattr__(self, "_sell_padded_slots", cached)
        return cached

    def spmm_work_elements(self, target: str) -> int:
        """Gathered/reduced elements per passive DP column on ``target``
        (the backend-dependent half of a stage's work)."""
        g = self.graph
        edges = max(g.num_directed, 1)
        if target in ("edges", "custom"):
            return edges
        if target == "ell":
            return g.n * max(g.max_degree(), 1)
        if target == "sell":
            return self.sell_padded_slots()
        if target == "dense":
            # n^2 MACs at matmul throughput ~= n^2 / advantage gather-grade
            # element visits (same constant select_backend compares with)
            return max(1, g.n**2 // _dense_work_advantage())
        if target == "blocked":
            return edges
        raise ValueError(f"unknown work target {target!r}")

    def group_cost_us(
        self, leader, backend: str, column_batch: int
    ) -> float:
        """Raw (uncalibrated) predicted us for one exec group's sweep.

        One group = one passive column-batch sweep shared by every member
        stage: the backend's gather over ``C(k, m_p)`` passive columns,
        each member's eMA contraction (``n * n_out * n_splits`` FMAs,
        backend-independent), and a fixed dispatch cost per fused slice.
        """
        from repro.core.colorsets import binom  # local: cycle-free

        p_idx, i = leader
        cplan = self.plan.counting_plans[p_idx]
        sub = cplan.partition.subs[i]
        passive_cols = binom(cplan.k, cplan.partition.subs[sub.passive].size)
        gather = self.spmm_work_elements(backend) * passive_cols
        ema = 0
        for q, j in self.plan.exec_groups[leader]:
            mplan = self.plan.counting_plans[q]
            msub = mplan.partition.subs[j]
            m = msub.size
            m_a = mplan.partition.subs[msub.active].size
            ema += self.graph.n * binom(mplan.k, m) * binom(m, m_a)
        cb = max(1, min(int(column_batch), passive_cols))
        sweeps = math.ceil(passive_cols / cb)
        return (gather + ema) * WORK_ELEMENT_US + sweeps * SWEEP_OVERHEAD_US

    def tree_group_leaders(self) -> "list":
        """Exec-group leaders of *tree* stages — the addresses a mixed
        config can bind (bag programs run through the uniform default)."""
        return [
            leader
            for leader in sorted(self.plan.exec_groups)
            if self.plan.counting_plans[leader[0]].partition is not None
        ]

    def predict_config_us(
        self,
        config,
        *,
        chunk_size: int,
        calibration: Optional[Dict[str, float]] = None,
        mesh_shards: Optional[int] = None,
    ) -> "Tuple[float, float]":
        """``(calibrated_us, raw_us)`` per coloring for one
        :class:`~repro.tune.config.TuningConfig`.

        Calibration multiplies each group's cost by its backend's
        measured/predicted ratio; ``raw_us`` skips that (it is what new
        measurements are ratioed against, keeping calibration a fixed
        point).  Bag-stage plans price their bag ops into the default
        backend's share implicitly via the launch term only — the lattice
        still ranks, it just ranks on the tree groups it can rebind.

        ``default_backend == "mesh"`` configs route through the comm model
        (:meth:`predict_mesh_config_us`; ``mesh_shards`` supplies the ring
        size).
        """
        calibration = calibration or {}
        if config.default_backend == "mesh":
            return self.predict_mesh_config_us(
                config,
                chunk_size=chunk_size,
                n_shards=mesh_shards or 1,
                calibration=calibration,
            )
        bindings = config.bindings()
        cb = config.column_batch or self.pick_local_column_batch()
        raw = calibrated = LAUNCH_OVERHEAD_US / max(1, int(chunk_size))
        for leader in self.tree_group_leaders():
            backend = bindings.get(leader, config.default_backend)
            cost = self.group_cost_us(leader, backend, cb)
            raw += cost
            calibrated += cost * calibration.get(backend, 1.0)
        return calibrated, raw

    def predict_mesh_config_us(
        self,
        config,
        *,
        chunk_size: int,
        n_shards: int,
        calibration: Optional[Dict[str, float]] = None,
    ) -> "Tuple[float, float]":
        """``(calibrated_us, raw_us)`` per coloring for a mesh config.

        Per stage: per-shard compute plus the *visible* (un-hidden) wire
        time under the config's comm mode, plus the per-sweep dispatch and
        (pipelined) per-ring-step overheads — the figures the
        :meth:`comm_schedule` decision rule balances, summed instead of
        compared.
        """
        calibration = calibration or {}
        cb = config.column_batch or self.pick_mesh_column_batch()
        raw = LAUNCH_OVERHEAD_US / max(1, int(chunk_size))
        for leader in self.tree_group_leaders():
            sched = self.comm_schedule(
                leader, n_shards, column_batch=cb,
                forced=getattr(config, "mesh_comm", None),
            )
            per_slice = (
                max(0, n_shards - 1)
                * sched.slice_rows
                * sched.slice_cols
                * self.itemsize
            )
            n_batches = (
                max(1, round(sched.wire_bytes / per_slice)) if per_slice else 1
            )
            visible_comm = (
                sched.comm_us * (1.0 - sched.overlap_efficiency)
                if sched.ring_steps > 1
                else sched.comm_us
            )
            step_overhead = (
                n_batches * sched.ring_steps * RING_STEP_OVERHEAD_US
                if sched.ring_steps > 1
                else 0.0
            )
            raw += (
                sched.compute_us
                + visible_comm
                + n_batches * SWEEP_OVERHEAD_US
                + step_overhead
            )
        return raw * calibration.get("mesh", 1.0), raw

    def candidate_lattice(
        self,
        *,
        platform: Optional[str] = None,
        calibration: Optional[Dict[str, float]] = None,
        memory_budget_bytes: int = DEFAULT_MEMORY_BUDGET_BYTES,
        chunk_size: Optional[int] = None,
        include_mixed: bool = True,
        mesh_shards: Optional[int] = None,
    ) -> "list[RankedCandidate]":
        """Ranked tuning candidates, cheapest-predicted first.

        The cross product of memory budgets x feasible backends x column
        batches x chunk sizes, plus (``include_mixed``) one greedy mixed
        candidate per (budget, column batch) binding each exec group to
        its per-group-cheapest backend.  The budget axis sweeps the given
        budget and its half (floored at 1 MiB) — each candidate records
        the budget it was priced under
        (``TuningConfig.memory_budget_bytes``), so differently-budgeted
        winners never share an engine cache key.  With ``mesh_shards``
        (the tuner ran with a ``mesh=``), mesh candidates join the lattice
        with the comm mode (``blocking`` | ``pipelined``) as an extra
        axis, priced by the comm model.  The tuner measures the top-N of
        this list; everything else is pruned unseen — which is the whole
        point of keeping an analytic model around once measurements
        exist.
        """
        from repro.tune.config import TuningConfig  # local: cycle-free

        if calibration is None:
            calibration = load_backend_calibration()
        backends = self.feasible_backends(platform)
        resident = self.resident_elements()
        picked_cb = self.pick_local_column_batch()
        max_cb = max(1, self.plan.max_passive_columns)
        col_batches = sorted({
            min(4, max_cb), min(picked_cb, max_cb), min(64, max_cb)
        })
        budget = int(memory_budget_bytes)
        budgets = sorted({budget, max(budget // 2, 1 << 20)})
        leaders = self.tree_group_leaders()
        candidates = []
        seen = set()

        def _add(config):
            if config.key_fragment() in seen:
                return
            seen.add(config.key_fragment())
            calibrated, raw = self.predict_config_us(
                config,
                chunk_size=config.chunk_size,
                calibration=calibration,
                mesh_shards=mesh_shards,
            )
            candidates.append(
                RankedCandidate(config=config, predicted_us=calibrated, raw_us=raw)
            )

        for bud in budgets:
            for cb in col_batches:
                # per-BACKEND chunk sets: each backend is probed at the
                # chunk its own byte model picks under this budget (plus
                # the half), never at a chunk derived from another
                # backend's transient — cross-pollinated chunks used to
                # crowd the analytic pick out of the probed top-N
                chunks_by_backend = {}
                for b in backends:
                    if chunk_size:
                        chunks_by_backend[b] = {int(chunk_size)}
                        continue
                    per = self.bytes_per_coloring(
                        self.transient_elements(
                            b,
                            cb,
                            sell_padded_slots=(
                                self.sell_padded_slots() if b == "sell" else None
                            ),
                        ),
                        resident,
                    )
                    picked = self.pick_chunk_size(per, bud)
                    chunks_by_backend[b] = {picked, max(1, picked // 2)}
                for b in backends:
                    for chunk in sorted(chunks_by_backend[b]):
                        _add(TuningConfig(
                            default_backend=b, column_batch=cb, chunk_size=chunk,
                            memory_budget_bytes=bud,
                        ))
                if include_mixed and len(backends) > 1 and leaders:
                    greedy = tuple(
                        (
                            leader,
                            min(
                                backends,
                                key=lambda b: self.group_cost_us(leader, b, cb)
                                * calibration.get(b, 1.0),
                            ),
                        )
                        for leader in leaders
                    )
                    names = {b for _, b in greedy}
                    if len(names) > 1:
                        # default backend serves bag ops + plain spmm: the
                        # cheapest gather-per-column backend among the bound
                        default = min(
                            names, key=lambda b: self.spmm_work_elements(b)
                        )
                        for chunk in sorted(chunks_by_backend[default]):
                            _add(TuningConfig(
                                default_backend=default,
                                group_backends=greedy,
                                column_batch=cb,
                                chunk_size=chunk,
                                memory_budget_bytes=bud,
                            ))
            if mesh_shards:
                # mesh candidates: the comm mode is the swept axis; chunk
                # comes from the resident footprint (the dominant per-shard
                # term the budget bounds)
                mesh_cb = self.pick_mesh_column_batch()
                per = self.bytes_per_coloring(0, resident)
                picked = (
                    int(chunk_size)
                    if chunk_size
                    else self.pick_chunk_size(per, bud)
                )
                for comm in ("blocking", "pipelined"):
                    _add(TuningConfig(
                        default_backend="mesh",
                        column_batch=mesh_cb,
                        chunk_size=picked,
                        memory_budget_bytes=bud,
                        mesh_comm=comm,
                    ))
        candidates.sort(key=lambda c: (c.predicted_us, repr(c.config.key_fragment())))
        # two budgets that land on the same (backend, groups, cb, chunk,
        # comm) build the same engine — measuring both burns a probe slot
        # for zero information, so keep only the best-ranked of each
        unique, seen_runtime = [], set()
        for cand in candidates:
            cfg = cand.config
            runtime = (
                cfg.default_backend, cfg.group_backends, cfg.column_batch,
                cfg.chunk_size, cfg.mesh_comm,
            )
            if runtime in seen_runtime:
                continue
            seen_runtime.add(runtime)
            unique.append(cand)
        return unique
