"""Backend auto-selection: graph statistics -> execution strategy name.

The dispatch half of the exec layer: given a graph (and the platform), pick
which local SpMM strategy the engine should bind its plan to.  Decisions
are logged on the ``repro.engine`` logger (the engine façade's channel, so
existing log-capture consumers keep working).
"""

from __future__ import annotations

import logging
import os
from typing import Optional, Tuple

import jax

__all__ = [
    "select_backend",
    "heuristic_backend",
    "resolve_backend_config",
    "consult_tuning",
    "tune_mode",
    "mesh_comm_mode",
    "ENGINE_BACKENDS",
    "BACKEND_ENV_VAR",
    "TUNE_MODE_ENV_VAR",
    "TUNE_MODES",
    "MESH_COMM_ENV_VAR",
    "MESH_COMM_MODES",
    "DENSE_MAX_VERTICES",
    "ELL_PAD_FACTOR",
    "BLOCKED_MAX_PADDING",
    "BLOCKED_MAX_ROWS",
    "blocked_operand_fit",
    "SELL_MIN_SCATTER_WORK",
    "DENSE_WORK_ADVANTAGE",
]

logger = logging.getLogger("repro.engine")

#: Graphs at or below this vertex count use the dense-adjacency backend.
DENSE_MAX_VERTICES = 256

#: ELL is chosen only when padding waste is bounded: ``n * max_deg`` must not
#: exceed this factor times the true directed edge count.
ELL_PAD_FACTOR = 1.5

#: On TPU the fused Pallas kernel is picked only when its blocked-ELL
#: operand is mostly edges: padded operand bytes at most this factor of the
#: unpadded edge bytes.  A sparse RMAT's off-diagonal (dst, src) block pairs
#: hold a few edges each, and every one of them pads to a full row.
BLOCKED_MAX_PADDING = 2.0

#: Most operand rows the kernel may take: its per-row block ids and run
#: flags (four int32 per row, 512 KiB here) ride in the 1 MiB SMEM as
#: scalar prefetch, beside the split tables.  ``tests/test_tpu_compile.py``
#: compiles both kernels at this bound with u12's widest stage; twice the
#: rows overflows SMEM.
BLOCKED_MAX_ROWS = 32768

#: Environment variable overriding the auto-selected local backend.
BACKEND_ENV_VAR = "REPRO_ENGINE_BACKEND"

#: Above this ``n * |E_directed|`` product, skewed graphs route to the
#: scatter-free SELL backend: XLA:CPU's scatter lowering falls off a cliff
#: in this regime (observed ~200x on 8k vertices / 130k directed edges)
#: while degree-bucketed gathers stay on the |E|-proportional cost curve.
SELL_MIN_SCATTER_WORK = 5 * 10**8

#: Dense adjacency wins only when the gather path's per-column element work
#: (``|E|``) is within this factor of the dense matmul's per-column ``n^2``
#: MACs — the throughput advantage of regular matmuls over irregular
#: gathers.  (The column count cancels: both paths scale linearly in it.)
DENSE_WORK_ADVANTAGE = 16

#: How engine builds use the tuning cache: ``off`` never consults it,
#: ``cached`` (default) applies persisted winners, ``full`` additionally
#: lets the serving layer schedule background tunes for un-tuned keys.
TUNE_MODE_ENV_VAR = "REPRO_TUNE"

TUNE_MODES = ("off", "cached", "full")

#: Environment override forcing the mesh backend's collective scheme:
#: ``blocking`` (one all-gather per column batch) or ``pipelined`` (the
#: double-buffered ring).  Unset = the cost model's per-stage decision.
MESH_COMM_ENV_VAR = "REPRO_MESH_COMM"

MESH_COMM_MODES = ("blocking", "pipelined")

ENGINE_BACKENDS = (
    "edges", "ell", "sell", "dense", "blocked", "mixed", "mesh", "custom"
)

#: Local backend names an env override / explicit ``backend=`` may name
#: without extra context (``mixed`` additionally needs a TuningConfig).
_LOCAL_BACKENDS = ("edges", "ell", "sell", "dense", "blocked")


def select_backend(graph, platform: Optional[str] = None, explain: bool = False):
    """Pick the local SpMM backend from graph statistics.

    * env override — ``REPRO_ENGINE_BACKEND=<name>`` forces any local
      backend (a bad auto-pick used to be silent and undiagnosable).
    * ``dense``   — tiny graphs, or work-dense graphs where the gather
      path's per-column element work ``|E|`` reaches
      ``n^2 / DENSE_WORK_ADVANTAGE`` (avg degree ``>= n / 16``): one
      (n, n) matmul beats gather/scatter.  The DP column count cancels
      from the comparison — both paths scale linearly in it.
    * ``blocked`` — on TPU, graphs whose blocked-ELL operand fits: the
      fused Pallas SpMM+eMA kernel (see :func:`blocked_operand_fit`).
    * ``ell``     — flat degree distributions where row padding is cheap.
    * ``sell``    — on CPU, rmat8k-class graphs (``n * |E|`` beyond
      ``SELL_MIN_SCATTER_WORK``): scatter-free degree-bucketed gathers;
      XLA:CPU's scatter collapses in this regime.  Not on other platforms:
      the cliff is XLA:CPU's, and SELL's per-group gathers grow the program
      with ``n / group_size``.
    * ``edges``   — everything else (small skewed / power-law graphs: a hub
      row would blow the ELL padding up to ``n * max_deg``).

    The ``mesh`` backend is never auto-selected from graph statistics — it
    is chosen by passing ``mesh=`` to ``CountingEngine``.

    The decision and its reason are logged on the ``repro.engine`` logger
    (DEBUG) so callers capture it with standard logging config;
    ``explain=True`` additionally returns ``(name, reason)`` for
    structured consumers (``CountingEngine.describe()``).
    """
    name, reason = _select_backend_reason(graph, platform)
    logger.debug(
        "select_backend: %s for n=%d edges=%d (%s)",
        name,
        graph.n,
        graph.num_directed,
        reason,
    )
    return (name, reason) if explain else name


def _env_backend() -> Optional[str]:
    """The validated ``REPRO_ENGINE_BACKEND`` override, or ``None``."""
    env = os.environ.get(BACKEND_ENV_VAR, "").strip()
    if not env:
        return None
    if env not in _LOCAL_BACKENDS:
        raise ValueError(
            f"{BACKEND_ENV_VAR}={env!r} is not a local backend "
            "(edges | ell | sell | dense | blocked)"
        )
    return env


def heuristic_backend(graph, platform: Optional[str] = None) -> Tuple[str, str]:
    """The pure analytic pick — ``(name, reason)`` from graph statistics
    alone, ignoring both the env override and the tuning cache.  This is
    the bottom of the resolution ladder (and what the tuner benches its
    winners against)."""
    return _heuristic_reason(graph, platform)


def tune_mode() -> str:
    """The ``REPRO_TUNE`` mode (``off`` | ``cached`` | ``full``).

    An unrecognized value warns once and behaves as ``cached`` — engine
    builds and service stats must never crash on a typo'd env var."""
    raw = os.environ.get(TUNE_MODE_ENV_VAR, "").strip().lower()
    if not raw:
        return "cached"
    if raw in TUNE_MODES:
        return raw
    if raw not in _BAD_TUNE_MODES_WARNED:
        _BAD_TUNE_MODES_WARNED.add(raw)
        logger.warning(
            "%s=%r is not one of %s — defaulting to 'cached'",
            TUNE_MODE_ENV_VAR, raw, "|".join(TUNE_MODES),
        )
    return "cached"


_BAD_TUNE_MODES_WARNED: set = set()


def mesh_comm_mode() -> Optional[str]:
    """The validated ``REPRO_MESH_COMM`` override, or ``None`` (let the
    cost model's per-stage ``comm_schedule`` decide).

    An unrecognized value warns once and behaves as unset — like
    :func:`tune_mode`, engine builds must never crash on a typo'd env
    var."""
    raw = os.environ.get(MESH_COMM_ENV_VAR, "").strip().lower()
    if not raw:
        return None
    if raw in MESH_COMM_MODES:
        return raw
    if raw not in _BAD_MESH_COMM_WARNED:
        _BAD_MESH_COMM_WARNED.add(raw)
        logger.warning(
            "%s=%r is not one of %s — ignoring the override",
            MESH_COMM_ENV_VAR, raw, "|".join(MESH_COMM_MODES),
        )
    return None


_BAD_MESH_COMM_WARNED: set = set()


def consult_tuning(graph, canons, *, signature=None, path=None):
    """Tuned config for ``(graph, canons)`` on this device, or ``None``.

    Honors ``REPRO_TUNE=off``; any cache trouble (missing, corrupt, wrong
    version, unreadable) degrades to ``None`` — the caller then falls
    through to the heuristic."""
    if canons is None or tune_mode() == "off":
        return None
    try:
        # local import: repro.tune.cache is downstream of the exec layer
        from repro.tune.cache import consult

        sig = signature if signature is not None else graph.signature()
        return consult(sig, canons, path=path)
    except Exception as exc:  # pragma: no cover - defensive
        logger.debug("tuning consult failed (%s) — using heuristic", exc)
        return None


def resolve_backend_config(
    graph,
    *,
    backend: str = "auto",
    canons=None,
    tuning=None,
    platform: Optional[str] = None,
    signature=None,
):
    """The full backend resolution ladder: ``(name, source, reason, config)``.

    Precedence (strongest first):

    1. **explicit** — a concrete ``backend=`` argument (engine callers and
       the degradation ladder's rung overrides must always win).
       ``backend="mixed"`` requires ``tuning`` (the per-group bindings).
    2. **env** — ``REPRO_ENGINE_BACKEND`` beats tuned configs too: the
       operator's escape hatch must not be overridable by a cache file.
    3. **tuned** — a :class:`~repro.tune.config.TuningConfig` passed as
       ``tuning`` or found in the tuning cache for ``(graph, canons)``.
    4. **heuristic** — the analytic pick from graph statistics.

    ``config`` is the :class:`TuningConfig` to bind (``None`` for
    env/heuristic/plain-explicit resolutions).
    """
    if backend != "auto":
        if backend == "mixed" and tuning is None:
            raise ValueError(
                "backend='mixed' needs a TuningConfig (tuning=...) for its "
                "per-group bindings"
            )
        cfg = tuning if backend == "mixed" else None
        return backend, "explicit", "backend= given by caller", cfg
    env = _env_backend()
    if env is not None:
        return env, "env", f"{BACKEND_ENV_VAR} env override", None
    cfg = tuning
    if cfg is None:
        cfg = consult_tuning(graph, canons, signature=signature)
    if cfg is not None:
        reason = (
            f"tuned config (default={cfg.default_backend}, "
            f"{len(cfg.group_backends)} group bindings, "
            f"column_batch={cfg.column_batch}, chunk_size={cfg.chunk_size})"
        )
        return cfg.backend_name, "tuned", reason, cfg
    name, reason = heuristic_backend(graph, platform)
    return name, "heuristic", reason, None


def _select_backend_reason(graph, platform: Optional[str]) -> Tuple[str, str]:
    env = _env_backend()
    if env is not None:
        return env, f"{BACKEND_ENV_VAR} env override"
    return _heuristic_reason(graph, platform)


def _heuristic_reason(graph, platform: Optional[str]) -> Tuple[str, str]:
    platform = platform or jax.default_backend()
    if graph.n <= DENSE_MAX_VERTICES:
        return "dense", f"n={graph.n} <= {DENSE_MAX_VERTICES} (tiny graph)"
    if platform == "tpu":
        fits, why = blocked_operand_fit(graph)
        if fits:
            return "blocked", f"tpu and {why}"
    edges = max(graph.num_directed, 1)
    if DENSE_WORK_ADVANTAGE * edges >= graph.n**2:
        return "dense", (
            f"{DENSE_WORK_ADVANTAGE}*|E|={DENSE_WORK_ADVANTAGE * edges} >= "
            f"n^2={graph.n**2} (work-dense graph)"
        )
    max_deg = graph.max_degree()
    if graph.n * max_deg <= ELL_PAD_FACTOR * edges:
        return "ell", (
            f"n*max_deg={graph.n * max_deg} <= {ELL_PAD_FACTOR}*|E| "
            "(flat degrees, padding bounded)"
        )
    if platform == "cpu" and graph.n * edges >= SELL_MIN_SCATTER_WORK:
        return "sell", (
            f"n*|E|={graph.n * edges} >= {SELL_MIN_SCATTER_WORK} "
            "(XLA:CPU scatter-cliff regime)"
        )
    return "edges", "skewed degrees below the scatter-cliff regime"


def blocked_operand_fit(graph) -> Tuple[bool, str]:
    """Whether the fused kernel's blocked-ELL operand fits — ``(ok, why)``.

    Decided from the operand's own geometry (computed, never built): its
    rows must fit the kernel's SMEM row tables, its padded bytes must stay
    within :data:`BLOCKED_MAX_PADDING` of the edge bytes, and it must fit
    the device's chunk budget.  The row bound is checked first from
    ``|E| / capacity`` (a lower bound on the rows), so a graph far too big
    never pays for the geometry's sort.
    """
    from repro.core.graph import BLOCKED_ROW_CAPACITY, blocked_ell_geometry
    from repro.plan.cost import default_memory_budget_bytes

    min_rows = -(-graph.num_directed // BLOCKED_ROW_CAPACITY)
    if min_rows > BLOCKED_MAX_ROWS:
        return False, f"needs >= {min_rows} operand rows > {BLOCKED_MAX_ROWS}"
    geo = blocked_ell_geometry(graph)
    if geo.n_rows + geo.n_blocks > BLOCKED_MAX_ROWS:
        return False, f"{geo.n_rows} operand rows > {BLOCKED_MAX_ROWS}"
    if geo.padding_factor > BLOCKED_MAX_PADDING:
        return False, (
            f"blocked operand {geo.operand_bytes} B is "
            f"{geo.padding_factor:.2f}x its edge bytes > {BLOCKED_MAX_PADDING}x"
        )
    budget = default_memory_budget_bytes()
    if geo.operand_bytes > budget:
        return False, f"blocked operand {geo.operand_bytes} B > budget {budget} B"
    return True, (
        f"blocked operand {geo.n_rows} rows, {geo.padding_factor:.2f}x "
        "its edge bytes"
    )
