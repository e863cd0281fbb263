"""Names on the chip's time: device scopes, host spans, a compile counter and gauges.

This module is the only place that knows the names.

* :func:`scope` — a ``jax.named_scope`` under the root ``s2v``.  Scopes are
  HLO metadata (each instruction's ``op_name``), so they cost nothing at run
  time; :func:`op_scopes` reads them back out of a compiled program's text,
  which maps a profiler's device operations to the DP stage and layer that
  issued them.
* :func:`span` — a ``jax.profiler.TraceAnnotation`` named ``s2v.<name>``
  on the host's profiler clock; inert when no profiler session is running.
* :func:`compile_counters` — count and seconds of JAX's compile events, from
  process-wide ``jax.monitoring`` listeners registered once, at import.
  Each event is also filed under every :func:`span` open on the thread that
  compiled, so ``compile_counters("count_keys_chunk")`` is the compile work
  launches paid.
* :func:`gauge` / :func:`gauges` — process-wide numbers a backend sets
  when it binds, for readers that see no engine object.  The mesh backend
  sets ``mesh_collective_bytes`` (bytes one chip receives over the
  interconnect in one launch), ``mesh_chunk`` (that launch's colourings),
  ``mesh_edge_slots`` and ``mesh_edges`` (padded and real directed edges
  over all shards); the most recently bound mesh engine's values stand.

Scopes of a launch (``CountingEngine.launch_scopes``)::

    s2v/colors                      colouring draw and one-hot leaf
    s2v/stage<p>_<i>_m<size>/spmm   passive column slice + neighbour reduction
    s2v/stage<p>_<i>_m<size>/ema    colour-set combine + cast to store dtype
    s2v/stage<p>_<i>_m<size>/spmm_ema   fused Pallas SpMM+eMA kernel
    s2v/root                        root reductions
    .../spmm/gather, .../spmm/ring  mesh all-gather and ring steps

Spans of a launch (``CountingEngine.count_keys_chunk``)::

    s2v.count_keys_chunk  the whole call, holding
      s2v.dispatch        key upload, padding and the program call
      s2v.fetch           wait for the device and copy the result to the host
      s2v.scale           float64 normalisation and the fault seam's result hook

Span of a mesh engine's construction: ``s2v.shard_graph``, the row
partition of the graph and the placement of its edge operands on the chips.
"""

from __future__ import annotations

import contextlib
import re
import threading
from collections import Counter, defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

import jax

__all__ = [
    "ROOT",
    "COMPILE_EVENTS",
    "scope",
    "stage",
    "span",
    "compile_counters",
    "gauge",
    "gauges",
    "op_scopes",
]

#: Root of every scope path and prefix of every span name.
ROOT = "s2v"
#: Scope names below the root (besides :func:`stage` names).
SCOPES = ("colors", "root", "spmm", "ema", "spmm_ema", "gather", "ring")
_STAGE = re.compile(r"stage\d+_\d+_m\d+")

#: ``jax.monitoring`` duration events the compile counter keeps, by short
#: name.  In JAX 0.9 ``backend_compile`` wraps ``compile_or_get_cached``:
#: a persistent-cache load is inside it, and ``cache_retrieval`` is a part
#: of that time, not an addition to it.
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}

_local = threading.local()
_lock = threading.Lock()
# (span name or None for every event, short event name) -> [count, seconds]
_compiles: Dict[Tuple[Optional[str], str], List[float]] = defaultdict(lambda: [0, 0.0])
_gauges: Dict[str, float] = {}


def _open(kind: str) -> List[str]:
    stack = getattr(_local, kind, None)
    if stack is None:
        stack = []
        setattr(_local, kind, stack)
    return stack


@contextlib.contextmanager
def scope(name: str) -> Iterator[None]:
    """``jax.named_scope(name)`` below the root: the outermost scope of a
    trace opens ``s2v/<name>``, nested ones ``<name>``."""
    stack = _open("scopes")
    path = name if stack else f"{ROOT}/{name}"
    stack.append(name)
    try:
        with jax.named_scope(path):
            yield
    finally:
        stack.pop()


def stage(plan_idx: int, sub_idx: int, size: int) -> str:
    """Scope name of the DP stage at ``(plan_idx, sub_idx)`` of the
    ``TemplatePlan`` (an exec group's leader), of template size ``size``."""
    return f"stage{plan_idx}_{sub_idx}_m{size}"


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Host span ``s2v.<name>`` on the profiler's clock."""
    stack = _open("spans")
    stack.append(name)
    try:
        with jax.profiler.TraceAnnotation(f"{ROOT}.{name}"):
            yield
    finally:
        stack.pop()


def _on_start(event: str, _value: float, **_) -> None:
    # JAX marks where a timed compile phase starts with a scalar event of
    # the same name.  A phase that starts while another is open (a jitted
    # function traced inside another's trace or lowering) is nested: its
    # time is already in the outer phase's
    if event in COMPILE_EVENTS:
        _open("depth").append(event)


def _on_duration(event: str, duration: float, **_) -> None:
    short = COMPILE_EVENTS.get(event)
    if short is None:
        return
    depth = _open("depth")
    if event in depth:
        depth.remove(event)
        if depth:
            return
    with _lock:
        for where in (None, *set(_open("spans"))):
            entry = _compiles[(where, short)]
            entry[0] += 1
            entry[1] += float(duration)


jax.monitoring.register_scalar_listener(_on_start)
jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_counters(span_name: Optional[str] = None) -> Dict[str, Dict[str, float]]:
    """Snapshot ``{event: {"count", "seconds"}}`` of the compile events
    since import, for each short name of :data:`COMPILE_EVENTS`; with
    ``span_name``, only those recorded while that :func:`span` was open.
    A phase nested in another open one is not counted again;
    ``cache_retrieval`` is a part of ``backend_compile``."""
    with _lock:
        got = {short: _compiles.get((span_name, short), (0, 0.0)) for short in COMPILE_EVENTS.values()}
    return {short: {"count": int(n), "seconds": float(sec)} for short, (n, sec) in got.items()}


def gauge(name: str, value: float) -> None:
    """Set the process-wide gauge ``name`` (host-side; no device work)."""
    with _lock:
        _gauges[name] = value


def gauges() -> Dict[str, float]:
    """Snapshot of every :func:`gauge` set since import."""
    with _lock:
        return dict(_gauges)


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLS = re.compile(r"\bfusion\(.*?\bcalls=%?([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?(\S+)\s.*\{\s*$")


def _scope_path(op_name: str) -> Optional[str]:
    marker = f"{ROOT}/"
    at = op_name.find(marker)
    if at < 0 or (at > 0 and op_name[at - 1] != "/"):
        return None
    stack = op_name[at + len(marker):].split("/")[:-1]
    parts = [p for p in stack if p in SCOPES or _STAGE.fullmatch(p)]
    return "/".join(parts) or None


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> its scope path below ``s2v/`` (for example
    ``stage0_2_m3/spmm``), read from each instruction's ``op_name``
    metadata.  Instructions outside every ``s2v`` scope are left out.

    An ``op_name`` is the name stack, then the primitive's name: the last
    component is dropped, and of the rest only this module's scope names
    are kept (JAX's own ``while/body``, ``jit(...)`` and ``vmap(...)`` go).
    A fusion that XLA built without metadata of its own (on a TPU, the
    pieces it splits a wide gather into) takes the scope its fused
    instructions carry most often.
    """
    out: Dict[str, str] = {}
    inner: Dict[str, List[str]] = defaultdict(list)  # computation -> paths
    unnamed: Dict[str, str] = {}  # fusion without metadata -> computation
    computation = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            header = _COMPUTATION.match(line)
            if header is not None:
                computation = header.group(1)
            continue
        op_name = _OP_NAME.search(line)
        if op_name is None:
            calls = _CALLS.search(line)
            if calls is not None:
                unnamed[m.group(1)] = calls.group(1)
            continue
        path = _scope_path(op_name.group(1))
        if path is not None:
            out[m.group(1)] = path
            inner[computation].append(path)
    for name, called in unnamed.items():
        paths = inner.get(called)
        if paths:
            out[name] = Counter(paths).most_common(1)[0][0]
    return out
