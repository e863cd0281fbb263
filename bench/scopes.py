"""Device time by program scope, and idle gaps by program span.

The program names its work (``repro.tracing``): device operations carry
``s2v`` scope paths in their HLO metadata (``stage0_2_m3/spmm``, ``colors``,
...), which ``CountingEngine.launch_scopes()`` returns as a map from
operation name to scope path, and ``count_keys_chunk`` holds the host spans
``s2v.dispatch``, ``s2v.fetch`` and ``s2v.scale``.  This module reads a
profiler trace with them:

* ``scope_s``: chip 0's self time in the window summed by scope path (the
  empty path: operations outside every scope);
* top operations labelled ``<op> <shape> @<scope>``;
* idle gaps named by the innermost ``bench.*`` or ``s2v.*`` span.

Run on a TPU host, it breaks one cell down the way ``bench/run.py`` sets it
up (graph from the seed, engine, warm launch), with no correctness check::

    python3 bench/scopes.py --workload <name> --seed <n> [--launches 3]

It makes ``--launches`` launches untimed by the profiler, then as many in a
profiled window, and prints one JSON line: the breakdown, both windows'
colorings per second, the seconds ``launch_scopes()`` took, and the
program's compile counters of the warm launch.
"""

from __future__ import annotations

import argparse
import glob
import json
import shutil
import sys
import time
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

_ROOT = Path(__file__).resolve().parent.parent
for _p in (_ROOT / "src", _ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import trace  # noqa: E402

#: Host annotations kept: the benchmark's and the program's.
SPAN_PREFIXES = ("bench.", "s2v.")
#: Scope path of operations outside every ``s2v`` scope.
UNSCOPED = ""


def load_xplane(path: str) -> trace.Trace:
    """:func:`bench.trace.load_xplane`, keeping the program's ``s2v.*``
    host spans beside the benchmark's ``bench.*`` annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[int, List[trace.Event]] = {}
    spans: List[trace.Event] = []
    for plane in data.planes:
        m = trace._DEVICE_PLANE.fullmatch(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(int(m.group(1)), []).extend(
                        trace.Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events
                    )
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend(
                    trace.Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events
                    if ev.name.startswith(SPAN_PREFIXES)
                )
    for evs in ops.values():
        evs.sort(key=lambda e: (e.start, -e.end))
    spans.sort(key=lambda e: (e.start, -e.end))
    return trace.Trace(ops=ops, annotations=spans)


def labelled_self_s(events: Sequence[trace.Event], scopes: Mapping[str, str]) -> Dict[Tuple[str, str], float]:
    """Self seconds of each operation, keyed by ``(label, scope path)``."""
    return {
        (label, scopes.get(label.split(" ", 1)[0], UNSCOPED)): ns * 1e-9
        for label, ns in trace.self_times(events).items()
    }


@dataclass(frozen=True)
class ScopedReading:
    """A traced window's :class:`bench.trace.Reading`, by program scope."""

    reading: trace.Reading
    scope_s: Dict[str, float]
    top_ops: List[Tuple[str, float]]
    unscoped_ops: List[Tuple[str, float]]

    def component_s(self, component: str) -> float:
        """Seconds of the scope paths holding ``component`` (``spmm``, ``ema``)."""
        return sum(s for path, s in self.scope_s.items() if component in path.split("/"))

    def scoped_share(self) -> float:
        """Share of chip 0's self time that falls under an ``s2v`` scope."""
        total = sum(self.scope_s.values())
        return 1.0 - self.scope_s.get(UNSCOPED, 0.0) / total if total > 0 else 0.0


def read(tr: trace.Trace, devices: Sequence[int], window_annotation: str, scopes: Mapping[str, str],
         top: int = 10) -> Optional[ScopedReading]:
    """:func:`bench.trace.read`, with chip 0's self time by scope path and
    the top operations labelled ``@<scope>``."""
    reading = trace.read(tr, devices, window_annotation, top=top)
    if reading is None:
        return None
    lo, hi = trace.window(tr, window_annotation)
    in_window = [trace.Event(e.name, max(e.start, lo), min(e.end, hi)) for e in tr.ops.get(devices[0], [])
                 if e.end > lo and e.start < hi]
    by_op = labelled_self_s(in_window, scopes)
    scope_s: Dict[str, float] = defaultdict(float)
    for (_, path), s in by_op.items():
        scope_s[path] += s
    ranked = sorted(by_op.items(), key=lambda x: -x[1])
    return ScopedReading(
        reading=reading,
        scope_s=dict(scope_s),
        top_ops=[(f"{label} @{path}" if path else label, s) for (label, path), s in ranked[:top]],
        unscoped_ops=[(label, s) for (label, path), s in ranked if path == UNSCOPED][:top],
    )


def breakdown(scoped: ScopedReading, colorings: int, top: int = 10) -> Dict:
    """The JSON-ready numbers of a scoped reading over ``colorings``."""
    reading = scoped.reading
    first = min(reading.busy_s)
    spmm, ema = scoped.component_s("spmm"), scoped.component_s("ema")
    return {
        "busy_s": reading.busy_s[first],
        "window_s": reading.window_s,
        "spmm_s_per_coloring": spmm / colorings,
        "ema_s_per_coloring": ema / colorings,
        "scoped_share": scoped.scoped_share(),
        "scopes": sorted(scoped.scope_s.items(), key=lambda x: -x[1])[:top],
        "device_ops": scoped.top_ops,
        "unscoped_ops": scoped.unscoped_ops,
        "idle_gaps": reading.gaps,
    }


def main(argv=None, root: Optional[Path] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--launches", type=int, default=3)
    args = ap.parse_args(argv)

    import jax

    from bench import run as bench_run
    from bench import spec
    from repro import tracing
    from repro.core import CountingEngine
    from repro.core.graph import Graph
    from repro.core.templates import Template

    cell = spec.load_cell(args.workload, root)
    try:
        devices = bench_run.require_chips(cell.chips)
    except bench_run.NoAccelerator as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    bench_run.enable_cache()
    edges = bench_run.make_graph(cell.config, args.seed)
    mesh = jax.make_mesh((cell.chips,), ("dev",), devices=devices) if cell.chips > 1 else None
    chunk_policy = cell.traffic["chunk"]
    engine = CountingEngine(
        Graph(n=edges.n, src=edges.src, dst=edges.dst),
        [Template(t["name"], tuple(tuple(e) for e in t["edges"])) for t in cell.traffic["templates"]],
        mesh=mesh,
        dtype_policy=cell.config["precision"],
        chunk_size=None if chunk_policy == "engine" else int(chunk_policy),
    )
    stream = bench_run.KeyStream(args.seed, engine.chunk_size)
    before = tracing.compile_counters()
    t0 = time.perf_counter()
    engine.count_keys_chunk(stream.next())
    first_launch_s = time.perf_counter() - t0
    after = tracing.compile_counters()
    warm = {k: {f: after[k][f] - before[k][f] for f in ("count", "seconds")} for k in after}

    t0 = time.perf_counter()
    for _ in range(args.launches):
        engine.count_keys_chunk(stream.next())
    untraced_s = time.perf_counter() - t0

    log_dir = bench_run._start_trace()
    annotate = jax.profiler.TraceAnnotation
    t0 = time.perf_counter()
    with annotate(bench_run.WINDOW):
        for _ in range(args.launches):
            with annotate("bench.keys"):
                keys = stream.next()
            with annotate("bench.count_keys_chunk"):
                engine.count_keys_chunk(keys)
    traced_s = time.perf_counter() - t0
    jax.profiler.stop_trace()

    t0 = time.perf_counter()
    scopes = engine.launch_scopes()
    launch_scopes_s = time.perf_counter() - t0
    try:
        paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
        scoped = read(load_xplane(paths[0]), list(range(cell.chips)), bench_run.WINDOW, scopes) if paths else None
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    colorings = args.launches * engine.chunk_size
    out = {
        "workload": cell.name,
        "backend": engine.backend,
        "chunk": engine.chunk_size,
        "launches": args.launches,
        "first_launch_s": first_launch_s,
        "warm_launch_compiles": warm,
        "colorings_per_s_untraced": colorings / untraced_s,
        "colorings_per_s_traced": colorings / traced_s,
        "launch_scopes_s": launch_scopes_s,
        "scoped_ops": len(scopes),
        "breakdown": breakdown(scoped, colorings) if scoped is not None else None,
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
