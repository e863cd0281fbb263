"""One run of one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The run finds the cell's configuration, traffic mix, limits and metric
readers by name (``bench/spec.py``), then:

1. set-up: starts JAX on the cell's chips, makes the configuration's graph on
   the device from ``--seed``, builds the program's ``CountingEngine`` on it
   (the engine picks backend and chunk; on several chips it gets a mesh),
   and makes one warm launch of the cell's shape;
2. window: launches ``CountingEngine.count_keys_chunk`` back to back, each on
   a full chunk of fresh PRNG keys drawn from ``--seed``, until the first
   launch that ends after ``--seconds``; with ``--trace 1`` the window is
   profiled (at least two launches) and per-layer metrics are read from it;
3. check: after the window, frees the engine and recomputes a sample of the
   window's colourings, drawn from ``--seed``, with the configuration's plain
   reference; every estimate of the window must be finite and non-negative.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared beside its limit).
The run exits 2 and prints no result when the default device is not a TPU or
there are fewer chips than the cell needs.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

_ROOT = Path(__file__).resolve().parent.parent
for _p in (_ROOT / "src", _ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402

from bench import graphgen, spec, trace as tracing  # noqa: E402
from bench.metrics.dp_roofline_share import launch_bytes  # noqa: E402
from bench.treelets import plan_tree  # noqa: E402

#: Host annotation spanning the measured window.
WINDOW = "bench.window"
GENERATORS = {"rmat": graphgen.rmat_edges}
#: Configuration keys each generator takes.
GENERATOR_KEYS = {"rmat": ("scale", "edgefactor", "a", "b", "c", "permute")}


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


@dataclass
class Run:
    """What the metric readers read (``bench/metrics/<name>.py``)."""

    chips: int
    peaks: Dict
    spans: Dict[str, float] = field(default_factory=dict)
    launches: int = 0
    colorings: int = 0
    window_s: Optional[float] = None
    launch_bytes: int = 0
    trace: Optional[tracing.Reading] = None


def require_chips(chips: int):
    """The first ``chips`` TPU devices; :class:`NoAccelerator` otherwise."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"default device is {devices[0].platform!r}, not a TPU")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices[:chips]


def make_graph(config: Dict, seed: int) -> graphgen.EdgeList:
    name = config["generator"]
    return GENERATORS[name](seed, **{k: config[k] for k in GENERATOR_KEYS[name]})


def edge_capacity(config: Dict) -> int:
    """Most directed edges the configuration's generator can give."""
    return 2 * (int(config["edgefactor"]) << int(config["scale"]))


class KeyStream:
    """Fresh raw PRNG keys, a chunk at a time, from the run's seed."""

    def __init__(self, seed: int, chunk: int):
        self.chunk = chunk
        self._rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))

    def next(self) -> np.ndarray:
        return self._rng.integers(0, 2**32, size=(self.chunk, 2), dtype=np.uint32)


def enable_cache() -> None:
    """JAX's persistent compilation cache where ``repro.compile_cache`` puts
    it, for every program however fast it compiles."""
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def _peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def _start_trace():
    import jax

    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=options)
    return log_dir


def _read_trace(log_dir: str, chips: int) -> Optional[tracing.Reading]:
    try:
        paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
        if not paths:
            return None
        return tracing.read(tracing.load_xplane(paths[0]), list(range(chips)), WINDOW)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def check(cell: spec.Cell, edges: graphgen.EdgeList, templates, results, seed: int) -> Dict[str, Dict]:
    """Numbers compared, each beside its limit (run after the engine is freed)."""
    import jax
    import jax.numpy as jnp

    estimates = np.concatenate([est for _, est in results])
    invalid = int(np.sum(~np.isfinite(estimates) | (estimates < 0)))
    ref_mod = spec.reference_module(cell)
    k = templates[0][1]
    refs = [
        ref_mod.TreeReference(plan_tree(t_edges, t_k), edges.n, edges.src, edges.dst, edge_capacity(cell.config))
        for t_edges, t_k in templates
    ]
    keys = np.concatenate([keys for keys, _ in results])
    want = min(int(cell.traffic["check_colorings"]), keys.shape[0])
    pick = np.random.default_rng(np.random.SeedSequence([int(seed), 2])).choice(keys.shape[0], want, replace=False)
    worst = 0.0
    for i in sorted(pick):
        colours = jax.random.randint(jnp.asarray(keys[i]), (edges.n,), 0, k)
        for t, ref in enumerate(refs):
            want_est = ref.estimate(colours)
            got = float(estimates[i, t])
            err = abs(got - want_est) / max(abs(want_est), 1e-300) if math.isfinite(got) else math.inf
            worst = max(worst, err)
    limit = cell.limits["max_rel_err"]["limit"]
    return {
        "max_rel_err": {"value": worst, "limit": limit},
        "invalid_estimates": {"value": invalid, "limit": 0},
    }


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool, devices, peaks: Dict) -> Dict:
    import jax

    from repro.core import CountingEngine
    from repro.core.graph import Graph
    from repro.core.templates import Template

    enable_cache()
    run = Run(chips=cell.chips, peaks=peaks)
    templates = [(tuple(tuple(e) for e in t["edges"]), int(t["k"])) for t in cell.traffic["templates"]]

    t0 = time.perf_counter()
    edges = make_graph(cell.config, seed)
    run.spans["graph"] = time.perf_counter() - t0

    mesh = jax.make_mesh((cell.chips,), ("dev",), devices=devices) if cell.chips > 1 else None
    chunk_policy = cell.traffic["chunk"]
    t0 = time.perf_counter()
    engine = CountingEngine(
        Graph(n=edges.n, src=edges.src, dst=edges.dst),
        [Template(t["name"], edges_) for t, (edges_, _) in zip(cell.traffic["templates"], templates)],
        mesh=mesh,
        dtype_policy=cell.config["precision"],
        chunk_size=None if chunk_policy == "engine" else int(chunk_policy),
    )
    run.spans["engine_build"] = time.perf_counter() - t0
    chunk = engine.chunk_size
    run.launch_bytes = launch_bytes(templates, edges.n, edges.num_directed, chunk)
    stream = KeyStream(seed, chunk)

    t0 = time.perf_counter()
    engine.count_keys_chunk(stream.next())
    run.spans["first_launch"] = time.perf_counter() - t0
    print(f"[bench] {cell.name} n={edges.n} directed_edges={edges.num_directed} backend={engine.backend} "
          f"chunk={chunk} graph_s={run.spans['graph']:.3f} engine_build_s={run.spans['engine_build']:.3f} "
          f"first_launch_s={run.spans['first_launch']:.3f}", file=sys.stderr, flush=True)

    log_dir = _start_trace() if traced else None
    results: List = []
    annotate = jax.profiler.TraceAnnotation
    t_window = time.perf_counter()
    run.spans["setup"] = t_window - T_START
    with annotate(WINDOW):
        while True:
            with annotate("bench.keys"):
                keys = stream.next()
            with annotate("bench.count_keys_chunk"):
                est = engine.count_keys_chunk(keys)
            results.append((keys, est))
            elapsed = time.perf_counter() - t_window
            if elapsed >= seconds and (not traced or len(results) >= 2):
                break
    run.window_s = elapsed
    run.launches = len(results)
    run.colorings = run.launches * chunk
    if traced:
        jax.profiler.stop_trace()
        run.trace = _read_trace(log_dir, cell.chips)
    memory_peak = _peak_bytes(devices)

    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = spec.metric_reader(cell, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    del engine
    gc.collect()
    t0 = time.perf_counter()
    checks = check(cell, edges, templates, results, seed)
    print(f"[bench] setup_s={run.spans['setup']:.3f} window_s={run.window_s:.3f} launches={run.launches} "
          f"check_s={time.perf_counter() - t0:.3f}", file=sys.stderr, flush=True)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    failed = checks["invalid_estimates"]["value"] + int(checks["max_rel_err"]["value"] > checks["max_rel_err"]["limit"])

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": jax.device_count(),
        "memory_peak_bytes": memory_peak,
    }
    out = {"correct": bool(correct), "attempted": run.colorings, "failed": int(failed), "metrics": metrics,
           "device": device}
    if traced and run.trace is not None:
        device["busy_s"] = sum(run.trace.busy_s.values()) / len(run.trace.busy_s)
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {
            "device_ops": [[name, s] for name, s in run.trace.top_ops],
            "idle_gaps": [[name, s] for name, s in run.trace.gaps],
        }
    out["checks"] = checks
    return out


def main(argv=None, root: Optional[Path] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload, root)
    try:
        devices = require_chips(cell.chips)
    except NoAccelerator as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    peaks = spec.device_peaks(cell.bench_dir, devices[0].device_kind)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices, peaks)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
