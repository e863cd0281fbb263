"""Readings that set a cell's ``max_rel_err`` limit; run by hand, never by a benchmark run.

    python3 bench/calibrate.py --workload <name> --seeds <s1,s2,...> [--control-seeds <s,...>]

For each seed, in one process: makes the cell's graph, builds the program's
engine as a run does, launches one chunk of the run's first keys, and
compares every colouring of it with the plain reference.  For each control
seed the same launch also goes through the program's ``bf16`` storage path
(the precision below the configuration's float32), whose errors give the
upper reading.  One JSON line per seed on standard output.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
for _p in (_ROOT / "src", _ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402

from bench import run as bench_run  # noqa: E402
from bench import spec  # noqa: E402
from bench.treelets import plan_tree  # noqa: E402


def _launch(cell, edges, templates, policy, chunk, seed, devices):
    """One launch of the run's first keys; returns the keys and the estimates."""
    import jax

    from repro.core import CountingEngine
    from repro.core.graph import Graph
    from repro.core.templates import Template

    engine = CountingEngine(
        Graph(n=edges.n, src=edges.src, dst=edges.dst),
        [Template(t["name"], e) for t, (e, _) in zip(cell.traffic["templates"], templates)],
        mesh=jax.make_mesh((cell.chips,), ("dev",), devices=devices) if cell.chips > 1 else None,
        dtype_policy=policy,
        chunk_size=chunk,
    )
    keys = bench_run.KeyStream(seed, engine.chunk_size).next()
    out = engine.count_keys_chunk(keys)
    del engine
    gc.collect()
    return keys, out


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devices = bench_run.require_chips(cell.chips)
    bench_run.enable_cache()
    templates = [(tuple(tuple(e) for e in t["edges"]), int(t["k"])) for t in cell.traffic["templates"]]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    ref_mod = spec.reference_module(cell)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        edges = bench_run.make_graph(cell.config, seed)
        chunk = None if cell.traffic["chunk"] == "engine" else int(cell.traffic["chunk"])
        keys, got = _launch(cell, edges, templates, cell.config["precision"], chunk, seed, devices)
        low = _launch(cell, edges, templates, "bf16", len(keys), seed, devices)[1] if seed in controls else None
        t1 = time.perf_counter()
        rows = {"seed": seed, "chunk": len(keys), "program": [], "control": []}
        for t, (t_edges, k) in enumerate(templates):
            ref = ref_mod.TreeReference(plan_tree(t_edges, k), edges.n, edges.src, edges.dst,
                                        bench_run.edge_capacity(cell.config))
            for i, key in enumerate(keys):
                want = ref.estimate(jax.random.randint(jnp.asarray(key), (edges.n,), 0, k))
                rows["program"].append(abs(got[i, t] - want) / abs(want))
                if low is not None:
                    rows["control"].append(abs(low[i, t] - want) / abs(want))
            del ref
            gc.collect()
        rows["program_s"] = t1 - t0
        rows["reference_s"] = time.perf_counter() - t1
        print(json.dumps(rows), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
