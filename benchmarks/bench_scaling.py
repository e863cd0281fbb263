"""Fig 13 analog: strong scaling of distributed SUBGRAPH2VEC.

On virtual CPU devices, wall-time across device counts measures dispatch
overhead, not hardware scaling; the strong-scaling evidence there is the
**per-shard resource scaling** extracted from the compiled artifact at mesh
sizes 1/2/4/8:

* per-shard M-matrix bytes (the paper's Fig 12 memory-extension claim),
* per-shard HLO flops (compute splits linearly),
* collective wire bytes per chip, as the mesh backend itself counts them
  (``MeshBackend.collective_bytes``: the communication the column batching
  bounds).

On CPU the mesh sizes run in a child process with eight virtual devices
(``XLA_FLAGS`` is read when the child's backend starts).  On a chip host they
run in this process over the real devices: a parent that holds the chips
cannot hand them to a child.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import record


def _strong_scaling(device_counts):
    """Per-shard compile figures and one timed launch per mesh size, over
    the first ``d`` devices of this process for each ``d``."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import CountingEngine, get_template, rmat_graph

    g = rmat_graph(16384, 160_000, seed=7)
    t = get_template("u7")
    colors = jnp.asarray(np.random.default_rng(0).integers(0, t.k, size=(1, g.n)))
    out = []
    for n_dev in device_counts:
        mesh = jax.make_mesh((n_dev,), ("data",), devices=jax.devices()[:n_dev])
        # the engine's mesh backend: one-coloring chunk for the per-shard probe
        eng = CountingEngine(g, [t], backend="mesh", mesh=mesh, column_batch=8,
                             chunk_size=1)
        run = eng.backend_impl.jit(eng.backend_impl.counts_for_colors)
        compiled = run.lower(colors).compile()
        val = float(run(colors)[0, 0])
        t0 = time.perf_counter()
        jax.block_until_ready(run(colors))
        dt = time.perf_counter() - t0
        ca = compiled.cost_analysis() or {}
        out.append({
            "devices": n_dev,
            "wall_s": dt,
            "flops_per_shard": ca.get("flops", 0.0),
            "bytes_per_shard": ca.get("bytes accessed", 0.0),
            "collective_bytes": eng.backend_impl.collective_bytes(1),
            "count": val,
        })
    return out


_CHILD = r"""
import json
from benchmarks.bench_scaling import _strong_scaling
print("RESULT " + json.dumps(_strong_scaling((1, 2, 4, 8))))
"""


def _cpu_env() -> dict:
    """Environment for a child with eight virtual CPU devices (the device
    count is read once, when the child's JAX backend starts)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(["src", "."])
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.pop("REPRO_MESH_COMM", None)
    return env


def run() -> None:
    import jax

    if jax.default_backend() == "cpu":
        proc = subprocess.run(
            [sys.executable, "-c", _CHILD], capture_output=True, text=True,
            env=_cpu_env(), timeout=900,
        )
        line = next(l for l in proc.stdout.splitlines() if l.startswith("RESULT "))
        data = json.loads(line[len("RESULT "):])
    else:
        # one process per chip: the mesh sizes run here, over real devices
        n = len(jax.devices())
        data = _strong_scaling(tuple(d for d in (1, 2, 4, 8) if d <= n))
    base = data[0]
    counts = [d["count"] for d in data]
    spread = (max(counts) - min(counts)) / max(abs(counts[0]), 1e-9)
    # fp32 reassociation across mesh sizes (the paper's Fig 14 effect)
    assert spread < 1e-5, f"count drifted beyond fp tolerance: {counts}"
    for d in data:
        record(
            f"fig13/strong_scaling/{d['devices']}dev",
            d["wall_s"] * 1e6,
            f"flops_per_shard_frac={d['flops_per_shard'] / max(base['flops_per_shard'], 1):.3f};"
            f"bytes_per_shard_frac={d['bytes_per_shard'] / max(base['bytes_per_shard'], 1):.3f}",
        )

