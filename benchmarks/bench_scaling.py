"""Fig 13 analog: strong scaling of distributed SUBGRAPH2VEC.

On virtual CPU devices, wall-time across device counts measures dispatch
overhead, not hardware scaling; the strong-scaling evidence there is the
**per-shard resource scaling** extracted from the compiled artifact at mesh
sizes 1/2/4/8:

* per-shard M-matrix bytes (the paper's Fig 12 memory-extension claim),
* per-shard HLO flops (compute splits linearly),
* all-gather wire bytes (the communication the column batching bounds).

The ``fig13/ring`` row family is the wall-clock half: an interleaved A/B
of ``mesh_comm="blocking"`` vs ``"pipelined"`` (same process, same graph,
alternating arms) through the ``scripts/perf_subgraph_u20.py`` driver, at a
working-set size where the all-gathered column buffer falls out of cache
but the ring's two circulating slices do not.  Each row records the
pipelined us/coloring plus ``ratio=`` (pipelined/blocking, < 1.0 is a ring
win), ``per_shard_byte_frac`` (transient footprint of the ring arm as a
fraction of blocking's), and ``overlap_eff`` (measured fraction of the
modeled wire time hidden).

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
import tempfile

from .common import record


def _strong_scaling(device_counts):
    """Per-shard compile figures and one timed launch per mesh size, over
    the first ``d`` devices of this process for each ``d``."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import CountingEngine, get_template, rmat_graph
    from repro.launch.roofline import collective_wire_bytes

    g = rmat_graph(16384, 160_000, seed=7)
    t = get_template("u7")
    colors = jnp.asarray(np.random.default_rng(0).integers(0, t.k, size=(1, g.n)))
    out = []
    for n_dev in device_counts:
        mesh = jax.make_mesh((n_dev,), ("data",), devices=jax.devices()[:n_dev])
        # the engine's mesh backend: one-coloring chunk for the per-shard probe
        eng = CountingEngine(g, [t], backend="mesh", mesh=mesh, column_batch=8,
                             ema_mode="loop", chunk_size=1)
        run = eng.backend_impl.jit(eng.backend_impl.counts_for_colors)
        compiled = run.lower(colors).compile()
        val = float(run(colors)[0, 0])
        t0 = time.perf_counter()
        jax.block_until_ready(run(colors))
        dt = time.perf_counter() - t0
        ca = compiled.cost_analysis() or {}
        coll, _ = collective_wire_bytes(compiled.as_text())
        out.append({
            "devices": n_dev,
            "wall_s": dt,
            "flops_per_shard": ca.get("flops", 0.0),
            "bytes_per_shard": ca.get("bytes accessed", 0.0),
            "collective_bytes": coll,
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
    _run_ring()


def _ring_args(n_dev: int, out: str) -> list:
    return [
        "--devices", str(n_dev), "--template", "u7",
        "--n", "65536", "--edges", "262144",
        "--column-batch", "256", "--chunk-size", "2",
        "--iters", "2", "--repeats", "2", "--out", out,
    ]


def _run_ring() -> None:
    """fig13/ring rows: interleaved blocking-vs-pipelined A/B per mesh size.

    Runs the perf driver (it owns the interleaving discipline); the config
    is sized so the all-gathered buffer (n_padded x B x cb ~ 256 MB) spills
    cache while a ring slice does not.  On CPU each mesh size runs in a
    child with virtual devices; on a chip host it runs here, over real
    devices.
    """
    import jax

    on_cpu = jax.default_backend() == "cpu"
    sizes = (4, 8) if on_cpu else tuple(d for d in (4, 8) if d <= len(jax.devices()))
    for n_dev in sizes:
        out = os.path.join(tempfile.mkdtemp(prefix="fig13_ring_"), "ab.json")
        if on_cpu:
            subprocess.run(
                [sys.executable, "scripts/perf_subgraph_u20.py", *_ring_args(n_dev, out)],
                check=True, capture_output=True, text=True, env=_cpu_env(), timeout=1800,
            )
        else:
            _perf_driver().main(_ring_args(n_dev, out))
        with open(out) as fh:
            ab = json.load(fh)
        assert ab["bit_exact"], f"A/B arms diverged at {n_dev} devices"
        record(
            f"fig13/ring/{n_dev}dev",
            ab["pipelined"]["us_per_coloring"],
            f"ratio={ab['ratio_pipelined_vs_blocking']:.3f};"
            f"per_shard_byte_frac={ab['per_shard_byte_fraction']:.3f};"
            f"overlap_eff={ab['measured_overlap_efficiency']:.2f}",
        )


def _perf_driver():
    """``scripts/perf_subgraph_u20.py`` as a module (scripts/ is no package)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "perf_subgraph_u20", os.path.join("scripts", "perf_subgraph_u20.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
