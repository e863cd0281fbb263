"""Distributed SUBGRAPH2VEC through the CountingEngine mesh backend.

Runs the engine's ``mesh`` backend (vertex 1-D partition + column-batched
all-gather SpMM + streamed eMA under ``shard_map``) on a multi-device host
mesh and cross-checks against the single-device local engine.

  PYTHONPATH=src python examples/distributed_counting.py                     # every chip
  JAX_PLATFORMS=cpu PYTHONPATH=src python examples/distributed_counting.py   # 8 CPU devices

On an accelerator host the mesh spans every chip.  On the CPU backend it
spans virtual host devices: 8 by default, or what
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` asks for.
"""

import os

if os.environ.get("JAX_PLATFORMS") == "cpu":
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import numpy as np

from repro.core import CountingEngine, get_template, rmat_graph


def main():
    mesh = jax.make_mesh((len(jax.devices()),), ("dev",))
    print(f"mesh: {dict(mesh.shape)} = {mesh.devices.size} devices")

    graph = rmat_graph(2048, 20_000, seed=11)
    template = get_template("u7")

    # The mesh backend shards the graph once (degree-balanced row partition),
    # builds the split tables once, and runs chunks of colorings batched
    # through the column-batched all-gather SpMM + streamed eMA.
    engine = CountingEngine(
        graph,
        [template],
        backend="mesh",
        mesh=mesh,
        column_batch=16,
        balance_degrees=True,
    )
    sharded = engine.backend_impl.sharded
    print(
        f"graph: {graph.n} vertices; {sharded.edges_per_shard} edges/shard "
        f"(degree-balanced); chunk_size={engine.chunk_size} "
        f"column_batch={engine.backend_impl.column_batch}"
    )

    result = engine.estimate(iterations=8, seed=0)[0]
    print(
        f"distributed estimate: {result.mean:.4g} "
        f"(std over colorings {result.std:.3g}, {result.iterations} iterations)"
    )

    # cross-check one fixed coloring against the single-device local engine
    colors = np.random.default_rng(0).integers(0, template.k, size=graph.n)
    local = CountingEngine(graph, [template], backend="edges")
    raw_mesh = float(engine.raw_counts(colors)[0])
    raw_local = float(local.raw_counts(colors)[0])
    rel = abs(raw_mesh - raw_local) / max(abs(raw_local), 1e-9)
    print(f"mesh vs local engine: {raw_mesh:.6g} vs {raw_local:.6g} (rel err {rel:.2e})")
    assert rel < 1e-5


if __name__ == "__main__":
    main()
