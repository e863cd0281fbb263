import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf iteration 3 (paper core): bf16 compressed all-gathers on u20."""

import json
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import SUBGRAPH_SHAPES
from repro.core import build_counting_plan
from repro.core.distributed import distributed_input_specs, make_distributed_count_fn
from repro.core.templates import PAPER_TEMPLATES
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import collective_wire_bytes

mesh = make_production_mesh()
shape = [s for s in SUBGRAPH_SHAPES if s.name == "rmat1m_u20"][0]
plan = build_counting_plan(PAPER_TEMPLATES["u20"])
n_shards = mesh.devices.size
n_padded = ((shape.params["n_vertices"] + n_shards - 1) // n_shards) * n_shards
e_directed = 2 * shape.params["n_edges"]
edges_per_shard = ((int(e_directed / n_shards * 1.2) + 7) // 8) * 8

out = {"cell": "subgraph2vec/rmat1m_u20/single/streamed"}
for name, gd in (("fp32_gather", None), ("bf16_gather", jnp.bfloat16)):
    # split tables are built once inside the builder (jit constants)
    fn = make_distributed_count_fn(plan, mesh, n_padded, edges_per_shard,
                                   column_batch=128, ema_mode="streamed", gather_dtype=gd)
    specs = distributed_input_specs(n_padded, n_shards, edges_per_shard)
    every = tuple(mesh.axis_names)
    in_sh = tuple(NamedSharding(mesh, P(every)) for _ in specs)
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=in_sh).lower(*specs).compile()
    ms = compiled.memory_analysis()
    resident = ms.argument_size_in_bytes + ms.temp_size_in_bytes + max(
        ms.output_size_in_bytes - ms.alias_size_in_bytes, 0)
    coll, counts = collective_wire_bytes(compiled.as_text())
    out[name] = {"collective_bytes": float(coll), "resident_bytes": float(resident),
                 "collective_s_at_50GBs": coll / 50e9}
    print(name, json.dumps(out[name]))
os.makedirs("results/perf", exist_ok=True)
json.dump(out, open("results/perf/subgraph_u20_bf16.json", "w"), indent=1)
print("wrote results/perf/subgraph_u20_bf16.json")
