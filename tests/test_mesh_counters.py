"""The mesh backend's gauges (``repro.tracing.gauges``), on four virtual devices.

* ``mesh_collective_bytes`` equals ``(n_shards - 1) * rows_per_shard * B *
  columns * wire bytes`` with the columns summed over the stage tables of the
  plans (every executed stage's passive columns, padded to whole column
  batches), the same for blocking and pipelined, and half as much when the
  states are stored in bfloat16;
* ``mesh_edge_slots`` and ``mesh_edges`` are ``shard_graph``'s padded and
  real edges;
* the compiled launch has the same operations with the gauges published as
  without them.

Each check runs in a child process (the test process keeps JAX's
single-device view).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r"""
import json, re, sys
from math import comb
import jax, numpy as np
from repro import tracing
from repro.core import CountingEngine, get_template, rmat_graph
from repro.exec.base import EngineBackend
from repro.exec.mesh import MeshBackend

g = rmat_graph(400, 2400, seed=7)
mesh = jax.make_mesh((4,), ("dev",))
templates = [get_template(name) for name in sys.argv[1].split(",")]
CHUNK = 3


def moved_columns(engine):
    # the program's stage walk: each rooted canonical form runs once and
    # aggregates its passive state
    cb = engine.backend_impl.column_batch
    done, cols = set(), 0
    for plan, canons in zip(engine.plans, engine.plan_ir.canons):
        for i, sub in enumerate(plan.partition.subs):
            if canons[i] in done:
                continue
            done.add(canons[i])
            if sub.is_leaf:
                continue
            cols += -(-comb(plan.k, plan.tables[i].m_p) // cb) * cb
    return cols


def build(**kw):
    return CountingEngine(g, templates, mesh=mesh, chunk_size=CHUNK, **kw)


out = {}
for comm in ("blocking", "pipelined"):
    for policy, wire in (("fp32", 4), ("bf16", 2)):
        engine = build(mesh_comm=comm, dtype_policy=policy)
        sg = engine.backend_impl.sharded
        got = tracing.gauges()
        out[f"{comm}/{policy}"] = {
            "bytes": got["mesh_collective_bytes"],
            "want": (sg.n_shards - 1) * sg.rows_per_shard * CHUNK * moved_columns(engine) * wire,
            "chunk": got["mesh_chunk"],
            "slots": got["mesh_edge_slots"],
            "edges": got["mesh_edges"],
            "geometry": [sg.n_shards * sg.edges_per_shard, int(sg.src.shape[0]),
                         int(sg.edge_mask.sum()), g.num_directed],
        }


def launch_ops(engine):
    text = engine._compiled(engine._get_chunk_fn(), (CHUNK, 2)).as_text()
    return sorted(re.findall(r"=\s*\S+\s+([a-z][\w-]*)\(", text))


with_gauges = launch_ops(build())
MeshBackend.publish_gauges = EngineBackend.publish_gauges
without = launch_ops(build())
out["ops"] = {"with": len(with_gauges), "same": with_gauges == without,
              "collectives": sum(op in ("all-gather", "collective-permute") for op in with_gauges)}
print(json.dumps(out))
"""


def _child(templates: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("REPRO_MESH_COMM", None)  # the child forces each schedule
    proc = subprocess.run([sys.executable, "-c", CHILD, templates], capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, f"child failed:\nstdout={proc.stdout}\nstderr={proc.stderr}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module", params=["u12", "u5-1,u5-2"])
def gauges(request):
    return _child(request.param)


RUNS = [f"{comm}/{policy}" for comm in ("blocking", "pipelined") for policy in ("fp32", "bf16")]


def test_collective_bytes_follow_the_stage_tables(gauges):
    for key in RUNS:
        assert gauges[key]["bytes"] == gauges[key]["want"] > 0, key
        assert gauges[key]["chunk"] == 3
    assert gauges["blocking/fp32"]["bytes"] == gauges["pipelined/fp32"]["bytes"]
    assert gauges["blocking/bf16"]["bytes"] * 2 == gauges["blocking/fp32"]["bytes"]


def test_edge_gauges_are_shard_graph_geometry(gauges):
    for key in RUNS:
        slots, real = gauges[key]["slots"], gauges[key]["edges"]
        assert gauges[key]["geometry"] == [slots, slots, real, real]
        assert real < slots


def test_gauges_add_no_operation_to_the_launch(gauges):
    assert gauges["ops"]["same"] is True
    assert gauges["ops"]["collectives"] > 0
