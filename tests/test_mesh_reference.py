"""The mesh backend against the plain reference, on four virtual devices.

``CountingEngine(..., mesh=<4 devices>)`` counts u12 and u7 on a seeded
R-MAT graph of 2,048 vertices with each comm schedule forced, and every
estimate is compared with ``bench/references/tree_dp.py`` (the benchmark's
plain float32 reference, which imports nothing of the program) on the same
colourings, drawn from the same keys.  A planted fault in the collective,
one ring hop that does not happen or one all-gather that brings back only
the chip's own rows, makes the comparison fail.

Each case runs in a child process (the test process keeps JAX's
single-device view).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Largest relative error allowed between the mesh and the reference.  Both
#: compute in float32 but sum in different orders (the mesh folds per-shard
#: edge buckets in ring order and splits colour sets by its own tables), so
#: they differ by rounding: about 2e-7 here.  Storing the states in bfloat16
#: errs by about 1e-5 on u12 (the benchmark's control, ``upper`` in
#: ``bench/limits/g500-s19-u12.json``), so 3e-6 holds every float32 ordering
#: and fails a program that loses float32 precision.
REL_TOL = 3e-6

CHILD = r"""
import json, sys
import jax, jax.numpy as jnp, numpy as np
from bench.graphgen import rmat_edges
from bench.references import tree_dp
from bench.run import KeyStream
from bench.treelets import plan_tree
from repro.core import CountingEngine
from repro.core.graph import Graph
from repro.core.templates import Template

comm, fault = sys.argv[1], sys.argv[2]
TEMPLATES = {
    "u12": ((0, 1), (0, 2), (1, 3), (1, 4), (2, 5), (2, 6), (3, 7), (4, 8), (5, 9), (6, 10), (10, 11)),
    "u7": ((0, 1), (1, 2), (1, 3), (0, 4), (4, 5), (4, 6)),
}

calls = []

def first_call_only(broken, real):
    # the first collective each program traces (its first stage's) goes
    # wrong, the rest run
    def patched(*args, **kwargs):
        calls.append(1)
        return broken(*args, **kwargs) if len(calls) == 1 else real(*args, **kwargs)
    return patched

if fault == "dropped_ring_step":
    # one hop does not happen: the chip consumes its own slice again
    jax.lax.ppermute = first_call_only(lambda x, axis_name, perm: x, jax.lax.ppermute)
elif fault == "local_gather":
    # the gather brings back the chip's own rows in every shard's place
    jax.lax.all_gather = first_call_only(
        lambda x, axis_name, axis=0, tiled=False: jnp.concatenate([x] * 4, axis=axis), jax.lax.all_gather)

g = rmat_edges(2**34 + 3, scale=11, edgefactor=8, a=0.57, b=0.19, c=0.19)
capacity = 2 * (8 << 11)
tree_dp.EDGE_CHUNK = capacity  # one scatter-add step over this small graph's edges
mesh = jax.make_mesh((4,), ("dev",))
keys = KeyStream(2**33 + 5, 3).next()
for name, edges in TEMPLATES.items():
    k = len(edges) + 1
    calls.clear()
    engine = CountingEngine(Graph(n=g.n, src=g.src, dst=g.dst), [Template(name, edges)], mesh=mesh,
                            mesh_comm=comm, chunk_size=len(keys))
    modes = {s["mode"] for s in engine.describe()["comm"]["schedule"]}
    got = engine.count_keys_chunk(keys)[:, 0]
    ref = tree_dp.TreeReference(plan_tree(edges, k), g.n, g.src, g.dst, capacity)
    want = np.array([ref.estimate(jax.random.randint(jnp.asarray(key), (g.n,), 0, k)) for key in keys])
    print(json.dumps({"template": name, "modes": sorted(modes),
                      "rel_err": float(np.max(np.abs(got - want) / np.abs(want)))}))
"""


def _errors(comm: str, fault: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(_REPO, "src"), _REPO])
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("REPRO_MESH_COMM", None)  # the cases force each schedule
    proc = subprocess.run([sys.executable, "-c", CHILD, comm, fault], capture_output=True, text=True,
                          env=env, timeout=600)
    assert proc.returncode == 0, f"child failed:\nstdout={proc.stdout}\nstderr={proc.stderr}"
    rows = [json.loads(line) for line in proc.stdout.splitlines() if line.startswith("{")]
    for row in rows:
        assert row["modes"] == [comm], row
    return {row["template"]: row["rel_err"] for row in rows}


@pytest.mark.parametrize("comm", ["blocking", "pipelined"])
def test_mesh_matches_reference(comm):
    errs = _errors(comm, "none")
    assert set(errs) == {"u12", "u7"}
    assert all(e <= REL_TOL for e in errs.values()), errs


@pytest.mark.parametrize("comm, fault", [("blocking", "local_gather"), ("pipelined", "dropped_ring_step")])
def test_planted_collective_fault_fails_the_comparison(comm, fault):
    errs = _errors(comm, fault)
    assert set(errs) == {"u12", "u7"}
    assert all(e > 100 * REL_TOL for e in errs.values()), errs
