"""The local backends against the plain reference.

``CountingEngine`` counts the paper's trees u3–u12 on a seeded R-MAT graph
of 512 vertices, and every estimate is compared with
``bench/references/tree_dp.py`` (the benchmark's plain float32 reference,
which imports nothing of the program: its own decomposition, split tables
and automorphism count) on the same colourings, drawn from the same keys.
The in-program oracles (``count_colorful_vectorized``, the two-pass
reference) share the engine's split tables and partitions, so a fault in
those passes a comparison with them; it fails this one.

One case per path: every pure-JAX local backend through ``count_keys`` with
the engine's own chunk, the launch the benchmark times (``count_keys_chunk``
at chunk 3), the bf16 dtype policy, and the fused Pallas kernel of the
``blocked`` backend in interpret mode.  Every engine aggregates 128 passive
columns a sweep, the column batch a TPU picks (one lane tile); the CPU's
16 would compile u12's program for about five times as long.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.graphgen import rmat_edges
from bench.references import tree_dp
from bench.run import KeyStream
from bench.treelets import plan_tree
from repro.core import CountingEngine, get_template
from repro.core.graph import Graph

TEMPLATES = ["u3", "u5-1", "u5-2", "u6", "u7", "u10", "u12"]

#: Largest relative error allowed in float32.  Both sides compute in float32
#: but sum in different orders (the engine's edge chunks and colour-set
#: batches against the reference's own), so they differ by rounding; the
#: mesh is held to the same bound (``tests/test_mesh_reference.py``).
REL_TOL = 3e-6
#: The same for states stored in bfloat16 (8 bits of mantissa; about 1e-5
#: on u12 at the benchmark's size, ``bench/limits/g500-s19-u12.json``).
BF16_TOL = 2e-2

SCALE, EDGEFACTOR = 9, 8
CAPACITY = 2 * (EDGEFACTOR << SCALE)


@pytest.fixture(scope="module")
def graph():
    return rmat_edges(2**35 + 11, scale=SCALE, edgefactor=EDGEFACTOR, a=0.57, b=0.19, c=0.19)


@pytest.fixture(scope="module")
def keys():
    return KeyStream(2**35 + 17, 3).next()


@pytest.fixture(scope="module")
def reference(graph, keys):
    """``name -> (3,)`` estimates of the plain reference, computed once."""
    cache = {}
    with pytest.MonkeyPatch.context() as mp:
        # one scatter-add step over this small graph's edges
        mp.setattr(tree_dp, "EDGE_CHUNK", CAPACITY)

        def estimates(name):
            if name not in cache:
                t = get_template(name)
                ref = tree_dp.TreeReference(plan_tree(t.edges, t.k), graph.n, graph.src, graph.dst, CAPACITY)
                cache[name] = np.array(
                    [ref.estimate(jax.random.randint(jnp.asarray(key), (graph.n,), 0, t.k)) for key in keys]
                )
            return cache[name]

        yield estimates


def _engine(graph, name, **kw):
    return CountingEngine(
        Graph(n=graph.n, src=graph.src, dst=graph.dst), [get_template(name)], column_batch=128, **kw
    )


def _rel_err(got, want):
    assert np.all(want > 0), want
    return float(np.max(np.abs(got - want) / want))


@pytest.mark.parametrize("tname", TEMPLATES)
@pytest.mark.parametrize("backend", ["edges", "ell", "sell", "dense"])
def test_backend_matches_reference(graph, keys, reference, backend, tname):
    engine = _engine(graph, tname, backend=backend)
    assert engine.backend == backend
    got = engine.count_keys(keys)[:, 0]
    assert _rel_err(got, reference(tname)) <= REL_TOL


@pytest.mark.parametrize("tname", TEMPLATES)
def test_launch_matches_reference(graph, keys, reference, tname):
    engine = _engine(graph, tname, backend="edges", chunk_size=len(keys))
    got = engine.count_keys_chunk(keys)[:, 0]
    assert _rel_err(got, reference(tname)) <= REL_TOL


@pytest.mark.parametrize("tname", TEMPLATES)
def test_bf16_policy_matches_reference(graph, keys, reference, tname):
    engine = _engine(graph, tname, backend="edges", chunk_size=len(keys), dtype_policy="bf16")
    got = engine.count_keys_chunk(keys)[:, 0]
    assert _rel_err(got, reference(tname)) <= BF16_TOL


@pytest.mark.parametrize("tname", ["u3", "u5-1", "u5-2", "u6", "u7"])
def test_blocked_kernel_matches_reference(graph, keys, reference, tname):
    engine = _engine(graph, tname, backend="blocked", chunk_size=len(keys), interpret=True)
    got = engine.count_keys_chunk(keys)[:, 0]
    assert _rel_err(got, reference(tname)) <= REL_TOL
